"""Plain float32 reference of a dense GQA decoder, its question encoder and
the IVF-PQ search of a RAG request, written from the published equations
and independent of the program under test.

Decoder layer (pre-norm):  x += Wo . attn(rope(Wq . n1(x)), rope(Wk . n1(x)),
Wv . n1(x));  x += Wd . (silu(Wg . n2(x)) * (Wu . n2(x))),  n = RMSNorm.
Rotary embedding turns the leading ``rotary_dims`` of every head by
position in interleaved pairs (ChatGLM's layout; Llama-style models that
rotate halves are the same function after a fixed permutation of the
Wq and Wk columns, which random weights do not distinguish).  Softmax
scale 1/sqrt(head_dim).  The encoder is the same layer, bidirectional,
mean-pooled over the question's tokens and L2-normalised.

IVF-PQ: the coarse quantizer probes the ``nprobe`` nearest centroids; a
vector's distance is the squared L2 distance from the query to
``centroid + concat_s(codebook_s[code_s])``, by asymmetric lookup.

Every matmul runs at ``Precision.HIGHEST`` on float32 values.  ``mode=
"fp8"`` is the control: the same functions with the inputs of every weight
matmul rounded to float8 e4m3 (per-column weight scales, per-row activation
scales) and the retrieval distances in bfloat16 -- the step below the bf16
weights and float32 index that the configurations state.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                       # largest finite float8_e4m3fn
ROWS = 128                           # questions per reference program


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta, rot):
    """x: (T, H, D); interleaved pairs over the leading ``rot`` dims."""
    if rot == 0:
        return x
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([r.reshape(x.shape[:-1] + (rot,)), x[..., rot:]],
                           axis=-1)


def _layer(x, lp, *, arch, eps, theta, rot, causal, valid, mode):
    """One layer over a (T, d) float32 sequence; ``valid`` (T,) masks the
    keys past the sequence's length."""
    n_layers, d, h, kv, dh, f, vocab = arch
    t = x.shape[0]
    pos = jnp.arange(t)
    hn = _rms(x, lp["ln1"], eps)
    q = _rope(_mm(hn, lp["wq"], mode).reshape(t, h, dh), pos, theta, rot)
    k = _rope(_mm(hn, lp["wk"], mode).reshape(t, kv, dh), pos, theta, rot)
    v = _mm(hn, lp["wv"], mode).reshape(t, kv, dh)
    q = q.reshape(t, kv, h // kv, dh)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HIGHEST) / math.sqrt(dh)
    mask = valid[None, :]
    if causal:
        mask = mask & (pos[None, :] <= pos[:, None])
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(t, h * dh), lp["wo"], mode)
    hn = _rms(x, lp["ln2"], eps)
    up = _mm(hn, lp["w_up"], mode)
    gate = _mm(hn, lp["w_gate"], mode)
    return x + _mm(jax.nn.silu(gate) * up, lp["w_down"], mode)


def _trunk(params, tokens, length, *, arch, eps, theta, rot, causal, mode):
    valid = jnp.arange(tokens.shape[0]) < length
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(x, lp, arch=arch, eps=eps, theta=theta, rot=rot,
                      causal=causal, valid=valid, mode=mode), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms(x, params["ln_f"], eps)


@partial(jax.jit, static_argnames=("arch", "eps", "theta", "rot", "mode"))
def decoder_logits(params, tokens, *, arch, eps, theta, rot, mode="f32"):
    """(T,) token ids -> (T, vocab) float32 next-token logits."""
    x = _trunk(params, tokens, tokens.shape[0], arch=arch, eps=eps,
               theta=theta, rot=rot, causal=True, mode=mode)
    return _mm(x, params["head"][:, :arch[6]], mode)


@partial(jax.jit, static_argnames=("arch", "eps", "theta", "rot", "mode"))
def encode(params, tokens, lengths, *, arch, eps, theta, rot, mode="f32"):
    """(N, T) padded questions with their lengths -> (N, dim) unit vectors:
    bidirectional trunk, mean over the valid tokens."""
    def one(tok, n):
        x = _trunk(params, tok, n, arch=arch, eps=eps, theta=theta, rot=rot,
                   causal=False, mode=mode)
        w = (jnp.arange(tok.shape[0]) < n).astype(jnp.float32)[:, None]
        v = jnp.sum(x * w, axis=0) / n
        return v / jnp.linalg.norm(v)
    return jax.lax.map(lambda a: one(*a), (tokens, lengths))


def _dot(a, b, mode):
    if mode == "fp8":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(a, b, precision=HIGHEST)


def _list_distances(q, lst, cent, books, codes, mode="f32"):
    """Squared L2 distances from q to every vector of list ``lst`` by
    asymmetric lookup: (list_len,)."""
    n_subq, _, dsub = books.shape
    res = (q - cent[lst]).reshape(n_subq, 1, dsub)
    diff = res - books
    if mode == "fp8":
        diff = diff.astype(jnp.bfloat16)
    lut = jnp.sum(diff * diff, axis=-1).astype(jnp.float32)   # (S, 256)
    c = codes[lst].astype(jnp.int32)                          # (LL, S)
    return jnp.sum(jnp.take_along_axis(lut[None], c[..., None], axis=-1)
                   [..., 0], axis=-1)


def _coarse(q, cent, mode="f32"):
    return jnp.sum(cent * cent, axis=1) - 2.0 * _dot(cent, q, mode)


@partial(jax.jit, static_argnames=("nprobe", "k", "mode"))
def ivfpq_search(index, queries, *, nprobe, k, mode="f32"):
    """Per query: the k nearest ids over the ``nprobe`` nearest lists."""
    cent, books, codes = (index["centroids"], index["codebooks"],
                          index["list_codes"])
    list_len = codes.shape[1]

    def one(q):
        _, probe = jax.lax.top_k(-_coarse(q, cent, mode), nprobe)
        d = jax.vmap(lambda lst: _list_distances(q, lst, cent, books, codes,
                                                 mode))(probe).reshape(-1)
        ids = (probe[:, None] * list_len
               + jnp.arange(list_len)[None]).reshape(-1)
        _, at = jax.lax.top_k(-d, k)
        return ids[at]

    return jax.lax.map(one, queries)


@partial(jax.jit, static_argnames=("nprobe",))
def retrieval_excess(index, queries, ids, *, nprobe):
    """Per query (N, dim) and its returned ids (N, k): how far the answer
    lies from the reference's, in units of the spread it was chosen from.

    The lists of the returned ids must be among the query's ``nprobe``
    nearest: a list's excess is its coarse distance over the
    ``nprobe``-th nearest's, in units of the coarse distances' spread.
    Within those lists the ids must be the k nearest: an id's excess is
    its distance over the k-th nearest of the lists' vectors, in units of
    the spread of the probed vectors' distances.  Both are 0 for the
    reference's own answer and grow with the error of the query that
    chose the answer, not with how near two candidates happened to be
    at a list boundary."""
    cent, books, codes = (index["centroids"], index["codebooks"],
                          index["list_codes"])
    list_len = codes.shape[1]
    k = ids.shape[1]

    def one(q, row):
        coarse = _coarse(q, cent)
        neg, probe = jax.lax.top_k(-coarse, nprobe)
        lists = row // list_len
        list_gap = jnp.max(jnp.maximum(coarse[lists] + neg[-1], 0.0)) \
            / jnp.std(coarse)
        dist = jax.vmap(lambda lst: _list_distances(q, lst, cent, books,
                                                    codes))
        spread = jnp.std(dist(probe))
        d = dist(lists)                                      # (k, LL)
        # a list that comes twice counts once
        first = jnp.argmax(lists[None, :] == lists[:, None], axis=1) \
            == jnp.arange(k)
        d_lists = jnp.where(first[:, None], d, jnp.inf).reshape(-1)
        kth = -jax.lax.top_k(-d_lists, k)[0][-1]
        d_ids = d[jnp.arange(k), row % list_len]
        id_gap = jnp.max(jnp.maximum(d_ids - kth, 0.0)) / spread
        return jnp.maximum(list_gap, id_gap)

    return jax.lax.map(lambda a: one(*a), (queries, ids))


def decoder_kwargs(model: dict) -> dict:
    from bench.model import arch_of, rotary_dims
    return {"arch": arch_of(model), "eps": float(model["rms_norm_eps"]),
            "theta": float(model["rope_theta"]),
            "rot": rotary_dims(model["head_dim"],
                               model["partial_rotary_factor"])}


def retrieval_gaps(data, mode):
    """Widest ``retrieval_excess`` over the compared questions, against
    the reference's float32 query vectors.  The control's answers are
    its own search's, from its own encoder, in its own precision."""
    model = data["model"]
    enc = dict(model["encoder"], vocab_size=model["vocab_size"])
    qs = data["questions"]
    n = len(qs)
    # rows padded to a multiple of ROWS, so each run reuses one program
    rows = -(-n // ROWS) * ROWS
    width = max(len(q) for q in qs)
    toks = np.zeros((rows, width), np.int32)
    lens = np.ones(rows, np.int32)
    for i, q in enumerate(qs):
        toks[i, :len(q)] = q
        lens[i] = len(q)
    toks, lens = jnp.asarray(toks), jnp.asarray(lens)
    qv_ref = encode(data["enc_params"], toks, lens, **decoder_kwargs(enc))
    nprobe, k = model["index"]["nprobe"], data["k"]
    if mode == "f32":
        ids = np.zeros((rows, k), np.int32)
        ids[:n] = np.asarray(data["retrieved"], np.int32)
        ids = jnp.asarray(ids)
    else:
        qv = encode(data["enc_params"], toks, lens, **decoder_kwargs(enc),
                    mode=mode)
        ids = ivfpq_search(data["index"], qv, nprobe=nprobe, k=k, mode=mode)
    gap = retrieval_excess(data["index"], qv_ref, ids, nprobe=nprobe)
    return float(jnp.max(gap[:n]))


def position_gaps(data, mode) -> np.ndarray:
    """Per served position of the sample, the gap by which the served
    token's reference logit lies below the reference's best.  For the
    control, the token is the one the control puts first."""
    kw = decoder_kwargs(data["model"])
    gaps = []
    for seq in data["sequences"]:
        prompt, served = seq["prompt"], seq["served"]
        toks = np.zeros(data["seq_len"], np.int32)
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks[:len(full)] = full
        rows = np.arange(len(prompt) - 1, len(full))
        ref = decoder_logits(data["params"], jnp.asarray(toks), **kw)
        ref = np.asarray(ref[rows])
        if mode == "f32":
            picked = np.asarray(served)
        else:
            ctl = decoder_logits(data["params"], jnp.asarray(toks), **kw,
                                 mode=mode)
            picked = np.asarray(jnp.argmax(ctl[rows], axis=-1))
        gaps.append(ref.max(axis=1) - ref[np.arange(len(rows)), picked])
    return np.concatenate(gaps)


def logit_gaps(data, mode):
    """Widest of ``position_gaps``."""
    return float(position_gaps(data, mode).max())


def compare(data, mode="f32") -> dict:
    """The numbers that decide ``correct`` for one run."""
    return {"logit_gap": logit_gaps(data, mode),
            "retrieval_gap": retrieval_gaps(data, mode)}
