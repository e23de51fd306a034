"""Transformer model-zoo unit tests: parity, MoE, quantization."""

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, hst, settings

from repro.models import common as cm
from repro.models import transformer as tr

TINY = tr.TransformerConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                            n_kv_heads=2, d_head=16, d_ff=96, vocab_size=256)
TINY_MOE = tr.TransformerConfig(name="tm", n_layers=2, d_model=64, n_heads=4,
                                n_kv_heads=2, d_head=16, d_ff=64,
                                vocab_size=256,
                                moe=tr.MoEConfig(n_experts=8, top_k=2))


@pytest.fixture(scope="module")
def params():
    return tr.init_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture(scope="module")
def moe_params():
    return tr.init_params(jax.random.PRNGKey(0), TINY_MOE)


def _toks(b, s, v=256, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, v)


def test_forward_shapes_no_nan(params):
    logits, aux = tr.forward(params, _toks(2, 16), TINY)
    assert logits.shape == (2, 16, TINY.padded_vocab)
    assert not bool(jnp.isnan(logits).any())


def _pool(cfg, n_pages, page, kv=None, tables=None):
    """A bf16 page pool; with ``kv`` (forward's (L, B, T, H, D) K/V), each
    sequence b's positions laid into the pages ``tables[b]``."""
    row = cfg.n_kv_heads * cfg.d_head
    pool = {k: jnp.zeros((cfg.n_layers, n_pages, page, row), jnp.bfloat16)
            for k in ("k", "v")}
    if kv is None:
        return pool
    B, M = tables.shape
    L, _, T = kv["k"].shape[:3]
    pad = ((0, 0), (0, 0), (0, M * page - T), (0, 0), (0, 0))
    return {k: pool[k].at[:, np.asarray(tables).reshape(-1)].set(
                jnp.pad(kv[k], pad).reshape(L, B * M, page, row))
            for k in pool}


def _rows(cfg, pool, block_row, n):
    """Positions [0, n) of one sequence's pages: (L, n, H_kv*D) float32."""
    L, _, page, row = pool.shape
    return np.asarray(pool[:, np.asarray(block_row)].reshape(
        L, -1, row)[:, :n], np.float32)


def test_decode_matches_forward_exactly(params):
    """A paged decode step after a forward prefill reproduces the
    forward's last-position logits bit for bit."""
    toks = _toks(2, 12)
    full, _ = tr.forward(params, toks, TINY)
    _, _, kv = tr.forward(params, toks[:, :-1], TINY, collect_cache=True)
    tables = np.random.default_rng(0).permutation(8).reshape(2, 4)
    pool = _pool(TINY, 9, 4, kv, tables)
    step_logits, _ = tr.paged_decode_step(
        params, pool, toks[:, -1], jnp.full((2,), 11, jnp.int32),
        jnp.asarray(tables, jnp.int32), TINY)
    np.testing.assert_allclose(np.asarray(full[:, -1]),
                               np.asarray(step_logits), rtol=0, atol=0)


def test_multi_step_decode_matches_forward(params):
    toks = _toks(1, 10)
    full, _ = tr.forward(params, toks, TINY)
    _, _, kv = tr.forward(params, toks[:, :4], TINY, collect_cache=True)
    tables = np.asarray([[2, 0, 3, 1]])
    pool = _pool(TINY, 5, 4, kv, tables)
    step = jax.jit(partial(tr.paged_decode_step, cfg=TINY))
    for i in range(4, 10):
        logits, pool = step(
            params, pool, toks[:, i - 1], jnp.full((1,), i - 1, jnp.int32),
            jnp.asarray(tables, jnp.int32))
        # feed true token: logits must match teacher-forced forward at i-1
        np.testing.assert_allclose(np.asarray(full[:, i - 1]),
                                   np.asarray(logits), atol=1e-2)


def test_moe_forward_and_grads(moe_params):
    toks = _toks(2, 16)
    loss = tr.loss_fn(moe_params, toks[:, :-1], toks[:, 1:], TINY_MOE)
    assert np.isfinite(float(loss))
    g = jax.grad(tr.loss_fn)(moe_params, toks[:, :-1], toks[:, 1:], TINY_MOE)
    flat = jax.tree_util.tree_leaves(g)
    assert all(bool(jnp.isfinite(x).all()) for x in flat)
    # expert weights actually receive gradient
    assert float(jnp.abs(g["layers"]["w_up"]).max()) > 0


def test_moe_capacity_drops_are_bounded(moe_params):
    """With capacity factor >= 1 and uniform-ish routing most tokens keep."""
    toks = _toks(4, 32)
    logits, aux = tr.forward(moe_params, toks, TINY_MOE)
    assert float(aux) < 4.0  # aux ~1 when balanced, E when collapsed


def test_relu2_variant():
    cfg = tr.TransformerConfig(name="r2", n_layers=2, d_model=32, n_heads=2,
                               n_kv_heads=2, d_head=16, d_ff=64,
                               vocab_size=128, ffn_type="relu2")
    p = tr.init_params(jax.random.PRNGKey(0), cfg)
    assert "w_gate" not in p["layers"]
    logits, _ = tr.forward(p, _toks(2, 8, 128), cfg)
    assert not bool(jnp.isnan(logits).any())


def test_param_count_matches_init(params, moe_params):
    def count(p):
        return sum(x.size for x in jax.tree_util.tree_leaves(p))
    pad_extra = 2 * (TINY.padded_vocab - TINY.vocab_size) * TINY.d_model
    assert count(params) == TINY.param_count() + pad_extra
    pad_extra_m = 2 * (TINY_MOE.padded_vocab
                       - TINY_MOE.vocab_size) * TINY_MOE.d_model
    assert count(moe_params) == TINY_MOE.param_count() + pad_extra_m


def test_int8_quantization_roundtrip(params):
    q = tr.quantize_for_serving(params)
    w = params["layers"]["wq"]
    deq = cm.dequantize_int8(q["layers"]["wq"], jnp.float32)
    err = jnp.abs(w - deq).max() / (jnp.abs(w).max() + 1e-9)
    assert float(err) < 1.0 / 100  # per-channel int8: <1% of range


def test_quantized_forward_close(params):
    qp = tr.quantize_for_serving(params)
    toks = _toks(2, 16)
    a, _ = tr.forward(params, toks, TINY)
    b, _ = tr.forward(qp, toks, TINY)
    # compare softmax distributions, not raw logits
    pa = jax.nn.softmax(a.astype(jnp.float32), -1)
    pb = jax.nn.softmax(b.astype(jnp.float32), -1)
    assert float(jnp.abs(pa - pb).max()) < 0.15


@settings(max_examples=10, deadline=None)
@given(s=hst.integers(8, 64), block=hst.sampled_from([8, 16, 32]))
def test_chunked_attention_matches_naive(s, block):
    q = jax.random.normal(jax.random.PRNGKey(0), (2, s, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, s, 4, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, s, 4, 16))
    a = cm.naive_causal_attention(q, k, v)
    b = cm.chunked_causal_attention(q, k, v, block_kv=block)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_sliding_window_masks_old_tokens():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 8))
    out_full = cm.naive_causal_attention(q, q, q)
    out_win = cm.naive_causal_attention(q, q, q, window=4)
    # early tokens (inside window) identical, late tokens differ
    np.testing.assert_allclose(np.asarray(out_full[:, :4]),
                               np.asarray(out_win[:, :4]), atol=1e-6)
    assert float(jnp.abs(out_full[:, -1] - out_win[:, -1]).max()) > 1e-4


def test_encode_is_normalized(params):
    e = tr.encode(params, _toks(3, 10), TINY)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(e, axis=-1)),
                               1.0, atol=1e-3)


def test_rope_partial_fraction():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 16))
    pos = jnp.arange(4)[None]
    full = cm.apply_rope(x, pos, 1e4, 1.0)
    half = cm.apply_rope(x, pos, 1e4, 0.5)
    # pass-through dims untouched under partial rotary
    np.testing.assert_allclose(np.asarray(half[..., 8:]),
                               np.asarray(x[..., 8:]), atol=0)
    assert float(jnp.abs(full[..., 8:] - x[..., 8:]).max()) > 1e-4


def test_chunk_extend_matches_sequential_decode(params):
    """Bucketed paged chunk extend == feeding the tokens one paged decode
    step at a time, with pad rows dropped and other sequences' pages
    untouched."""
    page, m, slot, plen = 4, 4, 1, 5
    tables = np.arange(12).reshape(3, m)[[2, 0, 1]]
    _, _, kv = tr.forward(params, _toks(1, plen), TINY, collect_cache=True)
    pool = _pool(TINY, 13, page, kv, tables[slot:slot + 1])
    tokens = np.asarray([7, 11, 3, 9, 22], np.int32)

    seq = dict(pool)
    mask = jnp.asarray(np.arange(3) == slot)
    step = jax.jit(partial(tr.paged_decode_step, cfg=TINY))
    for i, t in enumerate(tokens):
        step_logits, seq = step(
            params, seq, jnp.full((3,), t, jnp.int32),
            jnp.full((3,), plen + i, jnp.int32), jnp.asarray(tables),
            write_mask=mask)

    padded = np.zeros(8, np.int32)           # bucket 8 > 5 valid tokens
    padded[:len(tokens)] = tokens
    chunk, logits = tr.paged_chunk_extend(
        params, pool, jnp.asarray(tables[slot]), jnp.asarray(padded),
        jnp.int32(plen), jnp.int32(len(tokens)), TINY)
    end = plen + len(tokens)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(step_logits[slot], np.float32),
                               atol=5e-2)
    for k in ("k", "v"):
        np.testing.assert_allclose(_rows(TINY, chunk[k], tables[slot], end),
                                   _rows(TINY, seq[k], tables[slot], end),
                                   rtol=2e-2, atol=2e-2)
        # pad rows were dropped, other sequences' pages stayed zero
        rest = _rows(TINY, chunk[k], tables[slot], m * page)[:, end:]
        assert float(np.abs(rest).max()) == 0.0
        for other in (0, 2):
            assert float(jnp.abs(chunk[k][:, tables[other]]).max()) == 0.0


# The fold's safety net: the serving entry points share one layer body and
# differ in how they write and attend, so for one token sequence every
# route through them must give forward's logits and forward's K/V rows.
# MoE runs with capacity_factor = n_experts / top_k, where moe_ffn drops
# no token at any sequence length.
ROUTE_CFGS = {
    "dense": TINY,
    "moe": replace(TINY_MOE, moe=tr.MoEConfig(n_experts=8, top_k=2,
                                               capacity_factor=4.0)),
    "rotary_half": replace(TINY, name="rh", rotary_frac=0.5),
    "gqa_g4": replace(TINY, name="g4", n_heads=8, n_kv_heads=2),
}
# bf16 tolerances: about one bf16 ulp of the logits' and K/V's O(1)
# magnitudes.  On the CPU every route reads a gap of exactly 0.
ROUTE_ATOL = 0.02
KV_ATOL = 0.02


@pytest.mark.parametrize("name", list(ROUTE_CFGS))
def test_entry_points_agree(name):
    """Three routes over one sequence agree within bf16 tolerance, on the
    logits at every position and on the K/V rows they write: ``forward``
    teacher-forced; ``paged_chunk_extend`` of the prompt followed by
    ``paged_decode_step`` calls; and single-token ``paged_decode_step``
    calls from position 0."""
    cfg = ROUTE_CFGS[name]
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    S, p, page = 14, 6, 4
    toks = _toks(1, S, cfg.vocab_size, seed=3)
    full, _, kv = tr.forward(params, toks, cfg, collect_cache=True)
    full = np.asarray(full[0, :, :cfg.vocab_size], np.float32)
    want_kv = {k: np.asarray(v[:, 0], np.float32).reshape(cfg.n_layers, S, -1)
               for k, v in kv.items()}
    tables = jnp.asarray([[3, 0, 5, 1]], jnp.int32)     # 16 rows >= S
    step = jax.jit(partial(tr.paged_decode_step, cfg=cfg))

    def decode(pool, start, out):
        for i in range(start, S):
            lg, pool = step(params, pool, toks[:, i],
                            jnp.asarray([i], jnp.int32), tables)
            out.append(np.asarray(lg[0, :cfg.vocab_size], np.float32))
        return np.stack(out), pool

    padded = jnp.zeros(8, jnp.int32).at[:p].set(toks[0, :p])
    chunked, last = jax.jit(partial(tr.paged_chunk_extend, cfg=cfg))(
        params, _pool(cfg, 6, page), tables[0], padded, jnp.int32(0),
        jnp.int32(p))
    routes = {
        "chunk+decode": decode(
            chunked, p, [np.asarray(last[:cfg.vocab_size], np.float32)])
        + (full[p - 1:],),
        "decode": decode(_pool(cfg, 6, page), 0, []) + (full,),
    }
    for route, (got, pool, want) in routes.items():
        gap = np.abs(got - want).max()
        assert gap <= ROUTE_ATOL, (route, gap)
        for k in ("k", "v"):
            rows = _rows(cfg, pool[k], tables[0], S)
            np.testing.assert_allclose(rows, want_kv[k], rtol=0,
                                       atol=KV_ATOL, err_msg=route)


def test_greedy_generate_matches_teacher_forced_forward(params):
    """Batched greedy generation over right-padded prompts of ragged
    lengths: every generated token is the teacher-forced forward's greedy
    choice within the oracle's gap."""
    from _oracle import greedy_gaps
    lengths = np.asarray([3, 8, 5], np.int32)
    prompts = np.asarray(_toks(3, 8, seed=4))
    n_new = 6
    out = np.asarray(jax.jit(lambda t, n: tr.greedy_generate(
        params, t, n, TINY, n_new))(jnp.asarray(prompts),
                                    jnp.asarray(lengths)))
    assert out.shape == (3, n_new)
    for b, n in enumerate(lengths):
        seq = np.concatenate([prompts[b, :n], out[b, :-1]])
        gaps = greedy_gaps(params, TINY, seq, np.arange(n - 1, len(seq)),
                           out[b])
        assert gaps.max() <= 0.02, (b, gaps)
