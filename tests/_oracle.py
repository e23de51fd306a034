"""Oracles the tests hold the serving path to.

Teacher-forced ``tr.forward`` is the oracle for greedy token streams.  A
greedy token passes when its logit, in a forward pass over the logical
sequence that produced it, lies within ``GAP`` of the best logit at that
position.  That is robust to bf16 near-ties: a bucketed prefill, a paged
gather or a chunked append may round differently from the monolithic
forward and flip a tie, but never by more than a few bf16 ulps.

:func:`sliced_paged_decode_step` is the oracle for one paged decode step:
the route that hands the attention one layer's slice of the pool, cast to
the compute dtype, with the plain block tables.

Usage in test modules:  ``from _oracle import assert_follows_forward``
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as cm
from repro.models import transformer as tr

GAP = 0.02


def greedy_gaps(params, cfg, seq, at, chosen) -> np.ndarray:
    """Forward over ``seq`` (teacher-forced); for each i, how far the
    logit of ``chosen[i]`` at position ``at[i]`` sits below that
    position's best logit."""
    logits, _ = tr.forward(params, jnp.asarray(seq, jnp.int32)[None], cfg)
    lg = np.asarray(logits[0, :, :cfg.vocab_size], np.float32)[np.asarray(at)]
    return lg.max(-1) - lg[np.arange(len(at)), np.asarray(chosen)]


def spy_appends(engine) -> list:
    """Record every ``engine._append_tokens`` call as (rid, the slot's
    length before the append, the appended tokens)."""
    log = []
    real = engine._append_tokens

    def spy(slot, tokens):
        log.append((engine.active[slot].rid, int(engine.pool.lengths[slot]),
                    np.asarray(tokens, np.int32).copy()))
        real(slot, tokens)

    engine._append_tokens = spy
    return log


def logical_sequence(req, appends=()):
    """A served request's logical token sequence -- its prompt, then its
    output tokens fed back one decode step at a time, with each appended
    chunk at the length it was appended at -- and, per output token, the
    position whose logits chose it."""
    seq, at = list(req.prompt), [len(req.prompt) - 1]
    pending = sorted(((start, toks) for rid, start, toks in appends
                      if rid == req.rid), key=lambda a: a[0])
    for tok in req.output[:-1]:
        while pending and pending[0][0] == len(seq):
            seq.extend(pending.pop(0)[1])
        seq.append(tok)
        at.append(len(seq) - 1)
    assert not pending, f"request {req.rid}: appends past its last step"
    return seq, at


def assert_follows_forward(gen, reqs, appends=()) -> None:
    """Every output token of every request is the oracle's greedy choice
    within ``GAP`` (``gen``: the generative Component)."""
    for r in reqs:
        seq, at = logical_sequence(r, appends)
        gaps = greedy_gaps(gen.params, gen.cfg, seq, at, r.output)
        assert gaps.max() <= GAP, (r.rid, gaps)


def sliced_paged_decode_step(params, cache, token, pos, block_tables, cfg,
                             compute_dtype=jnp.bfloat16, attn_impl=None,
                             write_mask=None):
    """``tr.paged_decode_step`` by the per-layer-slice route: after the
    layer's scatter, attention gets ``pool[l]`` cast to ``compute_dtype``
    -- a (P, page, H_kv*D) pool -- and the tables as they are.  Same
    arguments and results as the step under test."""
    B = token.shape[0]
    L, P, page, row = cache["k"].shape
    M = block_tables.shape[1]
    x = jnp.take(cm.maybe_dequant(params["embed"], compute_dtype), token,
                 axis=0)[:, None, :]
    page_log = pos // page
    phys = jnp.take_along_axis(
        block_tables, jnp.minimum(page_log, M - 1)[:, None], axis=1)[:, 0]
    flat = jnp.where(page_log < M, phys * page + pos % page, P * page)
    if write_mask is not None:
        flat = jnp.where(write_mask, flat, P * page)
    page_idx, page_row = flat // page, flat % page
    attn = attn_impl
    if attn is None:
        def attn(q, kp, vp, tables, cache_len):
            kg = kp[tables].reshape(B, M * page, cfg.n_kv_heads, cfg.d_head)
            vg = vp[tables].reshape(B, M * page, cfg.n_kv_heads, cfg.d_head)
            return cm.decode_attention_ref(
                q, cm.repeat_kv(kg, cfg.q_per_kv),
                cm.repeat_kv(vg, cfg.q_per_kv), cache_len)

    def layer_fn(carry, scanned):
        x, k_pool, v_pool = carry
        lp, l = scanned

        def attend(q, k, v):
            kpool = k_pool.at[l, page_idx, page_row].set(
                k.reshape(B, row).astype(k_pool.dtype), mode="drop")
            vpool = v_pool.at[l, page_idx, page_row].set(
                v.reshape(B, row).astype(v_pool.dtype), mode="drop")
            kp = jax.lax.dynamic_index_in_dim(
                kpool, l, 0, keepdims=False).astype(compute_dtype)
            vp = jax.lax.dynamic_index_in_dim(
                vpool, l, 0, keepdims=False).astype(compute_dtype)
            return attn(q, kp, vp, block_tables, pos + 1), (kpool, vpool)

        x, _, (k_pool, v_pool) = tr._layer(x, lp, cfg, pos, attend,
                                           compute_dtype)
        return (x, k_pool, v_pool), None

    (x, k_pool, v_pool), _ = jax.lax.scan(
        layer_fn, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    x = cm.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = cm.maybe_dequant(params["head"], compute_dtype)
    logits = (x.astype(compute_dtype) @ head)[:, 0]
    return logits, {"k": k_pool, "v": v_pool}
