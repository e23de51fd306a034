"""Oracles for the ragged paged-decode attention kernel.

Two references with different jobs:

* :func:`paged_decode_attention_ref` -- a block-walk mirror of the
  kernel: identical arithmetic (same blocks of pages, same block-diagonal
  query, same dot_general shapes, same online-softmax update order, same
  f32 accumulators) driven from the block table.  Interpret-mode kernel
  runs are gated BIT-EXACTLY against it.
* :func:`paged_decode_attention_dense_ref` -- the semantic oracle: gather
  the logical (B, M*page, H, D) view (exactly what the pre-kernel engine
  attended over) and run plain masked-softmax attention.  Online softmax
  reorders the reduction, so kernel-vs-dense comparisons are allclose,
  not bitwise.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import (
    block_diagonal_query, head_lanes, pages_per_block)
from repro.models import common as cm

NEG_INF = -1e30


def paged_gather(pages: jax.Array, block_tables: jax.Array,
                 d: int) -> jax.Array:
    """(P, page, H_kv*D) page rows + (B, M) -> logical view
    (B, M*page, H_kv, D)."""
    _, page, row = pages.shape
    b, m = block_tables.shape
    return pages[block_tables].reshape(b, m * page, row // d, d)


@jax.jit
def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """Block-walk mirror of the kernel.  q: (B, H_kv, G, D); pages:
    (P, page, H_kv*D) -> (B, H_kv, G, D).

    Walks every sequence's pages in the kernel's blocks of
    ``pages_per_block(page, M)`` with the exact kernel update (the same
    block-diagonal query, dot shapes and f32 carries).  Page slots past
    ``ceil(len/page)`` -- past the live range or past the table -- read
    zeros, as the kernel's padding does, never the pool.  Blocks past the
    last live one are processed with fully masked scores instead of the
    kernel's ragged early exit; once the running max is finite that is an
    exact no-op (``exp(NEG_INF - m)`` underflows to 0.0 and the correction
    factor is exactly 1.0), and zero-length rows -- where the all-masked
    update WOULD diverge -- are zeroed at the end just like the kernel's
    l == 0 guard.  Jitted so its arithmetic compiles the same way the
    interpret-mode kernel body does; parity tests gate bit-exactly
    against it.
    """
    b, h_kv, g, d = q.shape
    _, page, row = k_pages.shape
    m_pages = block_tables.shape[1]
    ppb = pages_per_block(page, m_pages)
    bk = ppb * page
    sm_scale = 1.0 / math.sqrt(d)
    cdt = jnp.promote_types(q.dtype, k_pages.dtype)
    length = jnp.minimum(lengths.astype(jnp.int32), m_pages * page)
    n_pages = (length + page - 1) // page
    cells = []
    for bi in range(b):
        q_bd = block_diagonal_query(
            q[bi].reshape(h_kv * g, d).astype(cdt), h_kv)
        m_run = jnp.full((h_kv * g, 1), NEG_INF, jnp.float32)
        l_run = jnp.zeros((h_kv * g, 1), jnp.float32)
        acc = jnp.zeros((h_kv * g, row), jnp.float32)
        for t in range(-(-m_pages // ppb)):
            j = t * ppb + jnp.arange(ppb)
            live = (j < n_pages[bi])[:, None, None]
            phys = block_tables[bi, jnp.minimum(j, m_pages - 1)]
            k = jnp.where(live, k_pages[phys], 0).reshape(bk, row)
            v = jnp.where(live, v_pages[phys], 0).reshape(bk, row)
            k = k.astype(cdt)
            v = v.astype(jnp.float32)
            s = jax.lax.dot_general(q_bd, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale
            pos = t * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length[bi], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_run - m_new)
            l_run = l_run * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_run = m_new
        cells.append((head_lanes(acc, h_kv) / jnp.maximum(l_run, 1e-30))
                     .astype(q.dtype).reshape(h_kv, g, d))
    out = jnp.stack(cells)
    return jnp.where(jnp.reshape(length, (-1, 1, 1, 1)) > 0, out,
                     jnp.zeros_like(out))


def paged_decode_attention_dense_ref(q: jax.Array, k_pages: jax.Array,
                                     v_pages: jax.Array,
                                     block_tables: jax.Array,
                                     lengths: jax.Array) -> jax.Array:
    """Semantic oracle: gather the logical view, run f32 masked softmax.

    q: (B, H_kv, G, D); pages: (P, page, H_kv*D) -> (B, H_kv, G, D).
    This is the math the engine's
    ``"ref"`` attention path computes (modulo GQA head repeat, which is
    exact), so kernel-vs-engine drift shows up here first.
    """
    b, h_kv, g, d = q.shape
    kg = paged_gather(k_pages, block_tables, d).astype(jnp.float32)
    vg = paged_gather(v_pages, block_tables, d).astype(jnp.float32)
    qf = q.astype(jnp.float32) / math.sqrt(d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, kg)
    valid = jnp.arange(kg.shape[1])[None, :] < \
        jnp.reshape(lengths, (-1, 1))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, vg)
    out = jnp.where(jnp.reshape(lengths, (-1, 1, 1, 1)) > 0, out, 0.0)
    return out.astype(q.dtype)


def engine_ref_attn(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, cache_len: jax.Array,
                    q_per_kv: int) -> jax.Array:
    """The engine's pre-kernel decode attention, block-table-native form:
    gather the logical view, repeat KV heads, masked softmax in the
    caller's compute dtype (``cm.decode_attention_ref``).  Bit-identical
    to what ``paged_decode_step`` computed before the attn_impl contract
    existed -- the default/"ref" path in the engine closes over this.

    q: (B, 1, H, D); pages: (P, page, H_kv*D) -> (B, 1, H, D).
    """
    d = q.shape[-1]
    kg = paged_gather(k_pages, block_tables, d)
    vg = paged_gather(v_pages, block_tables, d)
    kr = cm.repeat_kv(kg, q_per_kv)
    vr = cm.repeat_kv(vg, q_per_kv)
    return cm.decode_attention_ref(q, kr, vr, cache_len)
