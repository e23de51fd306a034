"""Pallas TPU kernel: ragged paged-decode attention over a page-table pool.

One query token per sequence attends to its KV cache *in place* in the
paged pool -- no (B, M*page, H, D) logical gather ever materializes.  The
pool stores each page as one page-row block (P, page, H_kv*D): every
position's KV heads sit side by side along the lane axis, so a page is a
single contiguous (page, H_kv*D) tile.  The grid is one cell per sequence;
block tables and per-sequence lengths ride in scalar prefetch (SMEM) so
each cell drives its own DMA schedule.

Block walk.  A cell walks its pages a *block* at a time.  A block is
``pages_per_block(page, M)`` consecutive logical pages: the fewest that
cover ``BLOCK_POSITIONS`` (128) positions, capped at the table width M --
8 pages of 16 positions at the serving page size, so one block's K or V is
a (128, H_kv*D) tile.  The block size follows from the page size and M
alone; there is no knob.

  * ragged: cell b runs ``ceil(n_pages / pages_per_block)`` iterations,
    ``n_pages = ceil(lengths[b] / page)``, and never DMAs a page past the
    sequence's length (early exit, not masking);
  * overlapped: each page of a block is DMA'd HBM->VMEM into its own row
    range of a (num_buffers, pages_per_block*page, H_kv*D) staging buffer
    with ``make_async_copy``; block t+num_buffers-1 is started before block
    t is computed (``num_buffers=2`` double-buffers, 4 quad-buffers);
  * padding: the slots of the last block past ``n_pages`` (pages past the
    live range or past the table) are never fetched: their rows are
    written with zeros instead.  A stale or uninitialised row times a
    probability of 0 would still be NaN if it held one; a zero row scores
    0 (then masked by position) and adds exact zeros to p.V.

Head layout.  All KV heads go through the MXU in one product per block,
with no per-head lane slices inside the loop.  Once per cell the query is
laid out as a block-diagonal (H_kv*G, H_kv*D) tile: row h*G+g holds
q[h, g] in lanes h*D:(h+1)*D and zeros elsewhere.  Then

  * scores: Q_bd . K^T over the whole row gives every head's scores as one
    (H_kv*G, block) tile; the zero lanes add exact zeros;
  * p . V against the whole (block, H_kv*D) V tile accumulates a
    (H_kv*G, H_kv*D) tile, of which row h*G+g needs only lanes
    h*D:(h+1)*D -- cut out once per cell, after the loop.

That is H_kv times the MXU work of per-head products, traded for one
lane-dense product per block in place of 2*H_kv tiny ones per page.

Precision.  q and K reach the MXU in their own dtype (bf16 when serving)
with f32 accumulation -- a product of two bf16 values is exact in f32 --
and ``sm_scale`` is applied to the f32 scores.  Scores, the running max
and sum, the probabilities p and the accumulator are f32; V is cast to
f32 for p . V.  An f32 pool runs the same code in f32.

The pool is passed as ``memory_space=ANY`` (stays in HBM); only the
staging buffers live in VMEM.  CPU CI runs the same kernel in interpret
mode (``ops.paged_decode_attention`` defaults interpret on non-TPU
backends) where the DMA schedule degenerates to ordered copies, so parity
tests are bit-gated against
:func:`repro.kernels.paged_attention.ref.paged_decode_attention_ref`, a
block-walk mirror with identical arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_POSITIONS = 128        # positions a block covers: one MXU tile wide


def pages_per_block(page: int, max_pages: int) -> int:
    """Pages per block: the fewest covering ``BLOCK_POSITIONS`` positions,
    capped at the block table's width."""
    return min(max_pages, -(-BLOCK_POSITIONS // page))


def block_diagonal_query(q: jax.Array, h_kv: int) -> jax.Array:
    """(H_kv*G, D) query rows -> (H_kv*G, H_kv*D): row h*G+g keeps its
    values in lanes h*D:(h+1)*D, the lanes of its KV head in a page row,
    and zeros elsewhere."""
    h, d = q.shape
    g = h // h_kv
    rep = jnp.concatenate([q] * h_kv, axis=1)
    r = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1)
    keep = (r < g) & (c < d)
    for hi in range(1, h_kv):
        keep |= ((r >= hi * g) & (r < (hi + 1) * g)
                 & (c >= hi * d) & (c < (hi + 1) * d))
    return jnp.where(keep, rep, jnp.zeros_like(rep))


def head_lanes(acc: jax.Array, h_kv: int) -> jax.Array:
    """(H_kv*G, H_kv*D) -> (H_kv*G, D): row h*G+g's lanes h*D:(h+1)*D, the
    diagonal blocks of :func:`block_diagonal_query`'s layout."""
    h, row = acc.shape
    g, d = h // h_kv, row // h_kv
    r = jax.lax.broadcasted_iota(jnp.int32, (h, d), 0)
    out = acc[:, :d]
    for hi in range(1, h_kv):
        out = jnp.where(r >= hi * g, acc[:, hi * d:(hi + 1) * d], out)
    return out


def _paged_decode_kernel(tables_ref, len_ref,           # scalar prefetch
                         q_ref, k_hbm, v_hbm,           # inputs
                         o_ref,                         # output
                         kbuf, vbuf, sem,               # scratch
                         *, page: int, ppb: int, num_buffers: int,
                         sm_scale: float, max_pages: int):
    b = pl.program_id(0)
    h_kv = kbuf.shape[2] // q_ref.shape[2]
    bk = ppb * page
    # Positions past the block table were dropped at write time (the
    # scatter's OOB row); clamp so the loop never chases them either.
    length = jnp.minimum(len_ref[b], max_pages * page)
    n_pages = (length + page - 1) // page
    n_blocks = (n_pages + ppb - 1) // ppb

    def page_dma(j, slot, i):
        """Async copies of logical page j's K and V page rows into row
        range i of staging slot ``slot``."""
        phys = tables_ref[b, j]
        rows = pl.ds(i * page, page)
        return (
            pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[slot, rows],
                                  sem.at[slot, 0]),
            pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[slot, rows],
                                  sem.at[slot, 1]),
        )

    def start_block(t, slot):
        for i in range(ppb):
            j = t * ppb + i

            @pl.when(j < n_pages)
            def _fetch():                               # noqa: B023
                for dma in page_dma(j, slot, i):
                    dma.start()

            @pl.when(j >= n_pages)
            def _pad():                                 # noqa: B023
                rows = pl.ds(i * page, page)
                kbuf[slot, rows, :] = jnp.zeros((page, kbuf.shape[2]),
                                                kbuf.dtype)
                vbuf[slot, rows, :] = jnp.zeros((page, vbuf.shape[2]),
                                                vbuf.dtype)

    def wait_block(t, slot):
        for i in range(ppb):
            j = t * ppb + i

            @pl.when(j < n_pages)
            def _wait():                                # noqa: B023
                for dma in page_dma(j, slot, i):
                    dma.wait()

    # Warm-up: put the first num_buffers-1 blocks in flight.
    for t in range(num_buffers - 1):
        @pl.when(t < n_blocks)
        def _start():                                   # noqa: B023
            start_block(t, t)

    cdt = jnp.promote_types(q_ref.dtype, kbuf.dtype)
    q_bd = block_diagonal_query(q_ref[0].astype(cdt), h_kv)  # (H, row)
    h = q_bd.shape[0]

    def body(t, carry):
        m, l, acc = carry
        slot = jax.lax.rem(t, num_buffers)
        nxt = t + num_buffers - 1

        # Start fetching block t+num_buffers-1 before computing block t.
        @pl.when(nxt < n_blocks)
        def _prefetch():
            start_block(nxt, jax.lax.rem(nxt, num_buffers))

        wait_block(t, slot)
        k = kbuf[slot].astype(cdt)                      # (block, row)
        v = vbuf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(q_bd, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                # (H, block)
        pos = t * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, kbuf.shape[2]), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    # length == 0 never enters the loop: l stays 0 and the guard below
    # turns the output into exact zeros, matching the ref.
    o_ref[0] = (head_lanes(acc, h_kv)
                / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array,
                                  num_buffers: int = 2,
                                  interpret: bool = True) -> jax.Array:
    """q: (B, H_kv, G, D); pages: (P, page, H_kv*D) page rows;
    block_tables: (B, M) int32 physical page ids; lengths: (B,) int32
    -> (B, H_kv, G, D)."""
    b, h_kv, g, d = q.shape
    _, page, row = k_pages.shape
    if row != h_kv * d:
        raise ValueError(f"page row width {row} != H_kv*D = {h_kv}*{d}")
    max_pages = block_tables.shape[1]
    if num_buffers < 2:
        raise ValueError(f"num_buffers={num_buffers} must be >= 2 "
                         "(need one block in flight while computing another)")
    ppb = pages_per_block(page, max_pages)
    h = h_kv * g
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page, ppb=ppb,
                          num_buffers=num_buffers,
                          sm_scale=1.0 / math.sqrt(d), max_pages=max_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # K pool stays in HBM
                pl.BlockSpec(memory_space=pl.ANY),      # V pool stays in HBM
            ],
            out_specs=pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((num_buffers, ppb * page, row), k_pages.dtype),
                pltpu.VMEM((num_buffers, ppb * page, row), v_pages.dtype),
                pltpu.SemaphoreType.DMA((num_buffers, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q.reshape(b, h, d), k_pages, v_pages)
    return out.reshape(b, h_kv, g, d)
