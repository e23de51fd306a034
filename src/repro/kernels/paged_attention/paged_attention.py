"""Pallas TPU kernel: ragged paged-decode attention over a page-table pool.

One query token per sequence attends to its KV cache *in place* in the
paged pool -- no (B, M*page, H, D) logical gather ever materializes.  The
pool stores each page as one page-row block (P, page, H_kv*D): every
position's KV heads sit side by side along the lane axis, so a page is a
single contiguous (page, H_kv*D) tile.  The grid is one cell per sequence;
block tables and per-sequence lengths ride in scalar prefetch (SMEM) so
each cell drives its own DMA schedule:

  * ragged: cell b runs ``ceil(lengths[b] / page)`` loop iterations and
    never touches pages past the sequence's length (early exit, not
    masking) -- idle or short slots cost only their own pages' bandwidth;
  * overlapped: the kernel manually double-buffers (``num_buffers=2``; a
    quad-buffer variant behind the flag) whole-page copies HBM->VMEM with
    ``make_async_copy``, starting the DMA for page t+num_buffers-1 before
    computing page t, so page fetch latency hides behind the flash-style
    online-softmax update;
  * grouped: each KV head's (page, D) lane slice is cut from the fetched
    page in VMEM and serves all ``q_per_kv`` query heads of that group --
    GQA without repeating KV in HBM or VMEM.

Why whole pages: a per-head copy would slice one row out of the H_kv axis
(or a D-wide lane window out of a head-major page), which Mosaic refuses
unless the slice is aligned to the (8, 128) tiling.  A whole-page copy is
always aligned, and it moves exactly the bytes the ragged walk needs.

The pool is passed as ``memory_space=ANY`` (stays in HBM); only the
(num_buffers, page, H_kv*D) staging buffers live in VMEM.  CPU CI runs the
same kernel in interpret mode (``ops.paged_decode_attention`` defaults
interpret on non-TPU backends) where the DMA schedule degenerates to
ordered copies, so parity tests are bit-gated against
:func:`repro.kernels.paged_attention.ref.paged_decode_attention_ref`, a
page-loop mirror with identical arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(tables_ref, len_ref,           # scalar prefetch
                         q_ref, k_hbm, v_hbm,           # inputs
                         o_ref,                         # output
                         kbuf, vbuf, sem,               # scratch
                         *, page: int, num_buffers: int, sm_scale: float,
                         max_pages: int):
    b = pl.program_id(0)
    # Positions past the block table were dropped at write time (the
    # scatter's OOB row); clamp so the loop never chases them either.
    length = jnp.minimum(len_ref[b], max_pages * page)
    n_pages = (length + page - 1) // page

    def page_dma(j, slot):
        """Async copies of logical page j's whole K and V page rows."""
        phys = tables_ref[b, j]
        return (
            pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[slot],
                                  sem.at[slot, 0]),
            pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[slot],
                                  sem.at[slot, 1]),
        )

    # Warm-up: put the first num_buffers-1 pages in flight.
    for t in range(num_buffers - 1):
        @pl.when(t < n_pages)
        def _start():                                   # noqa: B023
            kd, vd = page_dma(t, t)
            kd.start()
            vd.start()

    h_kv, g, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    qs = [q_ref[0, h].astype(jnp.float32) * sm_scale     # (G, D) per head
          for h in range(h_kv)]

    def body(j, carry):
        slot = jax.lax.rem(j, num_buffers)
        nxt = j + num_buffers - 1
        # Start fetching page j+num_buffers-1 before computing page j.
        @pl.when(nxt < n_pages)
        def _prefetch():
            kd, vd = page_dma(nxt, jax.lax.rem(nxt, num_buffers))
            kd.start()
            vd.start()
        kd, vd = page_dma(j, slot)
        kd.wait()
        vd.wait()
        kpage = kbuf[slot].astype(jnp.float32)          # (page, H_kv*D)
        vpage = vbuf[slot].astype(jnp.float32)
        out = []
        for h in range(h_kv):
            m, l, acc = carry[h]
            k = kpage[:, h * d:(h + 1) * d]             # (page, D)
            v = vpage[:, h * d:(h + 1) * d]
            s = jax.lax.dot_general(qs[h], k, (((1,), (1,)), ((), ())))
            pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)     # (G, page)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            out.append((m_new, l, acc))
        return tuple(out)

    init = tuple((jnp.full((g, 1), NEG_INF, jnp.float32),
                  jnp.zeros((g, 1), jnp.float32),
                  jnp.zeros((g, d), jnp.float32)) for _ in range(h_kv))
    final = jax.lax.fori_loop(0, n_pages, body, init)
    # length == 0 never enters the loop: l stays 0 and the guard below
    # turns the output into exact zeros, matching the ref.
    for h in range(h_kv):
        _, l, acc = final[h]
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
                         "(need one page in flight while computing another)")
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page,
                          num_buffers=num_buffers,
                          sm_scale=1.0 / math.sqrt(d), max_pages=max_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h_kv, g, d), lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),      # K pool stays in HBM
                pl.BlockSpec(memory_space=pl.ANY),      # V pool stays in HBM
            ],
            out_specs=pl.BlockSpec((1, h_kv, g, d),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((num_buffers, page, row), k_pages.dtype),
                pltpu.VMEM((num_buffers, page, row), v_pages.dtype),
                pltpu.SemaphoreType.DMA((num_buffers, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h_kv, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)
