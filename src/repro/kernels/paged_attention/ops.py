"""Jitted wrapper: GQA head grouping + dtype plumbing for the paged kernel.

The public contract matches ``transformer.paged_decode_step``'s
block-table-native ``attn_impl`` signature: q for one decode token,
the POST-SCATTER page pool in page-row layout, the dense block tables and
the per-sequence cache lengths.  From the decode step, the pool is the
flat (L*P, page, H_kv*D) view of every layer's pages and the tables are
offset to the current layer (``block_tables + l*P``); any
(P, page, H_kv*D) pool with tables into it works the same.  No
logical-view gather, no per-layer slice and no cast of the pool happens
anywhere on this path -- the kernel DMAs the live pages from HBM through
the block table and casts each fetched tile to the query's dtype.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention_pallas)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("num_buffers", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, num_buffers: int = 2,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B, 1, H, D) or (B, H, D); pages: (P, page, H_kv*D);
    block_tables: (B, M); lengths: (B,) -> same rank as q.

    H query heads are grouped as (H_kv, q_per_kv): query head h*G + g
    reads KV head h, and each fetched block of KV pages serves every query
    head in one product -- KV is never repeated.
    """
    if interpret is None:
        interpret = _interpret_default()
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    b, h, d = q.shape
    h_kv = k_pages.shape[2] // d
    qg = q.reshape(b, h_kv, h // h_kv, d)
    out = paged_decode_attention_pallas(
        qg, k_pages, v_pages, block_tables.astype(jnp.int32),
        lengths.astype(jnp.int32), num_buffers=num_buffers,
        interpret=interpret)
    out = out.reshape(b, h, d)
    return out[:, None] if squeeze else out
