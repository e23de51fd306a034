"""Jitted public wrapper for the PQ ADC scan kernel (pads N, routes to the
Pallas kernel on TPU / interpret mode on CPU)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.pq_scan.pq_scan import pq_scan_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("block_n",))
def pq_scan(lut: jax.Array, codes: jax.Array, block_n: int = 512) -> jax.Array:
    """lut: (B, S, 256); codes: (B, N, S) uint8 -> distances (B, N) f32."""
    b, n, s = codes.shape
    # one block spanning the (8-padded) list, or block_n-wide tiles: the
    # output block's lane dim must be a multiple of 128 or the whole row
    n8 = -(-n // 8) * 8
    bn = n8 if n8 <= block_n else block_n
    pad = (-n) % bn
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)))
    out = pq_scan_pallas(lut.astype(jnp.float32), codes, block_n=bn,
                         interpret=_interpret_default())
    return out[:, 0, :n]
