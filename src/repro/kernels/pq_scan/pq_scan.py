"""Pallas TPU kernel: PQ ADC scan (the retrieval hot loop).

ScaNN/Faiss scan PQ codes with AVX in-register LUT shuffles; there is no TPU
analogue of register shuffles, so the kernel reformulates the per-code table
lookup as a **one-hot matmul** that runs on the MXU:

    dist[n] = sum_s lut[s, code[n, s]]  ==  sum_s onehot(code[:, s]) @ lut[s]

The 256-wide one-hot is MXU-aligned (2 x 128 lanes); codes stream through
VMEM in (block_n, S) tiles with the (S, 256) LUT resident, so each grid step
is one (block_n x 256) x (256,) contraction per sub-quantizer -- compute
bound on the MXU instead of gather-bound on the VPU.

The output is laid out (B, 1, N) with (1, 1, block_n) blocks: a block's
last two dims must be multiples of (8, 128) or span the whole array, and a
(1, block_n) block over a (B, N) output breaks that whenever B > 1 (the
IVF-PQ search always scans ``queries x nprobe`` lists at once).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pq_scan_kernel(lut_ref, codes_ref, out_ref, *, n_subq: int):
    codes = codes_ref[...]                       # (1, block_n, S) int32
    lut = lut_ref[...]                           # (1, S, 256) f32
    block_n = codes.shape[1]
    acc = jnp.zeros((block_n,), jnp.float32)
    for s in range(n_subq):
        onehot = jax.nn.one_hot(codes[0, :, s], 256, dtype=jnp.float32)
        # MXU contraction; full f32 precision so the LUT is not rounded
        acc = acc + jnp.dot(onehot, lut[0, s],
                            precision=jax.lax.Precision.HIGHEST)
    out_ref[...] = acc[None, None, :]


def pq_scan_pallas(lut: jax.Array, codes: jax.Array, block_n: int = 512,
                   interpret: bool = True) -> jax.Array:
    """lut: (B, S, 256) f32; codes: (B, N, S) uint8 -> (B, 1, N) f32.

    N must be a multiple of block_n, and block_n a multiple of 128 or equal
    to N (callers pad; padded rows are sliced off by the wrapper in ops.py).
    """
    b, s, _ = lut.shape
    _, n, _ = codes.shape
    assert n % block_n == 0, (n, block_n)
    grid = (b, n // block_n)
    return pl.pallas_call(
        functools.partial(_pq_scan_kernel, n_subq=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, 256), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_n, s), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        interpret=interpret,
    )(lut, codes.astype(jnp.int32))
