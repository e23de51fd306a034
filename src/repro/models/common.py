"""Shared pure-JAX model building blocks.

All models in the zoo are expressed as (init_fn, apply_fn) pairs over plain
pytrees of jnp arrays -- no framework dependency.  Every init_fn is safe to
call under ``jax.eval_shape`` so the dry-run can build abstract parameter
trees without allocating memory.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

Params = Any  # pytree of jnp arrays


def named(name: str, fn):
    """``fn`` (a function or a ``partial``) renamed to ``name``: jitted, it
    compiles to the XLA module ``jit_<name>``, which is how the device
    trace's ``XLA Modules`` line names each run of it."""
    fn.__name__ = name
    return fn


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    """Truncated-normal fan-in init (LLaMA-style 1/sqrt(d_in))."""
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -3, 3, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight + bias).astype(dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float, rotary_frac: float = 1.0) -> jax.Array:
    """Inverse frequencies for the rotated sub-dimension."""
    d_rot = int(d_head * rotary_frac)
    d_rot -= d_rot % 2
    return 1.0 / (theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rotary_frac: float = 1.0) -> jax.Array:
    """x: (..., S, H, D). positions: broadcastable to (..., S).

    ``rotary_frac < 1`` rotates only the leading fraction of head dims
    (ChatGLM-style 2D/partial RoPE).
    """
    d_head = x.shape[-1]
    inv_freq = rope_freqs(d_head, theta, rotary_frac)
    d_rot = inv_freq.shape[0] * 2
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (..., S, d_rot/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, d_rot/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x_rot, x_pass = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# Attention (pure-jnp reference paths; Pallas kernels live in repro.kernels)
# ---------------------------------------------------------------------------

def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, H_kv, D) -> (B, S, H_kv * n_rep, D) for GQA."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def naive_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           window: int | None = None) -> jax.Array:
    """Materialized-scores causal attention.  q,k,v: (B, S, H, D)."""
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             block_kv: int = 1024,
                             window: int | None = None) -> jax.Array:
    """Online-softmax attention scanned over KV blocks (flash-style in XLA).

    Never materializes the (S, S) score matrix; peak temp is
    (B, H, S, block_kv).  q,k,v: (B, S, H, D) with equal q/kv length.
    """
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    n_blocks = -(-s // block_kv)
    pad = n_blocks * block_kv - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, n_blocks, block_kv, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, block_kv, h, d).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(s)

    def body(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, blk_idx = blk
        kpos = blk_idx * block_kv + jnp.arange(block_kv)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        mask = mask & (kpos[None, :] < s)
        scores = jnp.where(mask[None, None], scores, -1e30)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), v_blk).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, s), dtype=jnp.float32)
    acc0 = jnp.zeros((b, h, s, d), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, S, H, D)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array) -> jax.Array:
    """Single-token decode attention.  q: (B, 1, H, D); caches: (B, S, H, D).

    ``cache_len`` masks out unwritten cache slots (scalar or (B,)).
    """
    b, s, h, d = k_cache.shape
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache).astype(jnp.float32) * scale
    valid = jnp.arange(s)[None, :] < jnp.reshape(cache_len, (-1, 1))
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)


# ---------------------------------------------------------------------------
# Int8 serving quantization (paper assumes 8-bit quantized model weights, §4)
# ---------------------------------------------------------------------------

def quantize_int8(w: jax.Array, axis: int = -1) -> dict:
    """Symmetric per-channel int8 quantization."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = (amax / 127.0 + 1e-12).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def dequantize_int8(wq: dict, dtype=jnp.bfloat16) -> jax.Array:
    return (wq["q"].astype(jnp.float32) * wq["scale"]).astype(dtype)


def maybe_dequant(w, dtype=jnp.bfloat16):
    if isinstance(w, dict) and "q" in w:
        return dequantize_int8(w, dtype)
    return w.astype(dtype)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def count_params(params: Params) -> int:
    leaves = jax.tree_util.tree_leaves(params)
    return sum(int(x.size) for x in leaves if hasattr(x, "size"))


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross entropy.  logits: (..., V); labels: int (...)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
