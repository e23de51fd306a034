"""Weights, retrieval index and passage table of one run, made from the seed.

Everything here is data that the benchmark hands to the system under test:
bf16 weights in the layout the engine's transformer consumes, the IVF-PQ
index of one chip's shard, and the passage table on the host.  Each is
made on the device in one jitted call, so set-up never materialises a
float32 copy of a model on the host.  The reference reads the same data
again (the same arrays, which the program never writes), never anything
that the program computed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Vocabulary rows are padded to this multiple, as the engine's transformer
# pads its embedding and head; ids past ``vocab_size`` are never looked up
# and their logits are never read.
VOCAB_PAD = 512


def key_of(seed: int, stream: int) -> jax.Array:
    """A raw PRNG key from any non-negative seed (wider than 32 bits too)
    and a stream number, so each kind of data draws independently."""
    state = np.random.SeedSequence([seed, stream]).generate_state(
        2, dtype=np.uint32)
    return jnp.asarray(state, jnp.uint32)


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _stacked(key, n_layers, shape, scale, dtype):
    """(L,) + shape, generated a layer at a time so the float32 draw never
    holds more than one layer."""
    return jax.lax.map(lambda k: _normal(k, shape, scale, dtype),
                       jax.random.split(key, n_layers))


@partial(jax.jit, static_argnames=("arch", "dtype", "with_head"))
def make_params(key, arch: tuple, dtype=jnp.bfloat16, with_head=True):
    """Random decoder (or encoder) weights.  ``arch`` is
    ``(n_layers, d_model, n_heads, n_kv_heads, d_head, d_ff, vocab)``.
    Matrices are fan-in normal, norms are ones, the embedding is
    N(0, 0.02^2)."""
    n_layers, d, h, kv, dh, f, vocab = arch
    ks = jax.random.split(key, 9)
    ones = jnp.ones((n_layers, d), dtype)
    layers = {
        "ln1": ones, "ln2": ones,
        "wq": _stacked(ks[0], n_layers, (d, h * dh), d ** -0.5, dtype),
        "wk": _stacked(ks[1], n_layers, (d, kv * dh), d ** -0.5, dtype),
        "wv": _stacked(ks[2], n_layers, (d, kv * dh), d ** -0.5, dtype),
        "wo": _stacked(ks[3], n_layers, (h * dh, d), (h * dh) ** -0.5,
                       dtype),
        "w_gate": _stacked(ks[4], n_layers, (d, f), d ** -0.5, dtype),
        "w_up": _stacked(ks[5], n_layers, (d, f), d ** -0.5, dtype),
        "w_down": _stacked(ks[6], n_layers, (f, d), f ** -0.5, dtype),
    }
    vp = padded_vocab(vocab)
    params = {"embed": _normal(ks[7], (vp, d), 0.02, dtype),
              "ln_f": jnp.ones((d,), dtype), "layers": layers}
    if with_head:
        params["head"] = _normal(ks[8], (d, vp), d ** -0.5, dtype)
    return params


@partial(jax.jit, static_argnames=("n_lists", "list_len", "dim", "n_subq",
                                   "centroid_norm", "residual_norm"))
def make_index_arrays(key, n_lists, list_len, dim, n_subq, centroid_norm,
                      residual_norm):
    """IVF-PQ shard with balanced lists and no training.  Centroids are
    random directions of norm ``centroid_norm``; every codeword of every
    sub-quantizer has norm ``residual_norm / sqrt(n_subq)``, so every
    stored vector's residual has norm ``residual_norm`` exactly and, with
    ``centroid_norm**2 + residual_norm**2 == 1``, the vector has norm 1 up
    to its centroid-residual cross term, as unit-norm embeddings do.
    Codes are random.  Vector ``i`` sits at row ``i % list_len`` of list
    ``i // list_len``, and its id is the passage it encodes."""
    kc, kb, kcode = jax.random.split(key, 3)

    def directions(k, shape):
        v = jax.random.normal(k, shape, jnp.float32)
        return v / jnp.linalg.norm(v, axis=-1, keepdims=True)

    centroids = directions(kc, (n_lists, dim)) * centroid_norm
    codebooks = directions(kb, (n_subq, 256, dim // n_subq)) \
        * (residual_norm * n_subq ** -0.5)
    codes = jax.random.bits(kcode, (n_lists, list_len, n_subq), jnp.uint8)
    ids = jnp.arange(n_lists * list_len, dtype=jnp.int32).reshape(
        n_lists, list_len)
    return {"centroids": centroids, "codebooks": codebooks,
            "list_ids": ids, "list_codes": codes}


@partial(jax.jit, static_argnames=("n", "length", "vocab"))
def _passages(key, n, length, vocab):
    return jax.random.bits(key, (n, length), jnp.uint16) % jnp.uint16(vocab)


def make_passages(key, n: int, length: int, vocab: int) -> np.ndarray:
    """(n, length) host passage table of token ids, drawn on the device
    (uint16: every vocabulary here is under 2**16)."""
    assert vocab <= 2 ** 16, vocab
    dev = _passages(key, n, length, vocab)
    host = np.asarray(dev)
    dev.delete()
    return host


def matmul_params(arch: tuple, with_head: bool = True) -> int:
    """Parameters that take part in a matmul per token (embedding lookup
    excluded, head included)."""
    n_layers, d, h, kv, dh, f, vocab = arch
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return n_layers * per_layer + (d * vocab if with_head else 0)


def arch_of(model: dict) -> tuple:
    """The seven sizes ``make_params`` takes, from a configuration dict."""
    return (model["num_hidden_layers"], model["hidden_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["intermediate_size"],
            model["vocab_size"])


def rotary_dims(head_dim: int, frac: float) -> int:
    d = int(head_dim * frac)
    return d - d % 2
