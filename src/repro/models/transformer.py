"""Decoder-only transformer (dense + MoE) with GQA, RoPE and SwiGLU.

Layers are stacked along a leading L axis and executed with ``jax.lax.scan``
so 28-48-layer models compile quickly and produce compact HLO.  Three entry
points per config:

  * ``forward``        -- full-sequence logits (training / encoder use)
  * ``prefill``        -- logits + populated KV cache (serving prefix stage)
  * ``decode_step``    -- one-token autoregressive step against a KV cache

MoE uses sort-free capacity dispatch (scatter into an (E, C) buffer per batch
row) so dispatch memory is O(tokens * top_k * capacity_factor * d_model), not
O(tokens * E * C); expert weights shard over the ``model`` mesh axis (EP).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed import hints
from repro.models import common as cm


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    moe: MoEConfig | None = None
    rope_theta: float = 10000.0
    rotary_frac: float = 1.0          # ChatGLM partial rotary: 0.5
    causal: bool = True               # False => bidirectional encoder
    attention: str = "full"           # "full" | "sliding_window"
    window: int = 4096
    ffn_type: str = "swiglu"          # "swiglu" | "relu2" (Nemotron/Minitron)
    attn_block_kv: int = 1024         # chunked-attention KV block
    chunked_attn_threshold: int = 2048  # use online-softmax path above this S
    norm_eps: float = 1e-6
    pad_vocab_to: int = 512           # Megatron-style vocab padding for TP

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def padded_vocab(self) -> int:
        m = self.pad_vocab_to
        return -(-self.vocab_size // m) * m

    def param_count(self) -> int:
        """Analytic parameter count (matches init below)."""
        d, h, kv, dh, f, v = (self.d_model, self.n_heads, self.n_kv_heads,
                              self.d_head, self.d_ff, self.vocab_size)
        n_ffn_mats = 2 if self.ffn_type == "relu2" else 3
        attn = d * h * dh + 2 * d * kv * dh + h * dh * d
        if self.moe is not None:
            ffn = d * self.moe.n_experts + self.moe.n_experts * n_ffn_mats * d * f
        else:
            ffn = n_ffn_mats * d * f
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + 2 * v * d + d

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top_k experts)."""
        if self.moe is None:
            return self.param_count()
        d, h, kv, dh, f = (self.d_model, self.n_heads, self.n_kv_heads,
                           self.d_head, self.d_ff)
        n_ffn_mats = 2 if self.ffn_type == "relu2" else 3
        attn = d * h * dh + 2 * d * kv * dh + h * dh * d
        ffn = d * self.moe.n_experts + self.moe.top_k * n_ffn_mats * d * f
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + 2 * self.vocab_size * d + d


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: TransformerConfig,
                dtype=jnp.float32) -> dict:
    d, h, kv, dh, f, v, L = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.d_head, cfg.d_ff, cfg.vocab_size,
                             cfg.n_layers)
    keys = jax.random.split(key, 12)

    def stack(k, shape_per_layer, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.truncated_normal(
            k, -3, 3, (L,) + shape_per_layer) * scale).astype(dtype)

    layers: dict[str, Any] = {
        "ln1": jnp.ones((L, d), dtype),
        "ln2": jnp.ones((L, d), dtype),
        "wq": stack(keys[0], (d, h * dh), d),
        "wk": stack(keys[1], (d, kv * dh), d),
        "wv": stack(keys[2], (d, kv * dh), d),
        "wo": stack(keys[3], (h * dh, d), h * dh),
    }
    gated = cfg.ffn_type != "relu2"
    if cfg.moe is None:
        if gated:
            layers["w_gate"] = stack(keys[4], (d, f), d)
        layers.update({
            "w_up": stack(keys[5], (d, f), d),
            "w_down": stack(keys[6], (f, d), f),
        })
    else:
        E = cfg.moe.n_experts
        layers["router"] = stack(keys[7], (d, E), d)
        if gated:
            layers["w_gate"] = stack(keys[4], (E, d, f), d)
        layers.update({
            "w_up": stack(keys[5], (E, d, f), d),
            "w_down": stack(keys[6], (E, f, d), f),
        })
    vp = cfg.padded_vocab
    return {
        "embed": cm.embed_init(keys[8], vp, d, dtype),
        "head": cm.dense_init(keys[9], d, vp, dtype),
        "ln_f": jnp.ones((d,), dtype),
        "layers": layers,
    }


def abstract_params(cfg: TransformerConfig, dtype=jnp.float32):
    """ShapeDtypeStruct tree (no allocation) for dry-runs."""
    return jax.eval_shape(
        lambda k: init_params(k, cfg, dtype), jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# MoE FFN (capacity dispatch, per batch row)
# ---------------------------------------------------------------------------

def moe_ffn(x: jax.Array, lp: dict, cfg: TransformerConfig,
            compute_dtype=jnp.bfloat16):
    """x: (B, S, d) -> (B, S, d), plus scalar aux load-balancing loss."""
    B, S, d = x.shape
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    C = max(1, int(math.ceil(S * k / E * cfg.moe.capacity_factor)))
    # Router matmul in compute dtype (bf16 cotangents back to x); softmax
    # statistics in f32 for stability.
    router = cm.maybe_dequant(lp["router"], compute_dtype)
    logits = jnp.einsum("bsd,de->bse", x.astype(compute_dtype), router)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (B, S, E)
    gval, eidx = jax.lax.top_k(gates, k)                         # (B, S, k)
    gval = gval / (jnp.sum(gval, axis=-1, keepdims=True) + 1e-9)

    # Aux loss (Switch): E * sum_e frac_tokens_e * mean_prob_e
    frac = jnp.mean(
        jax.nn.one_hot(eidx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    prob = jnp.mean(gates, axis=(0, 1))
    aux = E * jnp.sum(frac * prob)

    T = S * k
    eflat = eidx.reshape(B, T)                                    # slot order: (s0,c0..ck-1, s1,..)
    onehot = jax.nn.one_hot(eflat, E, dtype=jnp.int32)            # (B, T, E)
    pos = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.take_along_axis(pos, eflat[..., None], axis=-1)[..., 0]  # (B, T)
    keep = pos < C
    slot = jnp.where(keep, eflat * C + pos, E * C)                # OOB => dropped

    # Inverse permutation: which token fills each (expert, capacity) slot.
    # Built with a vmapped 1-D int scatter so SPMD never materializes a
    # per-element (B, E*C, d) index tensor (gather/scatter indices stay
    # (B, T) int32).  Dispatch itself is then a take_along_axis gather.
    tok_ids = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def _one_row(slot_r, tok_r):
        return jnp.full((E * C,), T, jnp.int32).at[slot_r].set(
            tok_r, mode="drop")

    inv = jax.vmap(_one_row)(slot, tok_ids)                       # (B, E*C)
    x_slots = jnp.repeat(x, k, axis=1).astype(compute_dtype)      # (B, T, d)
    x_pad = jnp.pad(x_slots, ((0, 0), (0, 1), (0, 0)))            # row T = 0
    hb = jnp.take_along_axis(x_pad, inv[..., None], axis=1)       # (B, E*C, d)
    hb = hints.constrain(hb.reshape(B, E, C, d), "moe_dispatch")

    wu = cm.maybe_dequant(lp["w_up"], compute_dtype)
    wd = cm.maybe_dequant(lp["w_down"], compute_dtype)
    up = jnp.einsum("becd,edf->becf", hb, wu)
    if cfg.ffn_type == "relu2":
        act = jnp.square(jax.nn.relu(up))
    else:
        wg = cm.maybe_dequant(lp["w_gate"], compute_dtype)
        act = cm.swiglu(jnp.einsum("becd,edf->becf", hb, wg), up)
    out = jnp.einsum("becf,efd->becd", act, wd)
    out = hints.constrain(out, "moe_dispatch").reshape(B, E * C, d)

    slot_safe = jnp.minimum(slot, E * C - 1)
    y = jnp.take_along_axis(out, slot_safe[..., None], axis=1)    # (B, T, d)
    y = jnp.where(keep[..., None], y, 0.0)
    y = (y.reshape(B, S, k, d) * gval[..., None].astype(compute_dtype)).sum(axis=2)
    return y.astype(x.dtype), aux


def dense_ffn(x: jax.Array, lp: dict, compute_dtype=jnp.bfloat16,
              ffn_type: str = "swiglu") -> jax.Array:
    wu = cm.maybe_dequant(lp["w_up"], compute_dtype)
    wd = cm.maybe_dequant(lp["w_down"], compute_dtype)
    xc = x.astype(compute_dtype)
    if ffn_type == "relu2":
        h = jnp.square(jax.nn.relu(xc @ wu))
    else:
        wg = cm.maybe_dequant(lp["w_gate"], compute_dtype)
        h = cm.swiglu(xc @ wg, xc @ wu)
    return (h @ wd).astype(x.dtype)


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------

def _qkv(x, lp, cfg, positions, compute_dtype):
    B, S, _ = x.shape
    wq = cm.maybe_dequant(lp["wq"], compute_dtype)
    wk = cm.maybe_dequant(lp["wk"], compute_dtype)
    wv = cm.maybe_dequant(lp["wv"], compute_dtype)
    xc = x.astype(compute_dtype)
    q = (xc @ wq).reshape(B, S, cfg.n_heads, cfg.d_head)
    k = (xc @ wk).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = (xc @ wv).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    q = cm.apply_rope(q, positions, cfg.rope_theta, cfg.rotary_frac)
    k = cm.apply_rope(k, positions, cfg.rope_theta, cfg.rotary_frac)
    return q, k, v


def _attn_full_seq(x, lp, cfg, positions, compute_dtype):
    """Self-attention over a full sequence. Returns (out, k, v)."""
    B, S, _ = x.shape
    q, k, v = _qkv(x, lp, cfg, positions, compute_dtype)
    kr = cm.repeat_kv(k, cfg.q_per_kv)
    vr = cm.repeat_kv(v, cfg.q_per_kv)
    window = cfg.window if cfg.attention == "sliding_window" else None
    if not cfg.causal:
        scale = 1.0 / math.sqrt(cfg.d_head)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vr)
    elif S > cfg.chunked_attn_threshold:
        out = cm.chunked_causal_attention(q, kr, vr, cfg.attn_block_kv, window)
    else:
        out = cm.naive_causal_attention(q, kr, vr, window)
    wo = cm.maybe_dequant(lp["wo"], compute_dtype)
    out = out.reshape(B, S, cfg.n_heads * cfg.d_head) @ wo
    return out.astype(x.dtype), k, v


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            compute_dtype=jnp.bfloat16, collect_cache: bool = False,
            remat: bool = False, sp_spec=None, return_hidden: bool = False):
    """Full-sequence forward.  tokens: (B, S) int32.

    Returns (logits, aux_loss) or (logits, aux_loss, cache) if
    ``collect_cache``.  ``remat`` checkpoints each layer (training);
    ``sp_spec`` (a PartitionSpec) sequence-shards the residual stream
    between layers (Megatron-SP style activation sharding).
    """
    B, S = tokens.shape
    embed = cm.maybe_dequant(params["embed"], compute_dtype)
    x = jnp.take(embed, tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def layer_fn(carry, lp):
        x, aux = carry
        if sp_spec is not None:
            x = jax.lax.with_sharding_constraint(x, sp_spec)
        h, k, v = _attn_full_seq(
            cm.rms_norm(x, lp["ln1"], cfg.norm_eps), lp, cfg, positions,
            compute_dtype)
        x = x + h
        xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, a = moe_ffn(xn, lp, cfg, compute_dtype)
            aux = aux + a
        else:
            h = dense_ffn(xn, lp, compute_dtype, cfg.ffn_type)
        x = x + h
        ys = (k, v) if collect_cache else None
        return (x, aux), ys

    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    (x, aux), caches = jax.lax.scan(layer_fn, (x, jnp.zeros((), jnp.float32)),
                                    params["layers"])
    x = cm.rms_norm(x, params["ln_f"], cfg.norm_eps)
    if return_hidden:
        return x
    head = cm.maybe_dequant(params["head"], compute_dtype)
    logits = x.astype(compute_dtype) @ head
    aux = aux / cfg.n_layers
    if collect_cache:
        return logits, aux, {"k": caches[0], "v": caches[1]}
    return logits, aux


def prefill(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            cache_len: int | None = None, compute_dtype=jnp.bfloat16):
    """Prefix stage: returns (last-token logits, KV cache padded to cache_len)."""
    B, S = tokens.shape
    logits, _, cache = forward(params, tokens, cfg, compute_dtype,
                               collect_cache=True)
    if cache_len is not None and cache_len > S:
        pad = ((0, 0), (0, 0), (0, cache_len - S), (0, 0), (0, 0))
        cache = {k: jnp.pad(v, pad) for k, v in cache.items()}
    return logits[:, -1], cache


def decode_step(params: dict, cache: dict, token: jax.Array,
                pos: jax.Array, cfg: TransformerConfig,
                compute_dtype=jnp.bfloat16, attn_impl=None):
    """One autoregressive step.

    cache: {"k","v"}: (L, B, S_max, H_kv, D).  token: (B,) int32.
    pos: (B,) int32 -- next position per sequence (== current cache length).
    ``attn_impl(q, k_cache, v_cache, cache_len) -> (B,1,H,D)`` lets the
    launcher swap in the distributed split-K attention.
    """
    B = token.shape[0]
    embed = cm.maybe_dequant(params["embed"], compute_dtype)
    x = jnp.take(embed, token, axis=0)[:, None, :]               # (B, 1, d)
    attn = attn_impl
    if attn is None:
        def attn(q, kc, vc, cache_len):
            kr = cm.repeat_kv(kc, cfg.q_per_kv)
            vr = cm.repeat_kv(vc, cfg.q_per_kv)
            return cm.decode_attention_ref(q, kr, vr, cache_len)

    def layer_fn(x, scanned):
        lp, kc, vc = scanned
        xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k_new, v_new = _qkv(xn, lp, cfg, pos[:, None], compute_dtype)
        # write new token into cache at pos (per-batch-row index)
        b_idx = jnp.arange(B)
        kc = kc.astype(compute_dtype).at[b_idx, pos].set(k_new[:, 0])
        vc = vc.astype(compute_dtype).at[b_idx, pos].set(v_new[:, 0])
        out = attn(q, kc, vc, pos + 1)
        wo = cm.maybe_dequant(lp["wo"], compute_dtype)
        x = x + (out.reshape(B, 1, cfg.n_heads * cfg.d_head) @ wo).astype(x.dtype)
        xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, _ = moe_ffn(xn, lp, cfg, compute_dtype)
        else:
            h = dense_ffn(xn, lp, compute_dtype, cfg.ffn_type)
        return x + h, (kc, vc)

    (x), caches = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"]))
    x = cm.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = cm.maybe_dequant(params["head"], compute_dtype)
    logits = (x.astype(compute_dtype) @ head)[:, 0]              # (B, V)
    return logits, {"k": caches[0], "v": caches[1]}


def greedy_generate(params: dict, tokens: jax.Array, lengths: jax.Array,
                    cfg: TransformerConfig, n_new: int,
                    compute_dtype=jnp.bfloat16) -> jax.Array:
    """Batched greedy continuation: ONE fused program per (T, n_new) shape.

    tokens: (B, T) int32 prompts, right-padded; lengths: (B,) valid prompt
    lengths.  Returns (B, n_new) int32 generated tokens.  The whole
    generation -- full prefill forward, per-row first-token argmax, and a
    ``lax.scan`` over decode steps -- runs inside a single XLA program, so
    jitting this (one compile per prompt bucket x n_new) replaces the
    eager one-decode-dispatch-per-token loops the serving executors used
    for query rewriting and multi-query fan-out.

    Padding is inert: row b's pad positions >= lengths[b] get garbage K/V
    from the prefill, but decode step i writes position lengths[b]+i before
    attending up to it, so every attended slot holds either real prompt
    K/V or a previously generated token's K/V.
    """
    B, T = tokens.shape
    logits, _aux, cache = forward(params, tokens, cfg, compute_dtype,
                                  collect_cache=True)
    # room for the generated tokens after the longest prompt
    pad = ((0, 0), (0, 0), (0, n_new), (0, 0), (0, 0))
    cache = {k: jnp.pad(v, pad) for k, v in cache.items()}
    lengths = lengths.astype(jnp.int32)
    first = jnp.argmax(
        logits[jnp.arange(B), lengths - 1, :cfg.vocab_size],
        axis=-1).astype(jnp.int32)

    def body(carry, _):
        tok, pos, cache = carry
        lg, cache = decode_step(params, cache, tok, pos, cfg, compute_dtype)
        nxt = jnp.argmax(lg[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
        return (nxt, pos + 1, cache), tok

    _, toks = jax.lax.scan(body, (first, lengths, cache), None, length=n_new)
    return toks.T                                     # (B, n_new)


def chunk_extend(params: dict, cache: dict, slot: jax.Array,
                 tokens: jax.Array, start_pos: jax.Array,
                 n_valid: jax.Array, cfg: TransformerConfig,
                 compute_dtype=jnp.bfloat16) -> dict:
    """Extend ONE pool slot's cache with a chunk of tokens in a single
    forward (iteration prefill for iterative retrieval, §5.3).

    cache: {"k","v"}: (L, B, S_max, H_kv, D) -- the full slot pool.
    tokens: (T,) int32, padded to T; only the first ``n_valid`` are real.
    start_pos: scalar int32 -- the slot's current cache length.

    Chunk token i attends to cache positions <= start_pos + i (the slot's
    existing prefix plus earlier chunk tokens, whose K/V are written first),
    so the result matches feeding the tokens one decode step at a time.
    Padding rows write out of bounds (dropped) and their activations are
    never read, so one compile per power-of-two bucket serves any chunk
    length.  Logits are not computed -- appended context is prompt, not
    generation.
    """
    s_max = cache["k"].shape[2]
    T = tokens.shape[0]
    embed = cm.maybe_dequant(params["embed"], compute_dtype)
    x = jnp.take(embed, tokens, axis=0)[None]                 # (1, T, d)
    offs = jnp.arange(T, dtype=jnp.int32)
    positions = (start_pos + offs)[None]                      # (1, T)
    # invalid rows target index s_max -> scatter mode="drop" discards them
    write_pos = jnp.where(offs < n_valid, start_pos + offs, s_max)
    scale = 1.0 / math.sqrt(cfg.d_head)

    def layer_fn(x, scanned):
        lp, kc, vc = scanned                    # kc: (B, S_max, H_kv, D)
        xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k_new, v_new = _qkv(xn, lp, cfg, positions, compute_dtype)
        kc = kc.astype(compute_dtype).at[slot, write_pos].set(
            k_new[0], mode="drop")
        vc = vc.astype(compute_dtype).at[slot, write_pos].set(
            v_new[0], mode="drop")
        kr = cm.repeat_kv(kc[slot][None], cfg.q_per_kv)       # (1, S, H, D)
        vr = cm.repeat_kv(vc[slot][None], cfg.q_per_kv)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(
            jnp.float32) * scale
        mask = jnp.arange(s_max)[None, None, None, :] <= \
            positions[0][None, None, :, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vr)
        wo = cm.maybe_dequant(lp["wo"], compute_dtype)
        x = x + (out.reshape(1, T, cfg.n_heads * cfg.d_head)
                 @ wo).astype(x.dtype)
        xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, _ = moe_ffn(xn, lp, cfg, compute_dtype)
        else:
            h = dense_ffn(xn, lp, compute_dtype, cfg.ffn_type)
        return x + h, (kc, vc)

    _, caches = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"]))
    return {"k": caches[0], "v": caches[1]}


def make_cache(cfg: TransformerConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16) -> dict:
    shape = (cfg.n_layers, batch, s_max, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Paged KV cache entry points
# ---------------------------------------------------------------------------
#
# Physical layout: {"k","v"}: (L, n_pages, page, H_kv*D) -- a flat pool of
# fixed-size pages shared by every sequence, each page stored as page rows
# with a position's KV heads side by side (the lane-dense tile the paged
# kernel DMAs whole).  A block table (B, M) int32 maps
# logical page j of sequence b to a physical page; position p of sequence b
# lives at physical row block_tables[b, p // page] * page + p % page.  Page
# allocation, sharing and refcounts are host-side policy
# (``repro.serving.kv_cache.PagedKVCachePool``); these entry points only
# scatter new K/V into physical rows and hand the POST-SCATTER pool plus the
# block tables to a block-table-native attention impl
# (``attn(q, k_pages, v_pages, block_tables, cache_len)``).  The default
# impl gathers the logical view (B, M*page, H, D) and runs reference masked
# softmax -- when M*page equals the dense s_max that view has the same shape
# as a dense cache slice and masked softmax zeroes every stale physical row
# exactly, so paged and dense decode agree token for token.  The Pallas
# kernel (``repro.kernels.paged_attention``) honors the same contract
# without ever materializing the gather.


def make_paged_cache(cfg: TransformerConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> dict:
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads * cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_decode_step(params: dict, cache: dict, token: jax.Array,
                      pos: jax.Array, block_tables: jax.Array,
                      cfg: TransformerConfig, compute_dtype=jnp.bfloat16,
                      attn_impl=None, write_mask: jax.Array | None = None):
    """One autoregressive step against a PAGED KV cache.

    cache: {"k","v"}: (L, P, page, H_kv*D).  token/pos: (B,) int32 as in
    :func:`decode_step`.  block_tables: (B, M) int32 physical page ids.
    The new token's K/V scatters into physical position
    ``block_tables[b, pos//page]*page + pos%page``; rows with
    ``write_mask`` False (slots not stepping this tick) target the
    out-of-bounds row ``P*page`` and are dropped, which replaces the
    dense fused path's whole-cache step-mask merge.

    ``attn_impl(q, k_pages, v_pages, block_tables, cache_len)`` is
    BLOCK-TABLE-NATIVE: it receives the post-scatter page pool
    (P, page, H_kv*D) and the tables, not a gathered per-sequence view,
    so a paged kernel can walk the pool directly.  The default impl
    reproduces the pre-kernel path bit-for-bit: gather the logical
    (B, M*page, H, D) view, repeat KV heads, reference masked softmax.
    Non-stepping rows read the same pool bytes either way (their write
    was dropped), so every impl sees identical inputs under a mask.
    """
    B = token.shape[0]
    _, P, page, row = cache["k"].shape
    M = block_tables.shape[1]
    embed = cm.maybe_dequant(params["embed"], compute_dtype)
    x = jnp.take(embed, token, axis=0)[:, None, :]               # (B, 1, d)
    page_log = pos // page
    phys = jnp.take_along_axis(
        block_tables, jnp.minimum(page_log, M - 1)[:, None], axis=1)[:, 0]
    flat = phys * page + pos % page
    flat = jnp.where(page_log < M, flat, P * page)     # OOB write -> dropped
    if write_mask is not None:
        flat = jnp.where(write_mask, flat, P * page)
    attn = attn_impl
    if attn is None:
        def attn(q, kp, vp, tables, cache_len):
            # gather each sequence's logical view: (B, M*page, H, D)
            kg = kp[tables].reshape(B, M * page, cfg.n_kv_heads, cfg.d_head)
            vg = vp[tables].reshape(B, M * page, cfg.n_kv_heads, cfg.d_head)
            kr = cm.repeat_kv(kg, cfg.q_per_kv)
            vr = cm.repeat_kv(vg, cfg.q_per_kv)
            return cm.decode_attention_ref(q, kr, vr, cache_len)

    def layer_fn(x, scanned):
        lp, kc, vc = scanned                           # (P, page, H_kv*D)
        xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k_new, v_new = _qkv(xn, lp, cfg, pos[:, None], compute_dtype)
        kf = kc.astype(compute_dtype).reshape(P * page, row)
        vf = vc.astype(compute_dtype).reshape(P * page, row)
        kf = kf.at[flat].set(k_new.reshape(B, row), mode="drop")
        vf = vf.at[flat].set(v_new.reshape(B, row), mode="drop")
        kp = kf.reshape(P, page, row)
        vp = vf.reshape(P, page, row)
        out = attn(q, kp, vp, block_tables, pos + 1)
        wo = cm.maybe_dequant(lp["wo"], compute_dtype)
        x = x + (out.reshape(B, 1, cfg.n_heads * cfg.d_head)
                 @ wo).astype(x.dtype)
        xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, _ = moe_ffn(xn, lp, cfg, compute_dtype)
        else:
            h = dense_ffn(xn, lp, compute_dtype, cfg.ffn_type)
        return x + h, (kp, vp)

    (x), caches = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"]))
    x = cm.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = cm.maybe_dequant(params["head"], compute_dtype)
    logits = (x.astype(compute_dtype) @ head)[:, 0]              # (B, V)
    return logits, {"k": caches[0], "v": caches[1]}


def paged_chunk_extend(params: dict, cache: dict, block_row: jax.Array,
                       tokens: jax.Array, start_pos: jax.Array,
                       n_valid: jax.Array, cfg: TransformerConfig,
                       compute_dtype=jnp.bfloat16):
    """Extend ONE sequence's paged cache with a chunk of tokens.

    The paged counterpart of :func:`chunk_extend` -- block_row: (M,) int32,
    the sequence's page table row.  Chunk token i scatters into the
    physical row of position ``start_pos + i`` (pad rows and positions
    past the table drop out of bounds) and attends over the gathered
    logical view, so the result matches feeding the tokens one decode
    step at a time.

    Unlike the dense version it also returns the last valid row's
    next-token logits: chunked prefill consumes a prompt piece by piece
    across decode ticks and reads the request's first token from the
    final chunk, so appended retrieval context and chunked prompt prefill
    share this one bucketed program.
    """
    _, P, page, row = cache["k"].shape
    M = block_row.shape[0]
    S = M * page
    T = tokens.shape[0]
    embed = cm.maybe_dequant(params["embed"], compute_dtype)
    x = jnp.take(embed, tokens, axis=0)[None]                 # (1, T, d)
    offs = jnp.arange(T, dtype=jnp.int32)
    positions = (start_pos + offs)[None]                      # (1, T)
    page_log = (start_pos + offs) // page
    phys = block_row[jnp.minimum(page_log, M - 1)]
    flat = phys * page + (start_pos + offs) % page
    flat = jnp.where((offs < n_valid) & (page_log < M), flat, P * page)
    scale = 1.0 / math.sqrt(cfg.d_head)

    def layer_fn(x, scanned):
        lp, kc, vc = scanned                           # (P, page, H_kv*D)
        xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k_new, v_new = _qkv(xn, lp, cfg, positions, compute_dtype)
        kf = kc.astype(compute_dtype).reshape(P * page, row)
        vf = vc.astype(compute_dtype).reshape(P * page, row)
        kf = kf.at[flat].set(k_new[0].reshape(T, row), mode="drop")
        vf = vf.at[flat].set(v_new[0].reshape(T, row), mode="drop")
        kg = kf.reshape(P, page, row)[block_row]
        vg = vf.reshape(P, page, row)[block_row]
        kr = cm.repeat_kv(kg.reshape(1, S, cfg.n_kv_heads, cfg.d_head),
                          cfg.q_per_kv)
        vr = cm.repeat_kv(vg.reshape(1, S, cfg.n_kv_heads, cfg.d_head),
                          cfg.q_per_kv)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(
            jnp.float32) * scale
        mask = jnp.arange(S)[None, None, None, :] <= \
            positions[0][None, None, :, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vr)
        wo = cm.maybe_dequant(lp["wo"], compute_dtype)
        x = x + (out.reshape(1, T, cfg.n_heads * cfg.d_head)
                 @ wo).astype(x.dtype)
        xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, _ = moe_ffn(xn, lp, cfg, compute_dtype)
        else:
            h = dense_ffn(xn, lp, compute_dtype, cfg.ffn_type)
        return x + h, (kf.reshape(P, page, row), vf.reshape(P, page, row))

    (x), caches = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"]))
    xf = cm.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = cm.maybe_dequant(params["head"], compute_dtype)
    last = xf[0, jnp.maximum(n_valid - 1, 0)]
    logits = last.astype(compute_dtype) @ head                # (V,)
    return {"k": caches[0], "v": caches[1]}, logits


def abstract_cache(cfg: TransformerConfig, batch: int, s_max: int,
                   dtype=jnp.bfloat16) -> dict:
    shape = (cfg.n_layers, batch, s_max, cfg.n_kv_heads, cfg.d_head)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def loss_fn(params: dict, tokens: jax.Array, labels: jax.Array,
            cfg: TransformerConfig, aux_weight: float = 0.01,
            compute_dtype=jnp.bfloat16, remat: bool = False,
            sp_spec=None) -> jax.Array:
    logits, aux = forward(params, tokens, cfg, compute_dtype, remat=remat,
                          sp_spec=sp_spec)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits.astype(jnp.float32))
    return cm.cross_entropy_loss(logits, labels) + aux_weight * aux


def encode(params: dict, tokens: jax.Array, cfg: TransformerConfig,
           compute_dtype=jnp.float32) -> jax.Array:
    """Mean-pooled, L2-normalized final hidden states -- the embedding path
    used by the DB encoder / query embedder / reranker components."""
    h = forward(params, tokens, cfg, compute_dtype, return_hidden=True)
    pooled = jnp.mean(h.astype(jnp.float32), axis=1)
    return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-6)


def quantize_for_serving(params: dict) -> dict:
    """Per-channel int8 quantization of all matmul weights (paper §4)."""
    out = {"ln_f": params["ln_f"],
           "embed": cm.quantize_int8(params["embed"]),
           "head": cm.quantize_int8(params["head"])}
    layers = {}
    for name, w in params["layers"].items():
        if name.startswith("ln"):
            layers[name] = w
        else:
            layers[name] = cm.quantize_int8(w)
    out["layers"] = layers
    return out
