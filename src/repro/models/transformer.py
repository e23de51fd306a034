"""Decoder-only transformer (dense + MoE) with GQA, RoPE and SwiGLU.

Layers are stacked along a leading L axis and executed with ``jax.lax.scan``
so 28-48-layer models compile quickly and produce compact HLO.  Every entry
point runs the same layer body (``_layer``) and differs only in its scan
wrapper and its ``attend`` strategy, the cache write and attention:

  * ``forward``            -- full-sequence logits, optionally the K/V
                              (prefill, training, encoder use)
  * ``paged_decode_step``  -- one token per sequence against the paged pool
  * ``paged_chunk_extend`` -- a chunk of one sequence's tokens into the pool
  * ``decode_step``        -- one token against a dense cache (scaffolding)

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
    """positions: (B, S), or (B,) for one token per sequence."""
    B, S, _ = x.shape
    if positions.ndim == 1:
        positions = positions[:, None]
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


def _attn_full_seq(q, k, v, cfg):
    """Self-attention of a full sequence over itself: (B, S, H, D)."""
    S = q.shape[1]
    kr = cm.repeat_kv(k, cfg.q_per_kv)
    vr = cm.repeat_kv(v, cfg.q_per_kv)
    window = cfg.window if cfg.attention == "sliding_window" else None
    if not cfg.causal:
        scale = 1.0 / math.sqrt(cfg.d_head)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vr)
    if S > cfg.chunked_attn_threshold:
        return cm.chunked_causal_attention(q, kr, vr, cfg.attn_block_kv,
                                           window)
    return cm.naive_causal_attention(q, kr, vr, window)


def _layer(x, lp, cfg, positions, attend, compute_dtype):
    """The one decoder layer every entry point runs: rms_norm -> QKV ->
    ``attend`` -> output projection and residual -> rms_norm -> dense or
    MoE FFN and residual.

    ``attend(q, k, v) -> (out, attend_out)`` is the entry point's own cache
    write and attention; ``out`` is (B, S, H, D) and ``attend_out`` is
    whatever its scan needs back (the layer's K/V, or the updated pool).
    Returns ``(x, aux, attend_out)``, with ``aux`` the MoE load-balancing
    loss (None for a dense FFN).  The named scopes are the ones the device
    trace's readers key on: ``attention``, ``ffn``, and ``kv_write`` inside
    the paged ``attend``s."""
    B, S, _ = x.shape
    with jax.named_scope("attention"):
        xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(xn, lp, cfg, positions, compute_dtype)
    out, attend_out = attend(q, k, v)
    with jax.named_scope("attention"):
        wo = cm.maybe_dequant(lp["wo"], compute_dtype)
        x = x + (out.reshape(B, S, cfg.n_heads * cfg.d_head)
                 @ wo).astype(x.dtype)
    aux = None
    with jax.named_scope("ffn"):
        xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            h, aux = moe_ffn(xn, lp, cfg, compute_dtype)
        else:
            h = dense_ffn(xn, lp, compute_dtype, cfg.ffn_type)
        x = x + h
    return x, aux, attend_out


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
    with jax.named_scope("embed"):
        embed = cm.maybe_dequant(params["embed"], compute_dtype)
        x = jnp.take(embed, tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def attend(q, k, v):
        with jax.named_scope("attention"):
            return _attn_full_seq(q, k, v, cfg), (k, v)

    def layer_fn(carry, lp):
        x, aux = carry
        if sp_spec is not None:
            x = jax.lax.with_sharding_constraint(x, sp_spec)
        x, a, kv = _layer(x, lp, cfg, positions, attend, compute_dtype)
        if a is not None:
            with jax.named_scope("ffn"):
                aux = aux + a
        return (x, aux), (kv if collect_cache else None)

    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    # the scan's own slicing and its stacked K/V outputs: the cache write
    with jax.named_scope("kv_write"):
        (x, aux), caches = jax.lax.scan(
            layer_fn, (x, jnp.zeros((), jnp.float32)), params["layers"])
    with jax.named_scope("head"):
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
    """One autoregressive step against a dense cache.

    cache: {"k","v"}: (L, B, S_max, H_kv, D).  token: (B,) int32.
    pos: (B,) int32 -- next position per sequence (== current cache length).
    ``attn_impl(q, k_cache, v_cache, cache_len) -> (B,1,H,D)`` swaps the
    attention.  Serving decodes through :func:`paged_decode_step`; this
    dense step and :func:`abstract_cache` remain only for the seed
    scaffolding (``launch/steps.py``, ``perf/variants.py``) and go with it
    (ROADMAP Queue 3, item 1).
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
    b_idx = jnp.arange(B)

    def layer_fn(x, scanned):
        lp, kc, vc = scanned

        def attend(q, k, v):
            # write the new token at pos (per-batch-row index), then attend
            kc_ = kc.astype(compute_dtype).at[b_idx, pos].set(k[:, 0])
            vc_ = vc.astype(compute_dtype).at[b_idx, pos].set(v[:, 0])
            return attn(q, kc_, vc_, pos + 1), (kc_, vc_)

        x, _, kv = _layer(x, lp, cfg, pos, attend, compute_dtype)
        return x, kv

    x, caches = jax.lax.scan(
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
    ``lax.scan`` of :func:`paged_decode_step` calls -- runs inside a single
    XLA program, so jitting this (one compile per prompt bucket x n_new)
    replaces one decode dispatch per token in the serving executors'
    query rewriting and multi-query fan-out.

    The decode steps run on a private page pool: row b owns pages
    [b*M, (b+1)*M) (the identity block table), with M pages enough for
    T + n_new positions, and the prefill's K/V is laid into them in the
    pool's row layout.  Padding is inert: row b's pad positions >=
    lengths[b] hold garbage K/V from the prefill, but decode step i writes
    position lengths[b]+i before attending up to it, so every attended
    row holds either real prompt K/V or a generated token's K/V.
    """
    B, T = tokens.shape
    page = 16                       # the engine's page size; any size works
    M = -(-(T + n_new) // page)
    logits, _aux, kv = forward(params, tokens, cfg, compute_dtype,
                               collect_cache=True)
    pad = ((0, 0), (0, 0), (0, M * page - T), (0, 0), (0, 0))
    row = cfg.n_kv_heads * cfg.d_head
    cache = {k: jnp.pad(v, pad).reshape(cfg.n_layers, B * M, page, row)
             for k, v in kv.items()}
    tables = jnp.arange(B * M, dtype=jnp.int32).reshape(B, M)
    lengths = lengths.astype(jnp.int32)
    first = jnp.argmax(
        logits[jnp.arange(B), lengths - 1, :cfg.vocab_size],
        axis=-1).astype(jnp.int32)

    def body(carry, _):
        tok, pos, cache = carry
        lg, cache = paged_decode_step(params, cache, tok, pos, tables, cfg,
                                      compute_dtype)
        nxt = jnp.argmax(lg[:, :cfg.vocab_size], axis=-1).astype(jnp.int32)
        return (nxt, pos + 1, cache), tok

    _, toks = jax.lax.scan(body, (first, lengths, cache), None, length=n_new)
    return toks.T                                     # (B, n_new)


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
# softmax, which zeroes every stale physical row exactly.  The Pallas
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

    cache: {"k","v"}: (L, P, page, H_kv*D).  token: (B,) int32.  pos: (B,)
    int32, each sequence's next position (== its current length).
    block_tables: (B, M) int32 physical page ids.
    The new token's K/V scatters into physical position
    ``block_tables[b, pos//page]*page + pos%page``; rows with
    ``write_mask`` False (slots not stepping this tick) target the
    out-of-bounds row ``P*page`` and are dropped, so the pool is never
    touched for them.

    ``attn_impl(q, k_pages, v_pages, block_tables, cache_len)`` is
    BLOCK-TABLE-NATIVE: it receives the post-scatter page pool
    (P, page, H_kv*D) and the tables, not a gathered per-sequence view,
    so a paged kernel can walk the pool directly.  The default impl
    reproduces the pre-kernel path bit-for-bit: gather the logical
    (B, M*page, H, D) view, repeat KV heads, reference masked softmax.
    Non-stepping rows read the same pool bytes either way (their write
    was dropped), so every impl sees identical inputs under a mask.

    The pool is carried through the layer scan and written in place, one
    row per stepping slot and layer; it keeps its own dtype (the new K/V
    is cast to it, the layer's pages to ``compute_dtype`` for attention).
    """
    B = token.shape[0]
    L, P, page, row = cache["k"].shape
    M = block_tables.shape[1]
    with jax.named_scope("embed"):
        embed = cm.maybe_dequant(params["embed"], compute_dtype)
        x = jnp.take(embed, token, axis=0)[:, None, :]           # (B, 1, d)
    with jax.named_scope("kv_write"):
        page_log = pos // page
        phys = jnp.take_along_axis(
            block_tables, jnp.minimum(page_log, M - 1)[:, None],
            axis=1)[:, 0]
        flat = phys * page + pos % page
        flat = jnp.where(page_log < M, flat, P * page)  # OOB write -> dropped
        if write_mask is not None:
            flat = jnp.where(write_mask, flat, P * page)
        page_idx, page_row = flat // page, flat % page  # dropped: page P
    attn = attn_impl
    if attn is None:
        def attn(q, kp, vp, tables, cache_len):
            # gather each sequence's logical view: (B, M*page, H, D)
            kg = kp[tables].reshape(B, M * page, cfg.n_kv_heads, cfg.d_head)
            vg = vp[tables].reshape(B, M * page, cfg.n_kv_heads, cfg.d_head)
            kr = cm.repeat_kv(kg, cfg.q_per_kv)
            vr = cm.repeat_kv(vg, cfg.q_per_kv)
            return cm.decode_attention_ref(q, kr, vr, cache_len)

    def layer_fn(carry, scanned):
        x, k_pool, v_pool = carry                  # pools: (L, P, page, row)
        lp, l = scanned

        def attend(q, k, v):
            with jax.named_scope("kv_write"):
                kpool = k_pool.at[l, page_idx, page_row].set(
                    k.reshape(B, row).astype(k_pool.dtype), mode="drop")
                vpool = v_pool.at[l, page_idx, page_row].set(
                    v.reshape(B, row).astype(v_pool.dtype), mode="drop")
                kp = jax.lax.dynamic_index_in_dim(
                    kpool, l, 0, keepdims=False).astype(compute_dtype)
                vp = jax.lax.dynamic_index_in_dim(
                    vpool, l, 0, keepdims=False).astype(compute_dtype)
            with jax.named_scope("attention"):
                out = attn(q, kp, vp, block_tables, pos + 1)
            return out, (kpool, vpool)

        x, _, (k_pool, v_pool) = _layer(x, lp, cfg, pos, attend,
                                        compute_dtype)
        return (x, k_pool, v_pool), None

    # the pools ride in the carry, not in xs/ys: stacked ys make XLA build
    # a second pool and copy it into the donated output on every step.
    # Under this scope stay the scan's slices of the layer weights.
    with jax.named_scope("kv_write"):
        (x, k_pool, v_pool), _ = jax.lax.scan(
            layer_fn, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    with jax.named_scope("head"):
        x = cm.rms_norm(x, params["ln_f"], cfg.norm_eps)
        head = cm.maybe_dequant(params["head"], compute_dtype)
        logits = (x.astype(compute_dtype) @ head)[:, 0]          # (B, V)
    return logits, {"k": k_pool, "v": v_pool}


def paged_chunk_extend(params: dict, cache: dict, block_row: jax.Array,
                       tokens: jax.Array, start_pos: jax.Array,
                       n_valid: jax.Array, cfg: TransformerConfig,
                       compute_dtype=jnp.bfloat16):
    """Extend ONE sequence's paged cache with a chunk of tokens.

    block_row: (M,) int32, the sequence's page table row.  tokens: (T,)
    int32, padded to T; only the first ``n_valid`` are real.  start_pos:
    scalar int32, the sequence's current length.  Chunk token i scatters
    into the physical row of position ``start_pos + i`` (pad rows and
    positions past the table drop out of bounds) and attends over the
    gathered logical view, so the result matches feeding the tokens one
    decode step at a time, and one compile per power-of-two bucket serves
    any chunk length.

    Returns ``(cache, logits)``, the latter the last valid row's
    next-token logits: chunked prefill consumes a prompt piece by piece
    across decode ticks and reads the request's first token from the
    final chunk, so appended retrieval context and chunked prompt prefill
    share this one bucketed program.
    """
    _, P, page, row = cache["k"].shape
    M = block_row.shape[0]
    S = M * page
    T = tokens.shape[0]
    with jax.named_scope("embed"):
        embed = cm.maybe_dequant(params["embed"], compute_dtype)
        x = jnp.take(embed, tokens, axis=0)[None]             # (1, T, d)
    offs = jnp.arange(T, dtype=jnp.int32)
    positions = (start_pos + offs)[None]                      # (1, T)
    with jax.named_scope("kv_write"):
        page_log = (start_pos + offs) // page
        phys = block_row[jnp.minimum(page_log, M - 1)]
        flat = phys * page + (start_pos + offs) % page
        flat = jnp.where((offs < n_valid) & (page_log < M), flat, P * page)
    scale = 1.0 / math.sqrt(cfg.d_head)

    def layer_fn(x, scanned):
        lp, kc, vc = scanned                           # (P, page, H_kv*D)

        def attend(q, k, v):
            with jax.named_scope("kv_write"):
                kf = kc.astype(compute_dtype).reshape(P * page, row)
                vf = vc.astype(compute_dtype).reshape(P * page, row)
                kf = kf.at[flat].set(k[0].reshape(T, row), mode="drop")
                vf = vf.at[flat].set(v[0].reshape(T, row), mode="drop")
            with jax.named_scope("attention"):
                kg = kf.reshape(P, page, row)[block_row]
                vg = vf.reshape(P, page, row)[block_row]
                kr = cm.repeat_kv(
                    kg.reshape(1, S, cfg.n_kv_heads, cfg.d_head), cfg.q_per_kv)
                vr = cm.repeat_kv(
                    vg.reshape(1, S, cfg.n_kv_heads, cfg.d_head), cfg.q_per_kv)
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(
                    jnp.float32) * scale
                mask = jnp.arange(S)[None, None, None, :] <= \
                    positions[0][None, None, :, None]
                scores = jnp.where(mask, scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
                out = jnp.einsum("bhqk,bkhd->bqhd", probs, vr)
            return out, (kf.reshape(P, page, row), vf.reshape(P, page, row))

        x, _, kv = _layer(x, lp, cfg, positions, attend, compute_dtype)
        return x, kv

    with jax.named_scope("kv_write"):
        (x), caches = jax.lax.scan(
            layer_fn, x, (params["layers"], cache["k"], cache["v"]))
    with jax.named_scope("head"):
        xf = cm.rms_norm(x, params["ln_f"], cfg.norm_eps)
        head = cm.maybe_dequant(params["head"], compute_dtype)
        last = xf[0, jnp.maximum(n_valid - 1, 0)]
        logits = last.astype(compute_dtype) @ head            # (V,)
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
