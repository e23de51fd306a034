"""ChatGLM3-6B's attention geometry on the serving path, against the
benchmark's float32 reference.

ChatGLM3-6B has 32 query heads over 2 KV heads of 128 (16 query heads per
KV group, 256-wide page rows) and turns only the leading 64 of each head's
128 dims (``rotary_frac`` 0.5).  A tiny model keeps that geometry (2
layers, ``d_model`` 128, ``d_ff`` 256, vocabulary 512) and goes through
the programs the engine runs: the bucketed ``transformer.forward`` prefill,
``PagedKVCachePool.write_prefix``'s page install, then paged decode steps
with the Pallas paged-attention kernel (interpret mode) over two slots of
different lengths that cross page boundaries.  Every logit row is compared
with ``bench/references/dense_decoder.decoder_logits`` on the same
weights and tokens.  The program runs in float32 here, so the two differ
only by the order of float32 sums.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import model as bm
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.models import transformer as tr
from repro.serving.engine import bucket_len
from repro.serving.kv_cache import PagedKVCachePool

MODEL = {"num_hidden_layers": 2, "hidden_size": 128,
         "num_attention_heads": 32, "num_key_value_heads": 2,
         "head_dim": 128, "intermediate_size": 256, "vocab_size": 512,
         "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
         "partial_rotary_factor": 0.5, "reference": "dense_decoder"}
# the program's config from the same sizes, as the benchmark's harness
# builds it
CFG = harness._transformer_config(tr, MODEL, "chatglm-geometry")
PAGE, S_MAX = 16, 64
PROMPTS = (40, 23)            # a partial last page each
STEPS = 20                    # both slots cross at least one page boundary
# float32 program against the float32 reference: logits of unit scale
# agree to within 1e-5 (the order of float32 sums, in 2 layers).  The same
# reference on the weights rounded to bf16 -- the step below -- lands
# 3e-2 to 4e-2 away, so 1e-3 tells the two apart with room on both sides.
TOL = 1e-3


def _serve(params):
    """Prefill both prompts, then ``STEPS`` greedy paged decode steps.
    Returns each slot's full token sequence and its logits rows, one per
    token the program chose (the first from prefill)."""
    rng = np.random.default_rng(7)
    pool = PagedKVCachePool(CFG, n_slots=len(PROMPTS), s_max=S_MAX,
                            page_size=PAGE, spare_pages=2,
                            dtype=jnp.float32)
    prefill = jax.jit(partial(tr.forward, cfg=CFG, collect_cache=True,
                              compute_dtype=jnp.float32))
    step = jax.jit(partial(
        tr.paged_decode_step, cfg=CFG, compute_dtype=jnp.float32,
        attn_impl=partial(paged_decode_attention, interpret=True)))
    seqs, rows, slots = [], [], []
    for rid, n in enumerate(PROMPTS):
        prompt = rng.integers(0, CFG.vocab_size, n).astype(np.int32)
        padded = np.zeros((1, bucket_len(n)), np.int32)
        padded[0, :n] = prompt
        logits, _, cache = prefill(params, jnp.asarray(padded))
        slot = pool.alloc(rid)
        pool.write_prefix(slot, cache, n, tokens=prompt, key_salt=b"g")
        first = np.asarray(logits[0, n - 1, :CFG.vocab_size])
        seqs.append(list(prompt) + [int(first.argmax())])
        rows.append([first])
        slots.append(slot)
    for _ in range(STEPS):
        token = np.zeros(pool.n_slots, np.int32)
        for i, slot in enumerate(slots):
            pool.prepare_append(slot, 1)
            token[slot] = seqs[i][-1]
        logits, pool.cache = step(
            params, pool.cache, jnp.asarray(token), pool.positions(),
            jnp.asarray(pool.block_tables()),
            write_mask=jnp.ones(pool.n_slots, bool))
        pool.advance(slots)
        logits = np.asarray(logits[:, :CFG.vocab_size])
        for i, slot in enumerate(slots):
            rows[i].append(logits[slot])
            seqs[i].append(int(logits[slot].argmax()))
    return seqs, [np.stack(r) for r in rows]


def _reference(ref, params, seq, n_prompt):
    """The reference's logits at the positions the program predicted
    from: the last prompt token and each served token but the last."""
    kw = ref.decoder_kwargs(MODEL)
    toks = np.zeros(S_MAX, np.int32)
    toks[:len(seq) - 1] = seq[:-1]
    out = ref.decoder_logits(params, jnp.asarray(toks), **kw)
    return np.asarray(out[n_prompt - 1:len(seq) - 1])


@pytest.fixture(scope="module")
def served():
    params = bm.make_params(bm.key_of(2**31 + 11, 1), bm.arch_of(MODEL),
                            dtype=jnp.float32)
    seqs, rows = _serve(params)
    return params, seqs, rows


def test_geometry_is_chatglm3():
    assert (CFG.n_heads // CFG.n_kv_heads, CFG.d_head) == (16, 128)
    assert bm.rotary_dims(CFG.d_head, CFG.rotary_frac) == 64
    assert CFG.n_kv_heads * CFG.d_head == 256      # the pool's page row


def test_paged_decode_logits_match_reference(served):
    params, seqs, rows = served
    ref = harness.reference(harness.BENCH_DIR, MODEL)
    for seq, got, n in zip(seqs, rows, PROMPTS):
        want = _reference(ref, params, seq, n)
        assert got.shape == want.shape == (STEPS + 1, CFG.vocab_size)
        assert np.abs(got - want).max() < TOL, np.abs(got - want).max()


def test_tolerance_refuses_a_bf16_reference(served):
    """The reference on bf16-rounded weights, one precision below the
    comparison's, misses ``TOL`` on the same tokens."""
    params, seqs, rows = served
    ref = harness.reference(harness.BENCH_DIR, MODEL)
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    worst = max(np.abs(_reference(ref, rounded, seq, n) - got).max()
                for seq, got, n in zip(seqs, rows, PROMPTS))
    assert worst > TOL, worst
