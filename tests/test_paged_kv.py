"""Paged KV cache pool: page-granular handoff bit-exactness, prefix
sharing (refcounts, copy-on-extend, eviction), capacity invariants, and
the regressions this layout's engine integration fixed (ragged iterative
batches, decode overflowing s_max, empty prompt budgets).

Tier structure mirrors test_cluster: pool-level tests fabricate K/V and
are fast; anything that builds a RAGEngine is ``slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _oracle import assert_follows_forward, spy_appends

from repro.models import transformer as tr
from repro.serving.engine import EngineConfig
from repro.serving.kv_cache import (ImportStats, PagedKVCachePool,
                                    PagedPrefix, payload_nbytes)
from repro.serving.request import Request, State

VOCAB = 64


def _tiny_cfg():
    return tr.TransformerConfig(name="pg", n_layers=2, d_model=32,
                                n_heads=4, n_kv_heads=2, d_head=8,
                                d_ff=64, vocab_size=VOCAB)


def _rand_cache(cfg, p, seed=0):
    """A fabricated prefill product: {"k","v"}: (L, 1, P, H_kv, D)."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.standard_normal(
                (cfg.n_layers, 1, p, cfg.n_kv_heads, cfg.d_head)),
                jnp.bfloat16)
            for k in ("k", "v")}


def _slot_contents(pool: PagedKVCachePool, slot: int) -> dict:
    """Assemble a slot's logical prefix {"k","v"}: (L, length, H_kv*D)
    from its page table."""
    length = int(pool.lengths[slot])
    ps = pool.page_size
    out = {}
    for k, v in pool.cache.items():
        rows = [np.asarray(v[:, phys, :min(length - j * ps, ps)])
                for j, phys in enumerate(pool.page_tables[slot])
                if j * ps < length]
        out[k] = np.concatenate(rows, axis=1)
    return out


# ---------------------------------------------------------------------------
# Page-granular handoff: bit-exact round trip + import dedup (fast)
# ---------------------------------------------------------------------------

def test_paged_export_import_bit_exact():
    """A prefix written into a paged pool, exported page-by-page, and
    imported into another paged pool is bit-identical."""
    cfg = _tiny_cfg()
    src = PagedKVCachePool(cfg, n_slots=2, s_max=32, page_size=16)
    dst = PagedKVCachePool(cfg, n_slots=2, s_max=32, page_size=16)
    p = 23                                   # 1 full keyed page + 7-row tail
    cache = _rand_cache(cfg, p, seed=1)
    tokens = np.arange(p, dtype=np.int32)
    slot = src.alloc(rid=0)
    src.write_prefix(slot, cache, p, tokens=tokens, key_salt=b"32")

    kv, length = src.export_slot(slot)
    assert isinstance(kv, PagedPrefix) and length == p
    assert kv.keys[0] is not None            # full page is content-addressed
    assert kv.keys[1] is None                # partial tail never is
    assert kv.pages[0]["k"].shape == (cfg.n_layers, 16, cfg.n_kv_heads,
                                      cfg.d_head)
    assert kv.pages[1]["k"].shape[1] == p - 16
    # the payload is the whole prefix: K and V, L * length * H_kv*D each
    itemsize = np.dtype(jnp.bfloat16).itemsize
    assert payload_nbytes(kv) == 2 * (cfg.n_layers * p * cfg.n_kv_heads
                                      * cfg.d_head * itemsize)

    dslot = dst.alloc(rid=0)
    stats = dst.import_slot(dslot, kv, length)
    assert stats == ImportStats(kv.nbytes, 2, 0)   # cold pool: all shipped
    assert int(dst.lengths[dslot]) == p
    a, b = _slot_contents(src, slot), _slot_contents(dst, dslot)
    for k in ("k", "v"):
        assert a[k].dtype == b[k].dtype      # no precision lost in transit
        assert np.array_equal(a[k], b[k])


def test_import_dedup_ships_only_missing_pages():
    """Importing the same prefix twice: the second import references the
    keyed page the pool already caches -- shipped bytes drop to the tail
    page only, and the result is still bit-exact."""
    cfg = _tiny_cfg()
    src = PagedKVCachePool(cfg, n_slots=1, s_max=32, page_size=16)
    dst = PagedKVCachePool(cfg, n_slots=2, s_max=32, page_size=16)
    p = 23
    slot = src.alloc(0)
    src.write_prefix(slot, _rand_cache(cfg, p, seed=2), p,
                     tokens=np.arange(p, dtype=np.int32), key_salt=b"s")
    kv, length = src.export_slot(slot)

    d0 = dst.alloc(0)
    first = dst.import_slot(d0, kv, length)
    d1 = dst.alloc(1)
    second = dst.import_slot(d1, kv, length)
    assert first.pages_shared == 0 and second.pages_shared == 1
    assert second.pages == 1                 # only the tail page travelled
    assert 0 < second.nbytes < first.nbytes
    # both slots resolve to the SAME physical page for the shared prefix
    assert dst.page_tables[d0][0] == dst.page_tables[d1][0]
    assert dst.metrics["pages_shared"] == 1
    a, b = _slot_contents(dst, d0), _slot_contents(dst, d1)
    assert all(np.array_equal(a[k], b[k]) for k in ("k", "v"))


def test_import_rejects_layout_mismatches():
    cfg = _tiny_cfg()
    src = PagedKVCachePool(cfg, n_slots=1, s_max=48, page_size=16)
    slot = src.alloc(0)
    src.write_prefix(slot, _rand_cache(cfg, 40, seed=3), 40,
                     tokens=np.arange(40, dtype=np.int32))
    kv, length = src.export_slot(slot)
    # only a PagedPrefix is importable
    dst = PagedKVCachePool(cfg, n_slots=1, s_max=48, page_size=16)
    with pytest.raises(TypeError, match="PagedPrefix"):
        dst.import_slot(dst.alloc(0), {"k": np.zeros(1), "v": np.zeros(1)}, 1)
    # page geometry must agree end to end
    odd = PagedKVCachePool(cfg, n_slots=1, s_max=48, page_size=8)
    with pytest.raises(ValueError, match="page_size"):
        odd.import_slot(odd.alloc(0), kv, length)
    # a prefix that does not fit raises instead of truncating
    small = PagedKVCachePool(cfg, n_slots=1, s_max=32, page_size=16)
    with pytest.raises(ValueError, match="s_max"):
        small.import_slot(small.alloc(0), kv, length)


# ---------------------------------------------------------------------------
# Prefix sharing: refcounts, immutability, copy-on-extend, eviction (fast)
# ---------------------------------------------------------------------------

def test_release_of_one_sharer_never_frees_a_live_page():
    cfg = _tiny_cfg()
    pool = PagedKVCachePool(cfg, n_slots=3, s_max=16, page_size=16)
    tokens = np.arange(16, dtype=np.int32)
    cache = _rand_cache(cfg, 16, seed=4)
    a = pool.alloc(0)
    pool.write_prefix(a, cache, 16, tokens=tokens, key_salt=b"x")
    b = pool.alloc(1)
    # identical tokens + salt: the second prefill references the cached
    # page instead of writing its own
    pool.write_prefix(b, _rand_cache(cfg, 16, seed=5), 16, tokens=tokens,
                      key_salt=b"x")
    phys = pool.page_tables[a][0]
    assert pool.page_tables[b][0] == phys
    assert pool.ref[phys] == 2 and pool.metrics["pages_shared"] == 1
    want = _slot_contents(pool, a)

    pool.release(a)
    assert pool.ref[phys] == 1               # b still holds the page
    assert phys not in pool.free_pages and phys not in pool._evictable
    got = _slot_contents(pool, b)
    assert all(np.array_equal(want[k], got[k]) for k in ("k", "v"))

    pool.release(b)                          # last sharer gone: page stays
    assert pool.ref[phys] == 0               # cached (evictable), not freed
    assert phys in pool._evictable and phys not in pool.free_pages
    c = pool.alloc(2)                        # ...and a later identical
    pool.write_prefix(c, _rand_cache(cfg, 16, seed=6), 16, tokens=tokens,
                      key_salt=b"x")         # prefill revives it from cache
    assert pool.page_tables[c][0] == phys and pool.ref[phys] == 1
    got = _slot_contents(pool, c)            # bytes never mutated in cache
    assert all(np.array_equal(want[k], got[k]) for k in ("k", "v"))


def test_copy_on_extend_isolates_shared_pages():
    """Writing into a shared or content-addressed page copies it first:
    the writer gets a private physical page, every other sharer (and the
    prefix index) keeps the original bytes."""
    cfg = _tiny_cfg()
    pool = PagedKVCachePool(cfg, n_slots=2, s_max=16, page_size=16)
    tokens = np.arange(16, dtype=np.int32)
    a = pool.alloc(0)
    pool.write_prefix(a, _rand_cache(cfg, 16, seed=7), 16, tokens=tokens)
    b = pool.alloc(1)
    pool.write_prefix(b, _rand_cache(cfg, 16, seed=8), 16, tokens=tokens)
    shared = pool.page_tables[a][0]
    want = _slot_contents(pool, a)

    pool._make_writable(a, 0)                # refcount > 1: must copy
    pa = pool.page_tables[a][0]
    assert pa != shared and pool.ref[shared] == 1 and pool.ref[pa] == 1
    assert pool.metrics["pages_cow"] == 1
    pool._make_writable(b, 0)                # refcount 1 but cached: copy too
    pb = pool.page_tables[b][0]
    assert pb != shared and pool.metrics["pages_cow"] == 2
    # the cached original survives both writers, bytes intact
    key = pool.key_of[shared]
    assert pool.prefix_index[key] == shared and shared in pool._evictable
    for slot in (a, b):
        got = _slot_contents(pool, slot)
        assert all(np.array_equal(want[k], got[k]) for k in ("k", "v"))
    # a private uncached page is already writable: no copy happens
    pool._make_writable(a, 0)
    assert pool.page_tables[a][0] == pa and pool.metrics["pages_cow"] == 2


def test_page_pressure_evicts_lru_then_raises():
    cfg = _tiny_cfg()
    # 1 slot x 1 page + 1 spare = 2 physical pages total
    pool = PagedKVCachePool(cfg, n_slots=1, s_max=16, page_size=16,
                            spare_pages=1)
    assert pool.n_pages == 2
    s = pool.alloc(0)
    pool.write_prefix(s, _rand_cache(cfg, 16, seed=9), 16,
                      tokens=np.arange(16, dtype=np.int32))
    cold_key = pool.key_of[pool.page_tables[s][0]]
    pool.release(s)                          # page parked in the prefix cache
    assert len(pool._evictable) == 1
    s = pool.alloc(1)                        # different tokens: cache miss,
    pool.write_prefix(s, _rand_cache(cfg, 16, seed=10), 16,
                      tokens=np.arange(16, 32, dtype=np.int32))
    # the free page was used first; the cached page is still parked
    assert pool.metrics["pages_evicted"] == 0
    pool._take_page()                        # pressure: evict the cached page
    assert pool.metrics["pages_evicted"] == 1
    assert cold_key not in pool.prefix_index and not pool._evictable
    with pytest.raises(RuntimeError, match="out of pages"):
        pool._take_page()                    # everything is now referenced


# ---------------------------------------------------------------------------
# Capacity invariant: lengths can never pass s_max (fast)
# ---------------------------------------------------------------------------

def test_pool_capacity_invariants():
    cfg = _tiny_cfg()
    pool = PagedKVCachePool(cfg, n_slots=1, s_max=16, page_size=8)
    s = pool.alloc(0)
    pool.write_prefix(s, _rand_cache(cfg, 16, seed=11), 16)
    with pytest.raises(AssertionError, match="s_max"):
        pool.prepare_append(s, 1)            # no room to stage a write
    with pytest.raises(AssertionError, match="s_max"):
        pool.advance([s])                    # ...nor to advance past the end
    # every slot taken: alloc refuses; a slot freed and taken again starts
    # empty, and a write range past s_max is refused before any page is
    # allocated for it
    assert pool.alloc(1) is None
    pool.release(s)
    assert pool.alloc(1) == s
    assert int(pool.lengths[s]) == 0 and not pool.page_tables[s]
    with pytest.raises(AssertionError, match="s_max"):
        pool.prepare_append(s, 17)
    assert not pool.page_tables[s]


def test_positions_are_a_snapshot():
    """``positions()`` feeds a step that may still be running when the host
    calls ``advance``; the array it returned must keep its values."""
    cfg = _tiny_cfg()
    pool = PagedKVCachePool(cfg, n_slots=2, s_max=16, page_size=8)
    s = pool.alloc(0)
    pool.write_prefix(s, _rand_cache(cfg, 5, seed=3), 5)
    pos = pool.positions()
    pool.advance([s])
    assert np.asarray(pos)[s] == 5
    assert np.asarray(pool.positions())[s] == 6


# ---------------------------------------------------------------------------
# The decode step's in-place layer write (fast)
# ---------------------------------------------------------------------------

def test_paged_decode_step_writes_only_stepping_rows():
    """One ``paged_decode_step`` over a float32 pool writes each stepping
    slot's row at ``pos`` in every layer and nothing else: masked slots
    and a position past the block table are dropped, and the pool keeps
    its dtype.  The written K/V is the dense ``decode_step``'s on the same
    logical cache (the "ref" attention reads the same bytes)."""
    cfg = _tiny_cfg()
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    page, m, b = 4, 4, 4
    row = cfg.n_kv_heads * cfg.d_head
    rng = np.random.default_rng(3)
    pool = {k: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, b * m + 2, page, row)), jnp.float32)
        for k in ("k", "v")}
    tables = rng.permutation(b * m + 2)[:b * m].reshape(b, m)
    token = jnp.asarray([3, 5, 7, 9], jnp.int32)
    # slot 1 is masked; slot 3 steps at the first position past its table
    pos = np.array([6, 2, 12, m * page])
    mask = np.array([True, False, True, True])
    _, out = tr.paged_decode_step(params, pool, token, jnp.asarray(pos),
                                  jnp.asarray(tables, jnp.int32), cfg,
                                  write_mask=jnp.asarray(mask))
    # the same logical cache, dense: (L, B, m*page, H_kv, D)
    dense = {k: v[:, tables].reshape(cfg.n_layers, b, m * page,
                                     cfg.n_kv_heads, cfg.d_head)
             for k, v in pool.items()}
    _, dense_out = tr.decode_step(params, dense, token,
                                  jnp.asarray(np.minimum(pos, m * page - 1)),
                                  cfg)
    for k in ("k", "v"):
        assert out[k].dtype == jnp.float32
        want = np.asarray(pool[k]).copy()
        for s in np.flatnonzero(mask & (pos < m * page)):
            phys = tables[s, pos[s] // page]
            want[:, phys, pos[s] % page] = np.asarray(
                dense_out[k][:, s, pos[s]], np.float32).reshape(
                    cfg.n_layers, row)
        assert np.array_equal(np.asarray(out[k]), want)


def test_engine_config_validation():
    # s_max must leave a positive prompt budget (s_max - max_new - 1), or
    # _assemble_prompt's tail slice keeps the whole prompt and decode
    # overflows the cache
    with pytest.raises(ValueError, match="prompt budget"):
        EngineConfig(s_max=17, max_new_tokens=16)
    EngineConfig(s_max=18, max_new_tokens=16)          # minimal legal budget
    with pytest.raises(ValueError, match="page_size"):
        EngineConfig(page_size=0)
    with pytest.raises(ValueError, match="iter_query_tokens"):
        EngineConfig(iter_query_tokens=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineConfig(prefill_chunk=0)
    EngineConfig(prefill_chunk=8)
    # one pool and one decode path: the dense-pool knobs are gone
    for knob in ("paged", "fused_decode"):
        with pytest.raises(TypeError, match=knob):
            EngineConfig(**{knob: False})


# ---------------------------------------------------------------------------
# Engine integration (slow: builds engines, jit-compiles)
# ---------------------------------------------------------------------------

ENG_VOCAB = 128


def _component(seed, causal=True, d=48):
    import jax
    from repro.serving.engine import Component
    cfg = tr.TransformerConfig(name=f"pk{seed}", n_layers=2, d_model=d,
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


def _engine(stack, **kw):
    from repro.serving.engine import RAGEngine
    gen, enc, corpus, _ = stack
    kw.setdefault("decode_slots", 3)
    kw.setdefault("s_max", 96)
    kw.setdefault("max_new_tokens", 6)
    return RAGEngine(gen, enc, corpus, EngineConfig(**kw))


@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    {},                                                    # baseline
    {"iterative_interval": 3, "retrieval_batch": 2,
     "max_new_tokens": 9},                                 # iterative preset
], ids=["baseline", "iterative"])
def test_engine_tokens_follow_forward(stack, kw):
    """The paged engine's tokens are the teacher-forced forward's greedy
    choices over each request's logical sequence, on the baseline and the
    iterative-retrieval configurations."""
    gen, _, _, make_q = stack
    engine = _engine(stack, **kw)
    appends = spy_appends(engine)
    reqs = [Request(question=make_q(i % 4)) for i in range(5)]
    engine.serve(reqs)
    assert all(r.state is State.DONE for r in reqs)
    assert_follows_forward(gen, reqs, appends)
    m = engine.metrics_snapshot()
    assert m["pages_allocated"] > 0
    assert m["capacity_stops"] == 0
    assert 0 < m["decode_host_syncs"] <= m["decode_steps"]


@pytest.mark.slow
def test_chunked_prefill_token_parity(stack):
    """Continuous batching's chunked prefill (one prompt chunk per tick)
    yields the same first token and the same stream as the monolithic
    bucketed prefill."""
    _, _, _, make_q = stack
    questions = [make_q(i % 4) for i in range(4)]

    def run(chunk):
        engine = _engine(stack, prefill_chunk=chunk)
        reqs = [Request(question=q.copy()) for q in questions]
        engine.serve(reqs)
        assert engine.metrics["prefills"] == len(questions)
        assert all(r.ttft is not None for r in reqs)
        return [r.output for r in reqs]

    assert run(None) == run(16) == run(8)


@pytest.mark.slow
def test_ragged_iterative_batch_regression(stack):
    """Regression: with retrieval_batch > 1, an iterative batch mixing a
    generated-token query with a shorter question-tail query used to
    crash ``np.stack`` (ragged shapes).  Fixed-width queries keep the
    batch rectangular for any mix of question lengths."""
    _, _, _, make_q = stack
    engine = _engine(stack, iterative_interval=3, retrieval_batch=2,
                     max_new_tokens=9)
    reqs = [Request(question=make_q(0, q_len=5)),
            Request(question=make_q(1, q_len=11))]
    engine.serve(reqs)
    assert all(r.state is State.DONE for r in reqs)
    assert all(r.retrievals_done >= 1 for r in reqs)
    assert all(len(r.output) == 9 for r in reqs)
    w = engine.cfg.iter_query_tokens
    assert all(len(engine._iter_query(r)) == w for r in reqs)


@pytest.mark.slow
def test_iterative_append_reserves_decode_room(stack):
    """Regression: iterative appends used to keep a fixed 2-token
    headroom, letting decode advance lengths past s_max (silently dropped
    K/V writes = corrupted context).  The append budget now reserves one
    position per remaining decode token, so a tight cache finishes every
    request with the pool invariant intact."""
    _, _, _, make_q = stack
    engine = _engine(stack, s_max=48, max_new_tokens=12,
                     iterative_interval=2, retrieval_k=2)
    reqs = [Request(question=make_q(i % 4)) for i in range(3)]
    engine.serve(reqs)                       # pool.advance asserts throughout
    assert all(r.state is State.DONE for r in reqs)
    assert all(len(r.output) == 12 for r in reqs)      # no tokens lost
    assert engine.metrics["capacity_stops"] == 0
    assert (engine.pool.lengths <= engine.pool.s_max).all()


@pytest.mark.slow
def test_decode_finishes_at_capacity(stack):
    """A slot whose cache is already full (e.g. a handed-off prefix at
    exactly s_max) finishes instead of decoding past the end."""
    engine = _engine(stack, s_max=32, max_new_tokens=8)
    gen_cfg = engine.gen.cfg
    slot = engine.pool.alloc(rid=0)
    engine.pool.write_prefix(slot, _rand_cache(gen_cfg, 32, seed=12), 32)
    req = Request(question=np.zeros(4, np.int32), max_new_tokens=8)
    for s in (State.RETRIEVING, State.PREFILL, State.DECODE):
        req.state = s
    req.slot = slot
    req.output.append(1)
    engine.active[slot] = req
    engine._decode_step()
    assert req.state is State.DONE and req.t_done is not None
    assert len(req.output) == 1              # nothing decoded past capacity
    assert engine.metrics["capacity_stops"] == 1
    assert slot in engine.pool.free          # slot recycled
