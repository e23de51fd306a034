"""Disaggregated serving cluster: bit-exact KV handoff, placement -> group
routing, plan -> group sizing, SLO-aware admission, deadline semantics at
the prefill/decode boundary, trace files, calibrate_xpu, and single-engine
vs cluster token parity.

Tier structure: the KV-handoff bit-exactness test, the state-machine and
routing tests, the trace-format tests and the calibration test are fast
(no model forward passes); everything that builds engines is ``slow``.
"""

import time
from dataclasses import replace

import numpy as np
import pytest

from repro.core.cost_model import calibrate_xpu, prefill_perf
from repro.core.hardware import XPU_C
from repro.core.ragschema import case_I
from repro.core.serving_plan import ServingPlan
from repro.core.stage_registry import DECODE, REGISTRY
from repro.models import transformer as tr
from repro.serving.kv_cache import PagedKVCachePool, payload_nbytes
from repro.serving.request import (LEGAL_TRANSITIONS, Request, State)
from repro.serving.trace import (TraceEntry, bursty_trace, load_trace,
                                 save_trace)

VOCAB = 64


def _tiny_cfg(n_layers=2, d_head=8, n_kv=2):
    return tr.TransformerConfig(name="kv", n_layers=n_layers, d_model=32,
                                n_heads=4, n_kv_heads=n_kv, d_head=d_head,
                                d_ff=64, vocab_size=VOCAB)


# ---------------------------------------------------------------------------
# KV handoff: bit-exact export/import (fast tier-1 guard)
# ---------------------------------------------------------------------------

def test_kv_export_import_bit_exact():
    """A prefix written into one pool, exported, and imported into another
    pool is bit-identical -- the invariant that makes disaggregated decode
    token-for-token equal to collocated decode."""
    import jax.numpy as jnp
    cfg = _tiny_cfg()
    src = PagedKVCachePool(cfg, n_slots=3, s_max=16, page_size=4)
    dst = PagedKVCachePool(cfg, n_slots=2, s_max=16, page_size=4)
    rng = np.random.default_rng(0)
    prefix_len = 11
    layer_cache = {
        k: jnp.asarray(rng.standard_normal(
            (cfg.n_layers, 1, prefix_len, cfg.n_kv_heads, cfg.d_head)),
            jnp.bfloat16)
        for k in ("k", "v")}
    slot = src.alloc(rid=1)
    src.write_prefix(slot, layer_cache, prefix_len)

    kv, length = src.export_slot(slot)
    assert length == prefix_len
    got = {k: np.concatenate([kv.pages[j][k] for j in sorted(kv.pages)],
                             axis=1) for k in ("k", "v")}
    assert got["k"].shape == (cfg.n_layers, prefix_len, cfg.n_kv_heads,
                              cfg.d_head)
    assert payload_nbytes(kv) == sum(v.nbytes for v in got.values())
    dslot = dst.alloc(rid=1)
    dst.import_slot(dslot, kv, length)
    assert int(dst.lengths[dslot]) == prefix_len
    for k in ("k", "v"):
        want = np.asarray(layer_cache[k][:, 0])
        assert got[k].dtype == want.dtype    # no precision lost in transit
        assert np.array_equal(got[k], want)
        rows = np.asarray(dst.cache[k][:, dst.page_tables[dslot]]).reshape(
            cfg.n_layers, -1, cfg.n_kv_heads, cfg.d_head)
        assert np.array_equal(rows[:, :prefix_len], want)
        # the tail page's rows past the prefix stay zeroed
        assert not rows[:, prefix_len:].any()


def test_kv_import_rejects_oversized_prefix():
    """Truncating a handoff would decode from a corrupted context, so a
    prefix that does not fit the destination pool raises instead."""
    cfg = _tiny_cfg()
    src = PagedKVCachePool(cfg, n_slots=1, s_max=16, page_size=4)
    dst = PagedKVCachePool(cfg, n_slots=1, s_max=8, page_size=4)
    import jax.numpy as jnp
    layer_cache = {k: jnp.ones((cfg.n_layers, 1, 12, cfg.n_kv_heads,
                                cfg.d_head), jnp.bfloat16)
                   for k in ("k", "v")}
    s = src.alloc(0)
    src.write_prefix(s, layer_cache, 12)
    kv, length = src.export_slot(s)
    d = dst.alloc(0)
    with pytest.raises(ValueError, match="s_max"):
        dst.import_slot(d, kv, length)
    assert not dst.page_tables[d] and int(dst.lengths[d]) == 0


# ---------------------------------------------------------------------------
# Lifecycle: the HANDOFF state (deadline at the group boundary)
# ---------------------------------------------------------------------------

def test_handoff_transitions_are_legal():
    """PREFILL -> HANDOFF -> DECODE is the lifecycle contract, with
    EXPIRED (deadline in the handoff queue) and the fault-recovery exits
    (RETRYING for a corrupt/dropped payload, FAILED when recovery is
    impossible); HANDOFF is unreachable except from PREFILL."""
    assert State.HANDOFF in LEGAL_TRANSITIONS[State.PREFILL]
    assert LEGAL_TRANSITIONS[State.HANDOFF] == frozenset(
        {State.DECODE, State.EXPIRED, State.RETRYING, State.FAILED})
    for state, nxt in LEGAL_TRANSITIONS.items():
        if state is not State.PREFILL:
            assert State.HANDOFF not in nxt, state


def test_handoff_expiry_history_is_legal():
    """The exact history a between-groups expiry produces walks the
    transition graph."""
    req = Request(question=np.zeros(4, np.int32))
    for s in (State.RETRIEVING, State.PREFILL, State.HANDOFF,
              State.EXPIRED):
        assert s in LEGAL_TRANSITIONS[req.state]
        req.state = s
    assert req.state_history == [State.QUEUED, State.RETRIEVING,
                                 State.PREFILL, State.HANDOFF,
                                 State.EXPIRED]
    assert req.done


# ---------------------------------------------------------------------------
# Placement -> group routing (registry) and plan -> group sizing
# ---------------------------------------------------------------------------

def test_registry_routes_stages_to_groups():
    schema = case_I()            # retrieval + prefill + decode
    groups = REGISTRY.route_groups(schema)
    assert groups["decode"] == ["decode"]
    assert groups["prefill"] == ["retrieval", "prefill"]
    # every enabled stage lands in exactly one group
    assert sorted(groups["prefill"] + groups["decode"]) == \
        sorted(schema.stages())
    for name in schema.stages():
        spec = REGISTRY.get(name)
        expect = "decode" if spec.placement == DECODE else "prefill"
        assert REGISTRY.group_for(name) == expect


def test_plan_group_sizes_keep_chip_ratio():
    plan = ServingPlan(schema=case_I(), group_chips=(4,), decode_chips=8)
    assert plan.group_sizes() == (1, 2)
    plan = ServingPlan(schema=case_I(), group_chips=(2, 2),
                       decode_chips=4)
    assert plan.group_sizes() == (1, 1)
    # clamped but ratio-preserving
    plan = ServingPlan(schema=case_I(), group_chips=(16,),
                       decode_chips=128)
    n_p, n_d = plan.group_sizes(max_per_group=4)
    assert (n_p, n_d) == (1, 4)
    # no allocation detail -> minimal cluster
    assert ServingPlan(schema=case_I()).group_sizes() == (1, 1)


# ---------------------------------------------------------------------------
# Trace files (RAGPulse-style bursty arrivals)
# ---------------------------------------------------------------------------

def test_trace_roundtrip(tmp_path):
    entries = [
        TraceEntry(0.0, np.asarray([1, 2, 3], np.int32), 4, None),
        TraceEntry(0.5, np.asarray([4, 5], np.int32), None, 2.0),
    ]
    path = tmp_path / "t.jsonl"
    save_trace(path, entries)
    back = load_trace(path)
    assert len(back) == 2
    assert back[0].max_new_tokens == 4 and back[0].deadline_s is None
    assert back[1].max_new_tokens is None and back[1].deadline_s == 2.0
    assert np.array_equal(back[1].question, entries[1].question)


def test_trace_validation(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"arrival_s": 1.0, "question": [1]}\n'
                   '{"arrival_s": 0.5, "question": [2]}\n')
    with pytest.raises(ValueError, match="sorted"):
        load_trace(bad)
    bad.write_text('{"arrival_s": 0.0, "question": []}\n')
    with pytest.raises(ValueError, match="non-empty"):
        load_trace(bad)
    bad.write_text('{"question": [1]}\n')
    with pytest.raises(ValueError, match="bad trace entry"):
        load_trace(bad)


def test_bursty_trace_is_bursty():
    entries = bursty_trace(40, VOCAB, burst_rate=50.0, idle_rate=1.0,
                           burst_len=5, seed=3)
    arr = np.asarray([e.arrival_s for e in entries])
    gaps = np.diff(arr)
    assert np.all(gaps >= 0)
    # overdispersed: burst gaps are far shorter than idle gaps
    assert np.percentile(gaps, 25) * 10 < np.percentile(gaps, 90)
    assert all(0 <= t < VOCAB for e in entries for t in e.question)


def test_checked_in_trace_is_wellformed():
    """The committed bursty example trace parses and fits the bench
    workload (vocab 128, positive horizons)."""
    from pathlib import Path
    path = Path(__file__).resolve().parent.parent / "benchmarks" / \
        "traces" / "bursty_rag.jsonl"
    entries = load_trace(path)
    assert len(entries) >= 20
    assert all(0 <= t < 128 for e in entries for t in e.question)
    assert any(e.deadline_s is not None for e in entries)
    assert any(e.deadline_s is None for e in entries)
    assert entries[-1].arrival_s < 30.0      # bench replay stays CI-sized


# ---------------------------------------------------------------------------
# calibrate_xpu: measured wall time moves analytical predictions
# ---------------------------------------------------------------------------

def test_calibrate_xpu_moves_prediction_toward_measured():
    schema = case_I("8B")
    before = prefill_perf(schema.generative, XPU_C, 1, 1,
                          schema.prefix_len).latency
    measured = before * 40.0                 # deployed system is far slower
    spec = calibrate_xpu(XPU_C, schema, {"prefill": measured * 6}, 6)
    after = prefill_perf(schema.generative, spec, 1, 1,
                         schema.prefix_len).latency
    assert abs(after - measured) < abs(before - measured)
    assert abs(after - measured) / measured < 0.05   # fixed point converged
    assert 0 < spec.flops_eff <= 1.0 and 0 < spec.mem_eff <= 1.0
    # measured faster than predicted: efficiencies rise but stay clamped
    fast = calibrate_xpu(XPU_C, schema, {"prefill": before * 0.5 * 4}, 4)
    assert fast.flops_eff >= XPU_C.flops_eff
    assert fast.flops_eff <= 1.0
    with pytest.raises(ValueError):
        calibrate_xpu(XPU_C, schema, {"prefill": 0.0}, 4)
    with pytest.raises(ValueError):
        calibrate_xpu(XPU_C, schema, {"prefill": 1.0}, 0)


# ---------------------------------------------------------------------------
# Cluster end-to-end (slow: builds engines)
# ---------------------------------------------------------------------------

def _component(seed, causal=True, d=32):
    import jax
    cfg = tr.TransformerConfig(name=f"cl{seed}", n_layers=2, d_model=d,
                               n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
                               vocab_size=VOCAB, causal=causal)
    from repro.serving.engine import Component
    return Component(cfg, tr.init_params(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def stack():
    from repro.data.synthetic import topical_corpus
    gen = _component(0)
    enc = _component(1, causal=False)
    corpus, topics, make_q = topical_corpus(32, 8, VOCAB, n_topics=4)
    return gen, enc, corpus, make_q


def _cluster(stack, n_prefill=1, n_decode=1, predicted_ttft=None, **kw):
    from repro.serving.cluster import RAGCluster
    from repro.serving.engine import EngineConfig, RAGEngine
    gen, enc, corpus, _ = stack
    kw.setdefault("decode_slots", 2)
    kw.setdefault("s_max", 96)
    kw.setdefault("max_new_tokens", 5)
    cfg = EngineConfig(**kw)
    first = RAGEngine(gen, enc, corpus, replace(cfg, decode_slots=1))
    prefill = [first] + [
        RAGEngine(gen, enc, corpus, replace(cfg, decode_slots=1),
                  db_vectors=first.db_vectors)
        for _ in range(n_prefill - 1)]
    decode = [RAGEngine(gen, enc, corpus, cfg, db_vectors=first.db_vectors)
              for _ in range(n_decode)]
    return RAGCluster(prefill, decode, predicted_ttft=predicted_ttft)


@pytest.mark.slow
def test_cluster_token_parity_with_single_engine(stack):
    """Acceptance: the same request set produces identical token streams
    on the collocated single-engine RAGServer and on a 1-prefill +
    1-decode RAGCluster -- the KV handoff is bit-exact end to end,
    including through iterative retrieval."""
    from repro.serving.engine import EngineConfig, RAGEngine
    from repro.serving.server import RAGServer
    gen, enc, corpus, make_q = stack
    kw = dict(decode_slots=2, s_max=96, max_new_tokens=7,
              iterative_interval=3, retrieval_batch=2)
    questions = [make_q(i % 4) for i in range(5)]

    ref = RAGServer(RAGEngine(gen, enc, corpus, EngineConfig(**kw)))
    ref_handles = [ref.submit(q.copy()) for q in questions]
    ref.run_until_idle()

    srv = RAGServer.from_cluster(_cluster(stack, **kw))
    clu_handles = [srv.submit(q.copy()) for q in questions]
    srv.run_until_idle()

    assert [h.output for h in ref_handles] == \
        [h.output for h in clu_handles]
    assert all(h.state is State.DONE for h in clu_handles)
    for h in clu_handles:
        hist = h.request.state_history
        assert State.HANDOFF in hist
        for a, b in zip(hist, hist[1:]):
            assert b in LEGAL_TRANSITIONS[a], hist
    cl = srv.cluster
    assert cl.metrics["handoffs"] == len(questions)
    assert cl.metrics["handoff_bytes"] > 0


@pytest.mark.slow
def test_paged_cluster_parity_and_handoff_dedup(stack):
    """Acceptance: a 1-prefill + 1-decode cluster is token-for-token
    identical to a collocated engine (the page-granular handoff is a pure
    optimization), and with repeated questions the handoff ships fewer
    bytes than the whole prefix -- pages the decode pool already caches
    are referenced, not transferred."""
    from repro.serving.engine import EngineConfig, RAGEngine
    from repro.serving.server import RAGServer
    gen, enc, corpus, make_q = stack
    kw = dict(decode_slots=2, s_max=96, max_new_tokens=7,
              iterative_interval=3, retrieval_batch=2)
    # a popular-question workload: repeats rebuild identical prefixes
    popular = [make_q(0), make_q(1)]
    questions = [popular[i % 2] for i in range(6)]

    ref = RAGServer(RAGEngine(gen, enc, corpus, EngineConfig(**kw)))
    ref_handles = [ref.submit(q.copy()) for q in questions]
    ref.run_until_idle()

    srv = RAGServer.from_cluster(_cluster(stack, **kw))
    clu_handles = [srv.submit(q.copy()) for q in questions]
    srv.run_until_idle()

    assert [h.output for h in ref_handles] == \
        [h.output for h in clu_handles]
    assert all(h.state is State.DONE for h in clu_handles)
    m = srv.cluster.metrics
    assert m["handoffs"] == len(questions)
    # page-granular dedup: repeats shipped less than the whole prefix
    assert m["handoff_pages_shared"] > 0
    assert 0 < m["handoff_bytes"] < m["handoff_bytes_full"]
    assert m["handoff_pages"] > 0
    # the prefill engines shared prefix pages across the repeats too
    assert sum(e.pool.metrics["pages_shared"]
               for e in srv.cluster.prefill_engines) > 0


@pytest.mark.slow
def test_cluster_spreads_load_across_groups(stack):
    """2 prefill + 2 decode engines: least-loaded dispatch uses both
    prefill engines, decode assignment uses both decode engines, and the
    group summary accounts every request."""
    from repro.serving.server import RAGServer
    gen, enc, corpus, make_q = stack
    srv = RAGServer.from_cluster(
        _cluster(stack, n_prefill=2, n_decode=2, decode_slots=1,
                 max_new_tokens=4))
    handles = [srv.submit(make_q(i % 4)) for i in range(6)]
    srv.run_until_idle()
    assert all(h.state is State.DONE for h in handles)
    cl = srv.cluster
    assert set(cl.prefill_of.values()) == {0, 1}
    assert set(cl.decode_of.values()) == {0, 1}
    g = cl.group_summary()
    assert g["prefill"]["n_engines"] == g["decode"]["n_engines"] == 2
    assert sum(p["n"] for p in g["prefill"]["per_engine"]) == 6
    assert sum(p["n"] for p in g["decode"]["per_engine"]) == 6
    assert g["prefill"]["ttft_s"]["p99"] > 0
    assert g["decode"]["tpot_s"]["p99"] > 0


@pytest.mark.slow
def test_slo_admission_sheds_predicted_expired(stack):
    """A request whose deadline cannot be met under the plan-predicted
    TTFT is EXPIRED at submission -- before any prefill or retrieval."""
    from repro.serving.server import RAGServer
    gen, enc, corpus, make_q = stack
    srv = RAGServer.from_cluster(_cluster(stack, predicted_ttft=10.0))
    doomed = srv.submit(make_q(0), deadline=time.monotonic() + 0.5)
    fine = srv.submit(make_q(1), deadline=time.monotonic() + 60.0)
    srv.run_until_idle()
    assert doomed.state is State.EXPIRED
    assert doomed.request.state_history == [State.QUEUED, State.EXPIRED]
    assert doomed.output == []
    assert fine.state is State.DONE
    cl = srv.cluster
    assert cl.metrics["shed_requests"] == 1
    # only the surviving request was ever prefilled
    assert sum(e.metrics["prefills"] for e in cl.prefill_engines) == 1
    assert srv.n_expired == 1 and srv.summary()["n_expired"] == 1


@pytest.mark.slow
def test_expiry_between_prefill_and_decode(stack):
    """Satellite acceptance: a request whose deadline passes while queued
    between prefill completion and decode-slot assignment ends EXPIRED
    with a legal history (... -> PREFILL -> HANDOFF -> EXPIRED): it was
    prefilled (first token exists) but never decoded."""
    from repro.serving.server import RAGServer
    srv = RAGServer.from_cluster(
        _cluster(stack, decode_slots=1, max_new_tokens=12))
    cl = srv.cluster
    _, _, _, make_q = stack

    # occupy the only decode slot with a long-running request
    blocker = srv.submit(make_q(0))
    while not any(e.active for e in cl.decode_engines):
        srv.step()
    # victim: prefilled while the slot is held, deadline in the gap
    victim = srv.submit(make_q(1), deadline=time.monotonic() + 0.15)
    cl._dispatch_prefill()
    assert victim.state is State.HANDOFF
    assert len(victim.request.output) == 1      # first token produced
    time.sleep(0.2)                              # deadline passes in handoff
    srv.run_until_idle()
    assert victim.state is State.EXPIRED
    assert len(victim.request.output) == 1       # never decoded
    hist = victim.request.state_history
    assert hist[-3:] == [State.PREFILL, State.HANDOFF, State.EXPIRED]
    for a, b in zip(hist, hist[1:]):
        assert b in LEGAL_TRANSITIONS[a], hist
    assert cl.metrics["expired_in_handoff"] == 1
    assert blocker.state is State.DONE


@pytest.mark.slow
def test_cluster_replay_trace_per_request_fields(stack):
    """Trace replay drives the cluster with per-entry token budgets."""
    from repro.serving.server import RAGServer
    gen, enc, corpus, make_q = stack
    entries = [
        TraceEntry(0.0, make_q(0), 3, None),
        TraceEntry(0.02, make_q(1), 5, None),
        TraceEntry(0.04, make_q(2), None, None),   # falls back to default
    ]
    srv = RAGServer.from_cluster(_cluster(stack, max_new_tokens=6))
    handles = srv.replay_trace(entries, max_new_tokens=4)
    assert [h.state for h in handles] == [State.DONE] * 3
    assert [len(h.output) for h in handles] == [3, 5, 4]
