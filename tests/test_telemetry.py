"""End-to-end observability: span timeline, metrics registry, exporters,
SLO attribution.

Fast tier: histogram bucket math, registry dict-compatibility and
detached snapshots, span tracer lifecycle, ring-buffer drop accounting,
well-formedness validation on synthetic timelines, and the Perfetto /
JSONL exporters on a hand-built trace.

Slow tier (engine builds): the chaos matrix run traced end to end -- the
fault paths are where span bookkeeping breaks first -- plus the
zero-cost-when-off guarantee (with the default ``NULL_TRACER`` the
serving path must never construct a single Span).
"""

import json
from dataclasses import replace
from types import SimpleNamespace

import numpy as np
import pytest

from repro.models import transformer as tr
from repro.serving import telemetry as T
from repro.serving.request import Request, State

VOCAB = 64


# ---------------------------------------------------------------------------
# metrics registry (fast)
# ---------------------------------------------------------------------------

def test_histogram_bucket_math():
    h = T.Histogram(bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 5.0, 50.0):
        h.observe(v)
    # bucket i counts observations <= bounds[i]; last bucket is overflow
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(55.65)
    assert h.mean == pytest.approx(55.65 / 5)
    assert h.min == 0.05 and h.max == 50.0
    # quantiles report the bucket upper bound; overflow reports the max
    assert h.quantile(0.2) == 0.1
    assert h.quantile(0.4) == 0.1          # 2 of 5 observations <= 0.1
    assert h.quantile(0.5) == 1.0          # the 3rd lands in (0.1, 1.0]
    assert h.quantile(0.99) == 50.0
    snap = h.snapshot()
    assert snap["counts"] == [2, 1, 1, 1] and snap["p99"] == 50.0
    # an empty histogram has no statistics, not fake zeros
    empty = T.Histogram(bounds=(1.0,))
    assert empty.mean is None and empty.quantile(0.5) is None
    assert empty.snapshot()["min"] is None


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError):
        T.Histogram(bounds=(1.0, 0.5))
    with pytest.raises(ValueError):
        T.Histogram(bounds=(1.0, 1.0))


def test_registry_is_dict_compatible():
    """Every call-site idiom the free-form ``self.metrics`` dicts used
    must keep working verbatim on the registry."""
    m = T.MetricsRegistry({"prefills": 0, "stage_time_s": {}})
    m["prefills"] += 3
    m["stage_time_s"]["prefill"] = (
        m["stage_time_s"].get("prefill", 0.0) + 0.25)
    m["new_counter"] = 7                       # late key creation
    assert m["prefills"] == 3 and m["new_counter"] == 7
    assert m["stage_time_s"]["prefill"] == pytest.approx(0.25)
    assert "prefills" in m and len(m) == 3
    assert set(m) == {"prefills", "stage_time_s", "new_counter"}
    # reassigning a dict into a family keeps the family's identity (the
    # idiom ``metrics["stage_time_s"] = {}`` resets, not replaces)
    fam = m["stage_time_s"]
    m["stage_time_s"] = {"decode": 1.0}
    assert m["stage_time_s"] is fam
    assert dict(fam) == {"decode": 1.0}


def test_registry_snapshot_is_detached():
    m = T.MetricsRegistry({"n": 1, "stage_time_s": {"prefill": 0.5}})
    m.observe("lat", 0.01, bounds=(0.1, 1.0))
    snap = m.snapshot()
    assert snap["n"] == 1 and snap["stage_time_s"] == {"prefill": 0.5}
    assert snap["histograms"]["lat"]["count"] == 1
    # mutating the snapshot must never reach the live registry
    snap["n"] = 99
    snap["stage_time_s"]["prefill"] = 99.0
    snap["histograms"]["lat"]["count"] = 99
    assert m["n"] == 1
    assert m["stage_time_s"]["prefill"] == 0.5
    assert m.snapshot()["histograms"]["lat"]["count"] == 1
    # and live updates do not retroactively edit old snapshots
    m["n"] += 5
    assert snap["n"] == 99 and m["n"] == 6


# ---------------------------------------------------------------------------
# span tracer (fast)
# ---------------------------------------------------------------------------

def test_span_lifecycle_and_annotate():
    tr_ = T.SpanTracer()
    tr_.event("SUBMIT", rid=7, t=1.0)
    s = tr_.begin("PREFILL", rid=7, engine="p0", t=1.5)
    tr_.annotate(7, prompt_tokens=32)
    tr_.end(s, t=2.0)
    tr_.end(s, t=9.0)                      # idempotent: first end wins
    assert s.t1 == 2.0 and s.attrs["prompt_tokens"] == 32
    d = tr_.begin("DECODE", rid=7, engine="d0", t=2.0)
    tr_.terminal(7, "done", t=3.0)
    assert d.t1 == 3.0 and d.attrs["closed_by"] == "done"
    assert not tr_.open_spans()
    kinds = [x.kind for x in tr_.spans_for(7)]
    assert kinds == ["SUBMIT", "PREFILL", "DECODE", "TERMINAL"]
    assert T.validate_spans(
        tr_, [SimpleNamespace(rid=7, state="done")]) == []
    # durations round-trip through the dict form
    as_dicts = [x.to_dict() for x in tr_.spans()]
    assert all(v["t1"] is not None for v in as_dicts if v["kind"] != "SUBMIT")


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr_ = T.SpanTracer(capacity=8)
    for i in range(20):
        tr_.record("DECODE_TICK", float(i), float(i) + 0.5, engine="d0",
                   tick=i)
    spans = tr_.spans()
    assert len(spans) == 8                  # memory stays bounded
    assert tr_.dropped == 12                # and the loss is accounted
    assert [s.tick for s in spans] == list(range(12, 20))  # oldest-first
    # with drops, completeness checks are skipped (the ring only promises
    # the recent window) but local invariants still apply
    req = SimpleNamespace(rid=999, state="done")
    assert T.validate_spans(tr_, [req]) == []


def test_validate_spans_flags_violations():
    def mkreq(rid):
        return SimpleNamespace(rid=rid, state="done")

    # an open span surviving its request's terminal state
    tr_ = T.SpanTracer()
    tr_.event("SUBMIT", rid=1, t=0.0)
    tr_.begin("DECODE", rid=1, t=1.0)
    tr_.record("TERMINAL", 2.0, 2.0, rid=1)    # terminal without close_open
    v = T.validate_spans(tr_, [mkreq(1)])
    assert any("open spans after terminal" in x for x in v)

    # two TERMINAL events for one request
    tr_ = T.SpanTracer()
    tr_.event("SUBMIT", rid=2, t=0.0)
    tr_.record("TERMINAL", 1.0, 1.0, rid=2)
    tr_.record("TERMINAL", 2.0, 2.0, rid=2)
    v = T.validate_spans(tr_, [mkreq(2)])
    assert any("TERMINAL" in x for x in v)

    # retry attempts interleaving in time
    tr_ = T.SpanTracer()
    tr_.event("SUBMIT", rid=3, t=0.0)
    tr_.record("PREFILL", 0.0, 5.0, rid=3, attempt=0)
    tr_.record("PREFILL", 1.0, 2.0, rid=3, attempt=1)   # starts inside #0
    tr_.record("TERMINAL", 6.0, 6.0, rid=3)
    v = T.validate_spans(tr_, [mkreq(3)])
    assert any("attempt" in x for x in v)

    # a healthy retry: attempt 1 strictly after attempt 0
    tr_ = T.SpanTracer()
    tr_.event("SUBMIT", rid=4, t=0.0)
    tr_.record("PREFILL", 0.0, 1.0, rid=4, attempt=0)
    tr_.record("RETRY", 1.0, 1.0, rid=4, attempt=1)
    tr_.record("PREFILL", 2.0, 3.0, rid=4, attempt=1)
    tr_.record("TERMINAL", 4.0, 4.0, rid=4)
    assert T.validate_spans(tr_, [mkreq(4)]) == []


def test_null_tracer_is_inert():
    n = T.NULL_TRACER
    assert n.enabled is False and n.dropped == 0
    assert n.begin("PREFILL", rid=1) is None
    n.end(None)
    n.end_kind(1, "PREFILL")
    n.annotate(1, a=1)
    n.close_open(1)
    n.terminal(1, "done")
    n.event("SUBMIT", rid=1)
    assert n.spans() == [] and n.spans_for(1) == [] and n.open_spans() == {}


# ---------------------------------------------------------------------------
# exporters (fast)
# ---------------------------------------------------------------------------

def _synthetic_trace():
    """Two engines, two requests, one cluster-scope instant."""
    tr_ = T.SpanTracer()
    for rid, eng in ((1, "prefill0"), (2, "decode0")):
        tr_.event("SUBMIT", rid=rid, t=0.1 * rid)
        s = tr_.begin("PREFILL", rid=rid, engine=eng, t=0.2 * rid)
        tr_.end(s, t=0.2 * rid + 0.05)
        tr_.terminal(rid, "done", t=1.0 + rid)
    tr_.record("DECODE_TICK", 0.5, 0.6, engine="decode0", tick=3)
    tr_.event("CONTROL:replan", t=0.7, attrs={"trigger": "load"})
    return tr_


def test_perfetto_export_tracks_and_events(tmp_path):
    tr_ = _synthetic_trace()
    path = tmp_path / "trace.json"
    doc = T.export_perfetto(tr_, str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == doc                       # file is the same doc
    ev = doc["traceEvents"]
    meta = [e for e in ev if e["ph"] == "M"]
    names = {(e["pid"], e.get("tid")): e["args"]["name"]
             for e in meta if e["name"] == "thread_name"}
    # one engine track per engine plus the cluster track, one per request
    assert set(names.values()) == {"cluster", "prefill0", "decode0",
                                   "req 1", "req 2"}
    procs = {e["pid"]: e["args"]["name"]
             for e in meta if e["name"] == "process_name"}
    assert set(procs.values()) == {"engines", "requests"}
    # complete spans are X events with µs timestamps >= 0 (normalized)
    xs = [e for e in ev if e["ph"] == "X"]
    assert xs and all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    # zero-duration events (SUBMIT/TERMINAL/CONTROL) render as instants
    instants = [e for e in ev if e["ph"] == "i"]
    by_name = {e["name"] for e in instants}
    assert {"SUBMIT", "TERMINAL", "CONTROL:replan"} <= by_name
    # the controller instant lands on the cluster track
    ctl = next(e for e in instants if e["name"] == "CONTROL:replan")
    assert names[(ctl["pid"], ctl["tid"])] == "cluster"
    assert doc["otherData"]["dropped_spans"] == 0


def test_jsonl_export_roundtrip(tmp_path):
    tr_ = _synthetic_trace()
    path = tmp_path / "spans.jsonl"
    n = T.export_jsonl(tr_, str(path))
    rows = T.load_spans(str(path))
    assert n == len(rows) == len(tr_.spans())
    assert {r["kind"] for r in rows} >= {"SUBMIT", "PREFILL", "TERMINAL",
                                         "DECODE_TICK", "CONTROL:replan"}
    by_kind = [r for r in rows if r["kind"] == "PREFILL"]
    assert all(r["t1"] > r["t0"] and r["engine"] for r in by_kind)


# ---------------------------------------------------------------------------
# request hook (fast): the tracer rides the state machine
# ---------------------------------------------------------------------------

def test_request_terminal_state_closes_spans():
    tr_ = T.SpanTracer()
    req = Request(question=np.zeros(4, np.int32))
    req.tracer = tr_
    tr_.event("SUBMIT", rid=req.rid, t=0.0)
    tr_.begin("DECODE", rid=req.rid, t=0.5)
    req.state = State.RETRIEVING
    req.state = State.PREFILL
    req.state = State.HANDOFF
    req.state = State.DECODE
    req.state = State.DONE                   # terminal -> TERMINAL event
    spans = tr_.spans_for(req.rid)
    assert [s.kind for s in spans][-1] == "TERMINAL"
    assert not tr_.open_spans()
    assert T.validate_spans(tr_, [req]) == []


def test_reset_for_retry_closes_attempt_and_marks_it():
    tr_ = T.SpanTracer()
    req = Request(question=np.zeros(4, np.int32))
    req.tracer = tr_
    tr_.event("SUBMIT", rid=req.rid, t=0.0)
    tr_.begin("PREFILL", rid=req.rid, t=0.5)
    req.state = State.RETRIEVING
    req.state = State.PREFILL
    req.reset_for_retry(now=1.0, backoff=0.01)
    kinds = [s.kind for s in tr_.spans_for(req.rid)]
    assert "RETRY" in kinds and not tr_.open_spans()
    retry = next(s for s in tr_.spans_for(req.rid) if s.kind == "RETRY")
    assert retry.attrs["retries"] == 1
    prefill = next(s for s in tr_.spans_for(req.rid)
                   if s.kind == "PREFILL")
    assert prefill.attrs["closed_by"] == "retry"
    # a migration is marked as such and never charged as a retry
    tr_.begin("PREFILL", rid=req.rid, t=2.0)
    req.state = State.RETRYING
    req.state = State.QUEUED
    req.state = State.RETRIEVING
    req.state = State.PREFILL
    req.reset_for_retry(now=3.0, backoff=0.0, migration=True)
    kinds = [s.kind for s in tr_.spans_for(req.rid)]
    assert "MIGRATE" in kinds


# ---------------------------------------------------------------------------
# chaos run traced end to end (slow)
# ---------------------------------------------------------------------------

def _component(seed, causal=True):
    import jax
    cfg = tr.TransformerConfig(name=f"tel{seed}", n_layers=2, d_model=32,
                               n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
                               vocab_size=VOCAB, causal=causal)
    from repro.serving.engine import Component
    return Component(cfg, tr.init_params(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def stack():
    from repro.data.synthetic import topical_corpus
    gen = _component(0)
    enc = _component(1, causal=False)
    corpus, _topics, make_q = topical_corpus(32, 8, VOCAB, n_topics=4)
    questions = [make_q(i % 4) for i in range(6)]
    return gen, enc, corpus, questions


def _traced_chaos_run(stack, schedule="combined"):
    from repro.serving.cluster import RAGCluster
    from repro.serving.engine import EngineConfig, RAGEngine
    from repro.serving.faults import (CHAOS_SCHEDULES, FaultInjector,
                                      FaultPlan)
    from repro.serving.server import RAGServer
    gen, enc, corpus, questions = stack
    cfg = EngineConfig(decode_slots=2, s_max=96, max_new_tokens=4)
    first = RAGEngine(gen, enc, corpus, replace(cfg, decode_slots=1))
    shared = dict(db_vectors=first.db_vectors, backend=first.backend)
    prefill = [first, RAGEngine(gen, enc, corpus,
                                replace(cfg, decode_slots=1), **shared)]
    decode = [RAGEngine(gen, enc, corpus, cfg, **shared) for _ in range(2)]
    injector = FaultInjector(
        FaultPlan.from_schedule(CHAOS_SCHEDULES[schedule], seed=0))
    cluster = RAGCluster(prefill, decode, injector=injector,
                         retry_backoff=0.001)
    tracer = T.SpanTracer()
    cluster.set_tracer(tracer)
    server = RAGServer(cluster)
    handles = [server.submit(q, max_new_tokens=4) for q in questions]
    server.run_until_idle(max_steps=5000)
    return cluster, server, tracer, [h.request for h in handles]


@pytest.mark.slow
def test_chaos_run_trace_is_well_formed(stack, tmp_path):
    """THE observability acceptance test: under the combined chaos
    schedule (stage error + handoff corruption + retrieval timeouts + a
    decode-engine crash) every request's span timeline must still be
    well-formed -- every span ended, one SUBMIT and one TERMINAL each,
    disjoint retry attempts -- and the trace must export to a valid
    Perfetto document with one track per engine and per request."""
    cluster, server, tracer, reqs = _traced_chaos_run(stack)
    assert all(r.state in (State.DONE, State.EXPIRED, State.FAILED)
               for r in reqs)
    assert tracer.dropped == 0
    assert T.validate_spans(tracer, reqs) == []

    kinds = {s.kind for s in tracer.spans()}
    assert "RETRY" in kinds                    # the schedule forced retries
    assert any(k.startswith("FAULT:") for k in kinds)
    assert "HANDOFF" in kinds and "PREFILL" in kinds

    # SLO attribution surfaces in both summaries when tracing is on
    slo = server.summary()["slo"]
    assert slo["n"] == len(reqs)
    assert slo["ttft_p99_s"] > 0
    assert set(slo["ttft_p99_breakdown_s"]) >= {"queue"}
    total = sum(slo["ttft_p99_breakdown_s"].values())
    assert total == pytest.approx(slo["ttft_p99_s"], rel=0.05)
    assert "slo" in cluster.group_summary()

    # span-derived latencies agree with the Request timestamps, including
    # for requests that went through a retry (per-attempt state resets)
    for r in reqs:
        if r.state is not State.DONE or r.ttft is None:
            continue
        d = T.derive_latencies(tracer, r)
        assert d["ttft"] == pytest.approx(r.ttft, abs=0.05)
        if d["tpot"] is not None and len(r.output) > 1:
            tpot = (r.latency - r.ttft) / (len(r.output) - 1)
            assert d["tpot"] == pytest.approx(tpot, abs=0.05)

    # the trace exports to a valid Perfetto doc: a track per engine (+
    # the cluster track) and one per request
    path = tmp_path / "chaos_trace.json"
    doc = T.export_perfetto(tracer, str(path))
    assert json.loads(path.read_text()) == doc
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"
            and e["name"] == "thread_name"]
    track_names = {e["args"]["name"] for e in meta}
    assert {"cluster", "prefill0", "prefill1",
            "decode0", "decode1"} <= track_names
    assert {f"req {r.rid}" for r in reqs} <= track_names
    n_terminals = sum(1 for e in doc["traceEvents"]
                      if e["ph"] == "i" and e["name"] == "TERMINAL")
    assert n_terminals == len(reqs)


@pytest.mark.slow
def test_decode_crash_retry_attempts_are_disjoint(stack):
    """A decode-engine crash mid-generation re-runs the request from the
    top; the trace must show the two attempts as time-disjoint span
    sequences with a RETRY marker between them."""
    cluster, _server, tracer, reqs = _traced_chaos_run(
        stack, schedule="decode_crash")
    assert T.validate_spans(tracer, reqs) == []
    retried = [r for r in reqs if r.retries or r.migrations]
    assert retried                         # the schedule forced recovery
    r = retried[0]
    spans = [s for s in tracer.spans_for(r.rid)
             if s.kind not in ("SUBMIT", "TERMINAL")]
    attempts = sorted({s.attempt for s in spans})
    assert len(attempts) >= 2
    first = [s for s in spans if s.attempt == attempts[0]]
    second = [s for s in spans if s.attempt == attempts[-1]]
    assert max(s.t1 for s in first) <= min(s.t0 for s in second) + 1e-6


@pytest.mark.slow
def test_tracing_off_constructs_no_spans(stack, monkeypatch):
    """Zero-cost-when-off: with the default ``NULL_TRACER`` the serving
    path must never construct a Span or a profiler ``TraceAnnotation``
    (patching both constructors to raise proves neither is reached), and
    the metrics snapshot must be fully detached from the live registry."""
    import jax.profiler

    from repro.serving.engine import EngineConfig, RAGEngine

    def boom(*a, **kw):
        raise AssertionError("Span constructed with tracing off")

    def boom_annotation(*a, **kw):
        raise AssertionError("TraceAnnotation constructed with tracing off")

    monkeypatch.setattr(T, "Span", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom_annotation)
    gen, enc, corpus, questions = stack
    eng = RAGEngine(gen, enc, corpus,
                    EngineConfig(decode_slots=2, s_max=96,
                                 max_new_tokens=4))
    assert eng.tracer is T.NULL_TRACER      # off by default
    out = eng.serve([Request(question=q.copy()) for q in questions[:3]])
    assert all(r.state is State.DONE for r in out)

    snap = eng.metrics_snapshot()
    assert snap["prefills"] >= 3 and snap["decode_steps"] > 0
    # deep-copy: mutating the snapshot cannot corrupt the live registry
    before = eng.metrics["prefills"]
    snap["prefills"] = 10_000
    snap["stage_time_s"]["prefill"] = -1.0
    assert eng.metrics["prefills"] == before
    assert eng.metrics["stage_time_s"]["prefill"] >= 0.0
