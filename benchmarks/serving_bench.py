"""Serving benchmark harness: drives ``RAGEngine`` over the
``configs/rag_pipelines`` presets and writes ``BENCH_serving.json``.

Per preset x retrieval backend it reports QPS, TTFT, TPOT, tokens/s,
retrieval recall@k vs the exact backend, and the engine's hot-path metrics
(host syncs, cache-copy bytes, per-stage wall time), so successive PRs
have a perf trajectory (RAGPulse-style: measure the pipeline, not just the
kernels).  It also times the IVF-PQ scan and emits the calibrated per-core
scan bandwidth the analytical retrieval model
(``core/retrieval_model.calibrate_host``) can consume in place of the
paper's 18 GB/s constant.

Engine configuration is DERIVED from each preset's RAGSchema
(``EngineConfig.from_schema``) -- the schema picks the stages, this
harness only applies test-scale clamps (tiny stand-in models bench the
serving machinery, not model FLOPs; paper-scale numbers come from the
analytical cost model).

Latency is reported as means AND p50/p95/p99 percentiles (TTFT, TPOT);
``--compare`` gates the p99 tail too, so a change that only hurts the
tail still fails CI.

Modes:
    PYTHONPATH=src python benchmarks/serving_bench.py            # full
    PYTHONPATH=src python benchmarks/serving_bench.py --smoke    # CI smoke
    ... --optimize         # schema -> enumerate_plans -> best_qps_per_chip
                           #   -> ServingPlan -> RAGServer.from_plan ->
                           #   open-loop Poisson traffic (the paper's
                           #   "optimize then serve" story end to end)
    ... --optimize --topology disagg
                           # deploy each plan's placement as a disaggregated
                           #   RAGCluster (prefill + decode engine groups,
                           #   KV handoff) and drive Poisson traffic AND the
                           #   checked-in bursty arrival trace through it;
                           #   reports p50/p99 TTFT/TPOT per engine group
    ... --faults           # drive a 2+2 disaggregated cluster through the
                           #   fixed "combined" chaos schedule (crashes,
                           #   handoff corruption, retrieval timeouts) and
                           #   report goodput, recovery counters and the
                           #   termination invariant under faults
    ... --trace-out T.json # export a Chrome/Perfetto trace (chrome://tracing
                           #   or https://ui.perfetto.dev) of the chaos run
                           #   when --faults is on, else of the telemetry
                           #   overhead run; a JSONL span log lands next to
                           #   it at T.json.spans.jsonl
    ... --autoscale        # drive a minimal 1+1 cluster through a scripted
                           #   workload shift (low-rate phase A -> high-rate
                           #   phase B) with the live ClusterController
                           #   attached: drift detection, calibrated
                           #   re-plan, zero-drop make-before-break resize.
                           #   Reports goodput, dropped count, p99 TTFT
                           #   before/during/after the resize, bit-parity
                           #   vs an unresized run, and post-resize p99 vs
                           #   a fresh deploy at the final size
    ... --compare PREV.json [--tolerance 0.25]
                           # nonzero exit on QPS / TPOT / p99-tail /
                           # goodput-under-faults / autoscale / tracing-
                           # overhead regression vs a previous
                           # BENCH_serving.json
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

RETRIEVAL_K = 2
DEFAULT_TRACE = Path(__file__).resolve().parent / "traces" / \
    "bursty_rag.jsonl"


def _percentile_fields(ttfts, tpots) -> dict:
    """p50/p95/p99 TTFT/TPOT fields (tail latency, RAGPulse-style)."""
    from repro.serving.cluster import percentiles
    out = {}
    for key, vals in (("ttft", ttfts), ("tpot", tpots)):
        for p, v in percentiles(vals).items():
            out[f"{key}_{p}_s"] = v
    return out


def _components(schema, vocab: int):
    """Tiny transformer stand-ins for the stages the schema enables."""
    import jax

    from repro.models import transformer as tr
    from repro.serving.engine import Component

    def mk(seed, causal=True, d=48):
        cfg = tr.TransformerConfig(name=f"bench{seed}", n_layers=2,
                                   d_model=d, n_heads=4, n_kv_heads=2,
                                   d_head=16, d_ff=64, vocab_size=vocab,
                                   causal=causal)
        return Component(cfg, tr.init_params(jax.random.PRNGKey(seed), cfg))

    comps = {"generative": mk(0), "encoder": mk(1, causal=False, d=32)}
    if schema.rewriter is not None:
        comps["rewriter"] = mk(2)
    if schema.reranker is not None:
        comps["reranker"] = mk(3, causal=False, d=32)
    if schema.safety_model is not None:
        comps["safety"] = mk(4, causal=False, d=32)
    return comps


def _scale_clamps(cfg):
    """Test-scale clamps on schema-derived sizes: tiny stand-in models
    keep PR-over-PR numbers comparable (3 rewrite tokens, 2 fan-out
    tokens, 6 rerank candidates -- the workload PR 3 pinned; iterative
    retrievals every 3 tokens so paper-scale intervals still fire events
    within the bench's short generations)."""
    return replace(cfg,
                   rewrite_tokens=min(cfg.rewrite_tokens, 3),
                   fanout_tokens=min(cfg.fanout_tokens, 2),
                   rerank_candidates=min(cfg.rerank_candidates, 6),
                   iterative_interval=(min(cfg.iterative_interval, 3)
                                       if cfg.iterative_interval else None))


def _engine_config(schema, backend: str, *, s_max: int, max_new_tokens: int,
                   attn_impl: str = "auto"):
    """Stage enabling comes from the schema via the registry
    (EngineConfig.from_schema); only deployment/test-scale knobs are set
    here.  Prefill stays monolithic (no ``prefill_chunk``): only the
    monolithic bucketed prefill content-addresses pages, so this is the
    path where the popular-question workload's prefix sharing
    (``pages_shared``) shows up per row."""
    from repro.serving.engine import EngineConfig
    cfg = EngineConfig.from_schema(
        schema, decode_slots=4, s_max=s_max, retrieval_k=RETRIEVAL_K,
        max_new_tokens=max_new_tokens, retrieval_backend=backend,
        attn_impl=attn_impl)
    return _scale_clamps(cfg)


def _recall_vs_exact(engine, questions) -> float:
    """Mean recall@k of the engine's backend against exact search over the
    engine's own database embeddings."""
    from repro.retrieval.backend import ExactBackend
    from repro.retrieval.ivf_pq import overlap_recall
    qv = engine._embed_batched(np.stack(questions))
    exact = ExactBackend(engine.db_vectors)
    _, e_ids = exact.search(qv, RETRIEVAL_K)
    _, a_ids = engine.backend.search(qv, RETRIEVAL_K)
    return overlap_recall(a_ids, e_ids)


def run_preset(name: str, schema, backend: str, corpus, questions,
               max_new_tokens: int, attn_impl: str = "auto") -> dict:
    from repro.serving.engine import RAGEngine
    from repro.serving.request import Request, State

    comps = _components(schema, vocab=128)
    cfg = _engine_config(schema, backend, s_max=128,
                         max_new_tokens=max_new_tokens, attn_impl=attn_impl)
    engine = RAGEngine(comps["generative"], comps["encoder"], corpus, cfg,
                       rewriter=comps.get("rewriter"),
                       reranker=comps.get("reranker"),
                       safety=comps.get("safety"))
    reqs = [Request(question=q.copy()) for q in questions]
    t0 = time.perf_counter()
    out = engine.serve(reqs)
    wall = time.perf_counter() - t0
    done = [r for r in out if r.state is State.DONE]
    ttfts = [r.ttft for r in done if r.ttft is not None]
    tpots = [(r.latency - r.ttft) / (len(r.output) - 1)
             for r in done if r.ttft is not None and len(r.output) > 1]
    tokens = sum(len(r.output) for r in done)
    metrics = engine.metrics_snapshot()
    return {
        "backend": backend,
        # which decode-attention implementation actually ran (the engine
        # resolves "auto" by backend), plus its per-step decode wall time
        # -- the number a kernel regression moves even when QPS is
        # admission-bound, gated by --compare like the p99 tails
        "attn_impl": engine.attn_impl,
        "decode_step_s": round(
            metrics["stage_time_s"].get("decode", 0.0)
            / max(metrics["decode_steps"], 1), 6),
        "n_requests": len(reqs),
        "n_done": len(done),
        "wall_s": round(wall, 4),
        "qps": round(len(done) / wall, 3),
        "ttft_s": round(statistics.mean(ttfts), 5) if ttfts else None,
        "tpot_s": round(statistics.mean(tpots), 5) if tpots else None,
        **_percentile_fields(ttfts, tpots),
        "tokens_per_s": round(tokens / wall, 2),
        "recall_at_k_vs_exact": round(_recall_vs_exact(engine, questions), 4),
        "xpu_calibration": _xpu_calibration(schema, engine.metrics),
        # engine counters + the paged pool's page accounting
        # (pages_allocated / pages_shared / pages_cow / pages_evicted)
        "metrics": metrics,
    }


def _xpu_calibration(schema, metrics) -> dict:
    """Measured per-stage wall time -> calibrated XPU-side cost model
    (``core/cost_model.calibrate_xpu``): what efficiency factors make the
    analytical prefill prediction match this run.

    Caveat (shared with every number this CPU-container bench emits): the
    measured mean includes each prompt bucket's one-time jit compile, so
    at bench scale the fit mostly absorbs compile overhead; on a real
    deployment with warmed buckets it tracks steady-state prefill."""
    from repro.core.cost_model import calibrate_xpu, prefill_perf
    from repro.core.hardware import XPU_C
    measured = metrics["stage_time_s"]["prefill"] / metrics["prefills"]
    spec = calibrate_xpu(XPU_C, schema, metrics["stage_time_s"],
                         metrics["prefills"])
    return {
        "measured_prefill_s": round(measured, 5),
        "predicted_before_s": round(prefill_perf(
            schema.generative, XPU_C, 1, 1, schema.prefix_len).latency, 6),
        "predicted_after_s": round(prefill_perf(
            schema.generative, spec, 1, 1, schema.prefix_len).latency, 6),
        "flops_eff": round(spec.flops_eff, 8),
        "mem_eff": round(spec.mem_eff, 8),
    }


def run_optimized(name: str, schema, corpus, questions, max_new_tokens: int,
                  rate_qps: float, topology: str = "single",
                  trace_file=None) -> dict:
    """The closed loop the paper promises, end to end: RAGO searches the
    schema, the winning PlanPoint becomes a ServingPlan, the plan deploys
    as a RAGServer (collocated single engine, or -- ``topology='disagg'``
    -- a RAGCluster realizing the plan's placement as prefill + decode
    engine groups with KV handoff), and open-loop traffic streams through
    it: Poisson arrivals, plus the bursty arrival-trace file under the
    disaggregated topology."""
    from repro.core.hardware import SystemConfig, XPU_C
    from repro.core.serving_plan import ServingPlan
    from repro.serving.server import RAGServer, poisson_offsets

    system = SystemConfig(n_servers=4, xpu=XPU_C)     # small 16-XPU slice
    t0 = time.perf_counter()
    plan = ServingPlan.optimize(schema, system)
    search_s = time.perf_counter() - t0

    comps = _components(schema, vocab=128)
    disagg = topology in ("disagg", "disaggregated")
    # test-scale deployment clamps (plan decode batches target real XPUs,
    # not this CPU container; engine-group sizes capped at 2 per group)
    clamps = dict(decode_slots=4, s_max=128, retrieval_k=RETRIEVAL_K,
                  max_new_tokens=max_new_tokens)
    if disagg:
        n_p, n_d = plan.group_sizes(max_per_group=2)
        server = RAGServer.from_plan(
            plan, comps["generative"], comps["encoder"], corpus,
            rewriter=comps.get("rewriter"), reranker=comps.get("reranker"),
            safety=comps.get("safety"), topology="disagg",
            n_prefill=n_p, n_decode=n_d, **clamps)
        for eng in (server.cluster.prefill_engines
                    + server.cluster.decode_engines):
            eng.cfg = _scale_clamps(eng.cfg)
    else:
        server = RAGServer.from_plan(
            plan, comps["generative"], comps["encoder"], corpus,
            rewriter=comps.get("rewriter"), reranker=comps.get("reranker"),
            safety=comps.get("safety"), **clamps)
        server.engine.cfg = _scale_clamps(server.engine.cfg)

    offsets = poisson_offsets(rate_qps, len(questions), seed=0)
    t0 = time.perf_counter()
    server.replay(questions, offsets)
    poisson_wall = time.perf_counter() - t0
    row = {
        "plan": plan.describe(),
        "topology": "disagg" if disagg else "single",
        "predicted_qps": round(plan.predicted["qps"], 3),
        "predicted_ttft_s": round(plan.predicted["ttft"], 5),
        "search_s": round(search_s, 3),
        "offered_qps": rate_qps,
        "replay_wall_s": round(poisson_wall, 4),
        **{k: (round(v, 5) if isinstance(v, float) else v)
           for k, v in server.summary().items()},
    }
    if disagg:
        if trace_file is not None and not Path(trace_file).exists():
            raise SystemExit(f"--trace file not found: {trace_file}")
        if trace_file is not None:
            before = server.summary()
            t0 = time.perf_counter()
            server.replay_trace(str(trace_file),
                                max_new_tokens=max_new_tokens)
            row["trace"] = {
                "file": Path(trace_file).name,
                "replay_wall_s": round(time.perf_counter() - t0, 4),
                "n_submitted": (server.summary()["n_submitted"]
                                - before["n_submitted"]),
                "n_done": server.summary()["n_done"] - before["n_done"],
                "n_expired": (server.summary()["n_expired"]
                              - before["n_expired"]),
            }
        # per-engine-group tail latency over everything this cluster served
        row["groups"] = server.cluster.group_summary()
        row["cluster"] = server.cluster.describe()
        # page-granular KV handoff accounting, normalized per handoff so
        # --compare can gate shipped bytes independently of request count
        sched = row["groups"]["scheduler"]
        n_handoffs = max(sched.get("handoffs", 0), 1)
        row["handoff"] = {
            "bytes": sched.get("handoff_bytes", 0),
            "bytes_full": sched.get("handoff_bytes_full", 0),
            "pages": sched.get("handoff_pages", 0),
            "pages_shared": sched.get("handoff_pages_shared", 0),
            "bytes_per_handoff": round(
                sched.get("handoff_bytes", 0) / n_handoffs, 1),
        }
    return row


def run_telemetry(corpus, questions, max_new_tokens: int,
                  repeats: int = 3) -> tuple:
    """Measure what the observability layer costs and prove what it
    records: the same closed batch is served alternately with the tracer
    off (``NULL_TRACER``) and on (a fresh :class:`SpanTracer` per
    repeat), on one pre-warmed baseline engine.  ``overhead_frac``
    compares the best wall of each arm (min-of-N rejects scheduler
    noise); ``--compare`` fails the run when it exceeds
    ``max_overhead_frac`` (5%) -- tracing must never become a tax you
    pay to find out why serving got slow.  The last traced repeat is
    checked for span well-formedness, its TTFT/TPOT are re-derived from
    spans and cross-checked against the Request timestamps, and its SLO
    stage attribution (p99-TTFT decomposed into queue/embed/retrieve/
    prefill) rides along.  Returns ``(row, tracer, requests)`` so
    ``--trace-out`` can export the traced run."""
    from repro.configs.rag_pipelines import PRESETS
    from repro.serving.engine import RAGEngine
    from repro.serving.request import Request, State
    from repro.serving.telemetry import (SpanTracer, derive_latencies,
                                         slo_summary, validate_spans)

    schema = PRESETS["baseline"]()
    comps = _components(schema, vocab=128)
    cfg = _engine_config(schema, "exact", s_max=128,
                         max_new_tokens=max_new_tokens)
    engine = RAGEngine(comps["generative"], comps["encoder"], corpus, cfg)
    # warm the jit caches so neither arm pays compile time
    engine.serve([Request(question=q.copy()) for q in questions])

    walls = {"off": [], "on": []}
    tracer, reqs = None, None
    for _ in range(repeats):
        for mode in ("off", "on"):        # alternate: drift hits both arms
            t = SpanTracer() if mode == "on" else None
            engine.set_tracer(t)
            batch = [Request(question=q.copy()) for q in questions]
            t0 = time.perf_counter()
            engine.serve(batch)
            walls[mode].append(time.perf_counter() - t0)
            if mode == "on":
                tracer, reqs = t, batch
    engine.set_tracer(None)
    off, on = min(walls["off"]), min(walls["on"])
    violations = validate_spans(tracer, reqs)

    # spans and Request timestamps are two recordings of the same events;
    # they must agree (the classic failure: a retry resets per-attempt
    # state and one of the two keeps stale times)
    max_err, n_checked = 0.0, 0
    for r in reqs:
        if r.state is not State.DONE or r.ttft is None:
            continue
        d = derive_latencies(tracer, r)
        if d["ttft"] is not None:
            max_err = max(max_err, abs(d["ttft"] - r.ttft))
            n_checked += 1
        if d["tpot"] is not None and len(r.output) > 1:
            tpot = (r.latency - r.ttft) / (len(r.output) - 1)
            max_err = max(max_err, abs(d["tpot"] - tpot))
    row = {
        "preset": "baseline",
        "repeats": repeats,
        "untraced_wall_s": round(off, 4),
        "traced_wall_s": round(on, 4),
        "overhead_frac": round(max(on / off - 1.0, 0.0), 4),
        "max_overhead_frac": 0.05,
        "spans": len(tracer.spans()),
        "dropped_spans": tracer.dropped,
        "spans_well_formed": not violations,
        "violations": violations[:5],
        "latency_crosscheck": {"n": n_checked,
                               "max_err_s": round(max_err, 6)},
        "slo": slo_summary(tracer, reqs),
    }
    return row, tracer, reqs


def run_faulted(corpus, questions, max_new_tokens: int) -> dict:
    """Serve a fixed request set on a 2-prefill + 2-decode cluster while
    the deterministic "combined" chaos schedule fires (transient stage
    error, handoff corruption, retrieval timeouts, a decode-engine crash)
    and report what the robustness layer delivered: goodput (fraction
    DONE), recovery counters, p99 TTFT including recovery delays, and the
    termination invariant (every request terminal, no slot/page leaks).
    The schedule and seed are pinned, so the row is comparable across
    runs and ``--compare`` can gate goodput-under-faults.

    The whole run is traced (:class:`SpanTracer` on the cluster): the
    chaos matrix is exactly where span well-formedness earns its keep --
    every retry, migration, and injected fault must still leave each
    request with one SUBMIT, one TERMINAL, and time-disjoint attempts.
    The verdict lands in the row (gated by ``--compare``) and the trace
    backs ``--trace-out``.  Returns ``(row, tracer, requests)``."""
    from repro.configs.rag_pipelines import PRESETS
    from repro.serving.cluster import RAGCluster, percentiles
    from repro.serving.engine import RAGEngine
    from repro.serving.faults import (CHAOS_SCHEDULES, FaultInjector,
                                      FaultPlan)
    from repro.serving.request import TERMINAL_STATES, State
    from repro.serving.server import RAGServer
    from repro.serving.telemetry import SpanTracer, validate_spans

    schema = PRESETS["baseline"]()
    comps = _components(schema, vocab=128)
    cfg = _engine_config(schema, "exact", s_max=128,
                         max_new_tokens=max_new_tokens)
    first = RAGEngine(comps["generative"], comps["encoder"], corpus,
                      replace(cfg, decode_slots=1))
    shared = dict(db_vectors=first.db_vectors, backend=first.backend)
    prefill = [first, RAGEngine(comps["generative"], comps["encoder"],
                                corpus, replace(cfg, decode_slots=1),
                                **shared)]
    decode = [RAGEngine(comps["generative"], comps["encoder"], corpus, cfg,
                        **shared) for _ in range(2)]
    injector = FaultInjector(
        FaultPlan.from_schedule(CHAOS_SCHEDULES["combined"], seed=0))
    cluster = RAGCluster(prefill, decode, injector=injector,
                         retry_backoff=0.005)
    tracer = SpanTracer()
    cluster.set_tracer(tracer)
    server = RAGServer(cluster)
    t0 = time.perf_counter()
    handles = [server.submit(q.copy()) for q in questions]
    steps = server.run_until_idle(max_steps=50_000)
    wall = time.perf_counter() - t0
    reqs = [h.request for h in handles]
    done = [r for r in reqs if r.state is State.DONE]
    ttfts = [r.ttft for r in done if r.ttft is not None]
    no_leaks = (not cluster.queue and not cluster.handoff
                and not cluster.retrying
                and all(not e.active and not e.pending_retrievals
                        for e in cluster.decode_engines))
    sched = cluster.group_summary()["scheduler"]
    violations = validate_spans(tracer, reqs)
    row = {
        "schedule": "combined",
        "n_requests": len(reqs),
        "n_done": len(done),
        # the headline number: fraction of submitted requests that still
        # completed despite the fault schedule (gated by --compare)
        "goodput": round(len(done) / max(len(reqs), 1), 4),
        "all_terminal": all(r.state in TERMINAL_STATES for r in reqs),
        "no_leaks": no_leaks,
        "steps": steps,
        "wall_s": round(wall, 4),
        "ttft_p99_s": percentiles(ttfts)["p99"],
        "faults_fired": len(injector.log),
        "recovery": {k: sched[k] for k in (
            "engine_failures", "requests_retried", "retries_exhausted",
            "handoff_corrupt", "handoff_dropped", "stage_errors",
            "brownout_shed", "degraded_answers", "retrieval_fallbacks",
            "retrieval_no_context")},
        "health": cluster.group_summary()["health"],
        "telemetry": {
            "spans": len(tracer.spans()),
            "dropped_spans": tracer.dropped,
            "spans_well_formed": not violations,
            "violations": violations[:5],
        },
    }
    return row, tracer, reqs


def run_autoscale(corpus, make_q, max_new_tokens: int) -> dict:
    """Workload-shift benchmark for the live control plane: a minimal
    1-prefill + 1-decode cluster serves a scripted two-phase trace (a
    quiet phase A at ``LOW`` QPS, then a regime shift to phase B at
    ``HIGH`` QPS) with a :class:`~repro.serving.controller.
    ClusterController` attached.  The controller must detect the drift,
    re-plan over *calibrated* specs, and execute a make-before-break
    resize while traffic keeps flowing.

    Three runs back the row's invariants:

    * the **autoscale** run itself -- goodput, dropped count (must be 0:
      a resize may delay a request, never drop one), re-plan / resize
      counts, and p99 TTFT before / during / after the resize;
    * a **static** run of the same trace through an identical unresized
      1+1 cluster -- the autoscale run's greedy outputs must be
      bit-identical to it (migration re-prefills exactly);
    * a **fresh deploy** at the autoscale run's final size serving the
      phase-B suffix from a clean start -- over the *same post-settle
      trace entries* (arrivals after the resize's settle window, when
      the migration backlog has drained), the autoscale run's p99 TTFT
      must be within 2x of the fresh deploy's (the resized cluster
      converges to what a from-scratch deployment of the same size
      delivers; pairing the exact arrival subset keeps the gate free of
      sample-size artifacts).

    All three invariants land in ``BENCH_serving.json["autoscale"]`` and
    are gated by ``--compare`` (dropped > 0 fails unconditionally)."""
    from repro.configs.rag_pipelines import PRESETS
    from repro.core.hardware import SystemConfig, XPU_C
    from repro.core.serving_plan import ServingPlan
    from repro.serving.cluster import RAGCluster, percentiles
    from repro.serving.controller import ClusterController, DriftDetector
    from repro.serving.engine import RAGEngine
    from repro.serving.request import TERMINAL_STATES, Request, State
    from repro.serving.server import RAGServer
    from repro.serving.trace import synthesize_trace

    schema = PRESETS["baseline"]()
    system = SystemConfig(n_servers=4, xpu=XPU_C)
    plan = ServingPlan.optimize(schema, system)
    comps = _components(schema, vocab=128)
    cfg = _engine_config(schema, "exact", s_max=128,
                         max_new_tokens=max_new_tokens)
    seed_eng = RAGEngine(comps["generative"], comps["encoder"], corpus, cfg)
    # every engine across all three runs shares the same database and
    # backend, so retrieval -- and therefore greedy output -- is a pure
    # function of the question (what makes bit-parity checkable)
    shared = dict(db_vectors=seed_eng.db_vectors, backend=seed_eng.backend)

    def make_engine(group: str) -> RAGEngine:
        eng = RAGEngine(comps["generative"], comps["encoder"], corpus,
                        replace(cfg, decode_slots=1) if group == "prefill"
                        else cfg, **shared)
        # warm the jit caches off the serving path so a mid-trace
        # scale-up does not pay compile time inside a request's TTFT
        eng.serve([Request(question=make_q(0, q_len=8).copy(),
                           max_new_tokens=2)])
        return eng

    def build_server(n_p: int, n_d: int) -> RAGServer:
        return RAGServer(RAGCluster(
            [make_engine("prefill") for _ in range(n_p)],
            [make_engine("decode") for _ in range(n_d)],
            retry_backoff=0.005))

    # the scripted regime shift: same 4 popular questions as the preset
    # rows (warm prompt buckets), fixed output length, no deadlines --
    # nothing but the arrival rate changes at the phase boundary
    LOW, HIGH = 1.2, 4.0
    mk = (lambda rng, q_len: make_q(int(rng.integers(0, 4)), q_len=8))
    kw = dict(diurnal_amplitude=0.0, burst_prob=0.0,
              out_median=float(max_new_tokens), out_sigma=0.0,
              out_max=max_new_tokens, presets=("baseline",),
              make_question=mk)
    phase_a = synthesize_trace(8, 128, mean_rate=LOW, seed=11, **kw)
    # phase B runs long enough (~12 s) that the trace outlives the
    # resize + settle window -- the gate needs post-settle arrivals
    phase_b = synthesize_trace(48, 128, mean_rate=HIGH, seed=12,
                               t0=phase_a[-1].arrival_s + 0.2, **kw)
    trace = phase_a + phase_b

    # -- run 1: autoscale (controller attached, in-band) ---------------
    server = build_server(1, 1)
    controller = ClusterController(
        server, schema, system, plan, engine_factory=make_engine,
        window_s=3.0, interval_s=0.3, reference_qps=LOW,
        load_detector=DriftDetector(band=1.5, clear_band=0.5, patience=2),
        tail_detector=DriftDetector(band=2.0, clear_band=0.5, patience=3),
        min_engines=1, max_engines=2, min_window_arrivals=4,
        settle_s=5.0).attach()
    t0 = time.perf_counter()
    handles = server.replay_trace(trace)
    wall = time.perf_counter() - t0
    reqs = [h.request for h in handles]
    outputs = [[int(t) for t in r.output] for r in reqs]
    done = [r for r in reqs if r.state is State.DONE]
    cl = server.cluster
    final_p, final_d = len(cl.prefill_engines), len(cl.decode_engines)
    no_leaks = (not cl.queue and not cl.handoff and not cl.retrying
                and all(not e.active and not e.pending_retrievals
                        for e in cl.decode_engines)
                and all(not e.active and not e.pending_retrievals
                        for _g, _eid, e in cl.retired))

    def p99(rs) -> float | None:
        vals = [r.ttft for r in rs if r.ttft is not None]
        return percentiles(vals)["p99"] if vals else None

    resize_ts = [e["t"] for e in controller.events
                 if e["event"] == "resize"]
    rt = resize_ts[0] if resize_ts else None
    if rt is None:
        phases = {"before": done, "during": [], "after": []}
        gate_idx = []
    else:
        settle_end = rt + controller.settle_s
        tft = (lambda r: r.t_first_token or 0.0)
        phases = {
            "before": [r for r in done if tft(r) < rt],
            "during": [r for r in done if rt <= tft(r) < settle_end],
            "after": [r for r in done if tft(r) >= settle_end],
        }
        # the 2x gate samples requests that arrived after the resize's
        # settle window -- the migration backlog has drained and the
        # resized cluster is at its new steady state
        gate_idx = [i for i, r in enumerate(reqs)
                    if r.t_arrive >= settle_end]

    # -- run 2: static bit-parity (same trace, no controller) ----------
    static = build_server(1, 1)
    s_handles = static.replay_trace(trace)
    s_outputs = [[int(t) for t in h.request.output] for h in s_handles]
    bit_identical = outputs == s_outputs

    # -- run 3: fresh deploy at the final size, phase-B suffix ---------
    b0 = phase_b[0].arrival_s
    suffix = [replace(e, arrival_s=e.arrival_s - b0) for e in phase_b]
    fresh = build_server(final_p, final_d)
    f_handles = fresh.replay_trace(suffix)
    # pair the gate on the SAME trace entries in both runs: identical
    # questions, arrival pattern, and sample count -- the only variable
    # left is whether the resized cluster converged to fresh-deploy
    # behaviour
    n_a = len(phase_a)
    fresh_reqs = [h.request for h in f_handles]
    gate_idx = [i for i in gate_idx if i >= n_a]
    post_p99 = p99([reqs[i] for i in gate_idx
                    if reqs[i].state is State.DONE])
    fresh_p99 = p99([fresh_reqs[i - n_a] for i in gate_idx
                     if fresh_reqs[i - n_a].state is State.DONE])
    ratio = (round(post_p99 / fresh_p99, 3)
             if post_p99 is not None and fresh_p99 else None)

    sched = cl.group_summary()["scheduler"]
    last_replan = next((e for e in reversed(controller.events)
                        if e["event"] == "replan"), None)
    return {
        "trace": {"n": len(trace), "phase_a_qps": LOW,
                  "phase_b_qps": HIGH, "phase_b_at_s": round(b0, 3)},
        "initial": {"prefill": 1, "decode": 1},
        "final": {"prefill": final_p, "decode": final_d},
        "replans": controller.replans,
        "resizes": controller.resizes,
        "n_requests": len(reqs),
        "n_done": len(done),
        # the headline invariant: a resize may delay, never drop
        "dropped": len(reqs) - len(done),
        "goodput": round(len(done) / max(len(reqs), 1), 4),
        "all_terminal": all(r.state in TERMINAL_STATES for r in reqs),
        "no_leaks": no_leaks,
        "bit_identical_vs_static": bit_identical,
        "requests_migrated": sched["requests_migrated"],
        "engines_added": sched["engines_added"],
        "engines_removed": sched["engines_removed"],
        "brownout_shed": sched["brownout_shed"],
        "ttft_p99_s": {k: p99(v) for k, v in phases.items()},
        "p99_gate": {"post_resize_ttft_p99_s": post_p99,
                     "fresh_deploy_ttft_p99_s": fresh_p99,
                     "n_samples": len(gate_idx),
                     "ratio": ratio, "max_ratio": 2.0},
        "calibrated": last_replan["calibrated"] if last_replan else None,
        "calibration": (last_replan["calibration"]
                        if last_replan else None),
        "wall_s": round(wall, 4),
    }


def compare_results(cur: dict, prev: dict, tolerance: float = 0.25) -> list:
    """QPS/TPOT/p99-tail regressions of ``cur`` vs a previous
    BENCH_serving.json.

    For every preset x backend present in BOTH files: QPS must not drop
    more than ``tolerance`` (fractional), TPOT must not grow more than
    ``tolerance``, and the p99 TTFT/TPOT tails and the per-step decode
    wall time (``decode_step_s`` = stage_time_s['decode'] / decode steps)
    must not grow more than ``2 * tolerance`` (doubled: with bench-sized
    request counts the p99 is the max sample and per-step decode time is
    jittery on shared CI, so they get headroom -- but a change that only
    hurts the tail, or a decode-kernel regression hidden behind
    admission-bound QPS, still fails).

    Disaggregated ``optimized`` rows additionally gate the KV handoff:
    shipped bytes per handoff must not grow more than ``tolerance`` vs
    the previous run (skipped when either file predates the page-granular
    handoff accounting).

    The ``telemetry`` row gates the observability layer in the CURRENT
    run unconditionally: tracing overhead must stay under the row's
    ``max_overhead_frac`` (5%) and the traced run's spans must be
    well-formed (every span ended, one SUBMIT / one TERMINAL per
    request, disjoint retry attempts).

    ``faults`` rows (``--faults``) gate robustness: the termination
    invariant (every request terminal, no leaked slots/pages) must hold
    in the CURRENT run unconditionally, goodput under the pinned
    chaos schedule must not drop more than ``tolerance`` vs the previous
    run, and the chaos run's trace must itself be well-formed (the fault
    paths are where span bookkeeping breaks first).

    ``autoscale`` rows (``--autoscale``) gate the live control plane's
    invariants in the CURRENT run unconditionally: zero requests dropped
    during the resize, every request terminal with no leaks, greedy
    outputs bit-identical to the unresized run, at least one calibrated
    re-plan + resize actually happened, and post-resize p99 TTFT within
    the row's ``max_ratio`` (2x) of a fresh deploy at the final size.
    Goodput is additionally gated against the previous run's autoscale
    row with ``tolerance``.  Returns human-readable regression strings
    (empty == pass)."""
    regressions = []
    gates = (("qps", "min", 1.0),
             ("tpot_s", "max", 1.0),
             ("ttft_p99_s", "max", 2.0),
             ("tpot_p99_s", "max", 2.0),
             ("decode_step_s", "max", 2.0))
    for preset, backends in prev.get("presets", {}).items():
        for backend, old in backends.items():
            new = cur.get("presets", {}).get(preset, {}).get(backend)
            if new is None:
                regressions.append(f"{preset}/{backend}: missing from "
                                   f"current run")
                continue
            for key, sense, scale in gates:
                if not old.get(key) or new.get(key) is None:
                    continue
                tol = tolerance * scale
                if sense == "min":
                    bound = old[key] * (1.0 - tol)
                    bad = new[key] < bound
                    rel = "<"
                else:
                    bound = old[key] * (1.0 + tol)
                    bad = new[key] > bound
                    rel = ">"
                if bad:
                    regressions.append(
                        f"{preset}/{backend}: {key} {new[key]} {rel} "
                        f"{bound:.5f} (prev {old[key]}, tol {tol})")
    for preset, old in prev.get("optimized", {}).items():
        new = cur.get("optimized", {}).get(preset)
        if new is None:
            continue                      # topology/preset set may differ
        old_h, new_h = old.get("handoff"), new.get("handoff")
        if not old_h or not new_h:
            continue                      # legacy file without handoff rows
        key = "bytes_per_handoff"
        if not old_h.get(key) or new_h.get(key) is None:
            continue
        bound = old_h[key] * (1.0 + tolerance)
        if new_h[key] > bound:
            regressions.append(
                f"{preset}/optimized: handoff {key} {new_h[key]} > "
                f"{bound:.1f} (prev {old_h[key]}, tol {tolerance})")
    new_t = cur.get("telemetry")
    if new_t is not None:
        cap = new_t.get("max_overhead_frac", 0.05)
        frac = new_t.get("overhead_frac")
        if frac is not None and frac > cap:
            regressions.append(
                f"telemetry: tracing overhead {frac:.2%} exceeds the "
                f"{cap:.0%} cap (untraced {new_t.get('untraced_wall_s')}s "
                f"-> traced {new_t.get('traced_wall_s')}s)")
        if not new_t.get("spans_well_formed", True):
            regressions.append(
                "telemetry: trace violates span well-formedness: "
                + "; ".join((new_t.get("violations")
                             or ["(no detail)"])[:3]))
    new_f = cur.get("faults")
    if new_f is not None:
        if not new_f.get("all_terminal", True):
            regressions.append("faults: termination invariant violated "
                               "(non-terminal request after drain)")
        if not new_f.get("no_leaks", True):
            regressions.append("faults: slot/page leak after drain")
        old_f = prev.get("faults")
        if (old_f and old_f.get("goodput")
                and new_f.get("goodput") is not None
                and old_f.get("schedule") == new_f.get("schedule")):
            bound = old_f["goodput"] * (1.0 - tolerance)
            if new_f["goodput"] < bound:
                regressions.append(
                    f"faults: goodput {new_f['goodput']} < {bound:.4f} "
                    f"(prev {old_f['goodput']}, tol {tolerance})")
        tele = new_f.get("telemetry")
        if tele is not None and not tele.get("spans_well_formed", True):
            regressions.append(
                "faults: chaos-run trace violates span well-formedness: "
                + "; ".join((tele.get("violations")
                             or ["(no detail)"])[:3]))
    new_a = cur.get("autoscale")
    if new_a is not None:
        if new_a.get("dropped", 0):
            regressions.append(
                f"autoscale: {new_a['dropped']} request(s) dropped -- a "
                f"resize may delay a request, never drop one")
        if not new_a.get("all_terminal", True):
            regressions.append("autoscale: termination invariant violated "
                               "(non-terminal request after drain)")
        if not new_a.get("no_leaks", True):
            regressions.append("autoscale: slot/page leak after drain")
        if not new_a.get("bit_identical_vs_static", True):
            regressions.append("autoscale: greedy outputs diverge from the "
                               "unresized run (migration is not exact)")
        if not new_a.get("replans", 0) or not new_a.get("resizes", 0):
            regressions.append(
                f"autoscale: the workload shift produced no re-plan/resize "
                f"(replans={new_a.get('replans', 0)}, "
                f"resizes={new_a.get('resizes', 0)})")
        gate = new_a.get("p99_gate") or {}
        ratio, cap = gate.get("ratio"), gate.get("max_ratio", 2.0)
        if ratio is None or ratio > cap:
            regressions.append(
                f"autoscale: post-resize ttft p99 "
                f"{gate.get('post_resize_ttft_p99_s')}s is {ratio}x a "
                f"fresh deploy at the final size "
                f"({gate.get('fresh_deploy_ttft_p99_s')}s; max {cap}x)")
        old_a = prev.get("autoscale")
        if (old_a and old_a.get("goodput")
                and new_a.get("goodput") is not None):
            bound = old_a["goodput"] * (1.0 - tolerance)
            if new_a["goodput"] < bound:
                regressions.append(
                    f"autoscale: goodput {new_a['goodput']} < {bound:.4f} "
                    f"(prev {old_a['goodput']}, tol {tolerance})")
    return regressions


def _scan_calibration(corpus, questions) -> dict:
    """Measured backend scan throughput -> calibrated analytical host."""
    import jax

    from repro.core.hardware import EPYC_MILAN
    from repro.core.retrieval_model import calibrate_host
    from repro.models import transformer as tr
    from repro.retrieval.backend import (ExactBackend, IVFPQBackend,
                                         measure_scan_bw)
    from repro.serving.engine import Component

    cfg = tr.TransformerConfig(name="cal-enc", n_layers=2, d_model=32,
                               n_heads=2, n_kv_heads=2, d_head=16, d_ff=64,
                               vocab_size=128, causal=False)
    enc = Component(cfg, tr.init_params(jax.random.PRNGKey(1), cfg))
    vecs = np.asarray(tr.encode(enc.params, np.stack([c for c in corpus]),
                                cfg))
    qv = np.asarray(tr.encode(enc.params, np.stack(questions), cfg))
    out = {}
    for backend in (ExactBackend(vecs), IVFPQBackend(vecs)):
        out[f"{backend.name}_scan_bytes_per_s"] = round(
            measure_scan_bw(backend, qv, k=RETRIEVAL_K), 1)
    calibrated = calibrate_host(EPYC_MILAN,
                                out["ivfpq_scan_bytes_per_s"])
    out["calibrated_pq_scan_bw_per_core"] = calibrated.pq_scan_bw_per_core
    return out


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny corpus / few requests / baseline preset only")
    p.add_argument("--out", default="BENCH_serving.json")
    p.add_argument("--presets", default=None,
                   help="comma-separated preset names (default: all)")
    p.add_argument("--backends", default="exact,ivfpq")
    p.add_argument("--attn-impl", default="auto",
                   choices=["auto", "ref", "pallas"],
                   help="decode-attention implementation for the preset "
                        "engines (auto: pallas on TPU, ref elsewhere); "
                        "the resolved impl is recorded per row")
    p.add_argument("--optimize", action="store_true",
                   help="also run schema -> plan -> RAGServer.from_plan "
                        "with open-loop Poisson traffic per preset")
    p.add_argument("--rate", type=float, default=2.0,
                   help="offered Poisson rate (QPS) for --optimize")
    p.add_argument("--topology", default="single",
                   choices=["single", "disagg"],
                   help="--optimize deployment: one collocated engine or "
                        "a disaggregated prefill/decode cluster")
    p.add_argument("--trace", default=str(DEFAULT_TRACE),
                   help="JSONL arrival trace replayed through the cluster "
                        "in --topology disagg (default: the checked-in "
                        "bursty RAGPulse-style trace)")
    p.add_argument("--faults", action="store_true",
                   help="also drive a 2+2 disaggregated cluster through "
                        "the pinned 'combined' chaos schedule and report "
                        "goodput + recovery counters + the termination "
                        "invariant under faults")
    p.add_argument("--autoscale", action="store_true",
                   help="also drive a 1+1 cluster through a scripted "
                        "workload shift with the live ClusterController "
                        "attached (drift -> calibrated re-plan -> "
                        "zero-drop resize) and report the control-plane "
                        "invariants")
    p.add_argument("--trace-out", default=None, metavar="TRACE.json",
                   help="write a Chrome/Perfetto trace of the chaos run "
                        "(--faults) or of the traced telemetry run, plus "
                        "a JSONL span log at TRACE.json.spans.jsonl")
    p.add_argument("--compare", default=None, metavar="PREV.json",
                   help="exit nonzero on QPS/TPOT regression vs a previous "
                        "BENCH_serving.json")
    p.add_argument("--tolerance", type=float, default=0.25,
                   help="fractional QPS/TPOT tolerance for --compare")
    args = p.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.rag_pipelines import PRESETS
    from repro.data.synthetic import topical_corpus

    if args.smoke:
        n_docs, n_requests, max_new = 48, 4, 4
        preset_names = ["baseline"]
    else:
        n_docs, n_requests, max_new = 128, 8, 8
        preset_names = list(PRESETS)
    if args.presets:
        preset_names = [s.strip() for s in args.presets.split(",")]
    backends = [s.strip() for s in args.backends.split(",")]

    corpus, _topics, make_q = topical_corpus(n_docs, 10, 128, n_topics=4)
    # A small pool of popular questions, cycled so repeats exist: repeated
    # questions rebuild identical prefixes, which is what makes the paged
    # pool's prefix sharing (pages_shared) and the cluster's page-deduped
    # handoff (handoff_bytes < handoff_bytes_full) visible in the output.
    popular = [make_q(t, q_len=8) for t in range(4)]
    questions = [popular[i % 4] for i in range(n_requests)]

    results = {"meta": {
        "smoke": bool(args.smoke),
        "jax_backend": jax.default_backend(),
        "corpus": [int(corpus.shape[0]), int(corpus.shape[1])],
        "n_requests": n_requests,
        "retrieval_k": RETRIEVAL_K,
        "calibration": _scan_calibration(corpus, questions),
    }, "presets": {}}

    for name in preset_names:
        schema = PRESETS[name]()
        results["presets"][name] = {}
        for backend in backends:
            t0 = time.perf_counter()
            row = run_preset(name, schema, backend, corpus, questions,
                             max_new, attn_impl=args.attn_impl)
            row["bench_total_s"] = round(time.perf_counter() - t0, 2)
            results["presets"][name][backend] = row
            print(f"{name}/{backend}[{row['attn_impl']}]: qps={row['qps']} "
                  f"ttft={row['ttft_s']}s tpot={row['tpot_s']}s "
                  f"recall@{RETRIEVAL_K}={row['recall_at_k_vs_exact']}",
                  flush=True)

    if args.optimize:
        results["optimized"] = {}
        for name in preset_names:
            row = run_optimized(name, PRESETS[name](), corpus, questions,
                                max_new, args.rate,
                                topology=args.topology,
                                trace_file=args.trace)
            results["optimized"][name] = row
            print(f"{name}/optimized[{row['topology']}]: {row['plan']}\n"
                  f"  open-loop @ {args.rate} QPS offered: "
                  f"served qps={row['qps']} ttft={row['ttft_s']}s "
                  f"p99 {row['ttft_p99_s']}s "
                  f"({row['n_done']}/{row['n_submitted']} done)",
                  flush=True)
            if "groups" in row:
                g = row["groups"]
                print(f"  {row['cluster']}\n"
                      f"  prefill group ttft p50/p99 = "
                      f"{g['prefill']['ttft_s']['p50']}/"
                      f"{g['prefill']['ttft_s']['p99']}s; decode group "
                      f"tpot p50/p99 = {g['decode']['tpot_s']['p50']}/"
                      f"{g['decode']['tpot_s']['p99']}s", flush=True)

    # the observability layer's own row: tracing overhead (gated at 5%),
    # span well-formedness, span-vs-timestamp latency crosscheck, and the
    # p99-TTFT stage decomposition
    row, tele_tracer, _tele_reqs = run_telemetry(corpus, questions, max_new)
    results["telemetry"] = row
    slo = row["slo"]
    print(f"telemetry: overhead={row['overhead_frac'] * 100:.1f}% "
          f"(cap {row['max_overhead_frac'] * 100:.0f}%), "
          f"spans={row['spans']} dropped={row['dropped_spans']} "
          f"well_formed={row['spans_well_formed']}, "
          f"crosscheck max_err={row['latency_crosscheck']['max_err_s']}s\n"
          f"  p99 ttft breakdown: "
          f"{slo.get('ttft_p99_breakdown_s')}", flush=True)
    trace_tracer = tele_tracer

    if args.faults:
        row, trace_tracer, _f_reqs = run_faulted(corpus, questions, max_new)
        results["faults"] = row
        rec = row["recovery"]
        tele = row["telemetry"]
        print(f"faults[{row['schedule']}]: goodput={row['goodput']} "
              f"({row['n_done']}/{row['n_requests']} done), "
              f"all_terminal={row['all_terminal']} "
              f"no_leaks={row['no_leaks']}, fired={row['faults_fired']}, "
              f"retried={rec['requests_retried']} "
              f"failures={rec['engine_failures']} "
              f"degraded={rec['degraded_answers']}, "
              f"spans={tele['spans']} "
              f"well_formed={tele['spans_well_formed']}", flush=True)

    if args.autoscale:
        row = run_autoscale(corpus, make_q, max_new)
        results["autoscale"] = row
        g = row["p99_gate"]
        print(f"autoscale: {row['initial']['prefill']}+"
              f"{row['initial']['decode']} -> {row['final']['prefill']}+"
              f"{row['final']['decode']} engines, "
              f"replans={row['replans']} resizes={row['resizes']}, "
              f"dropped={row['dropped']} "
              f"({row['n_done']}/{row['n_requests']} done), "
              f"migrated={row['requests_migrated']}, "
              f"bit_identical={row['bit_identical_vs_static']}\n"
              f"  ttft p99 before/during/after = "
              f"{row['ttft_p99_s']['before']}/{row['ttft_p99_s']['during']}"
              f"/{row['ttft_p99_s']['after']}s; post-resize vs fresh "
              f"deploy = {g['post_resize_ttft_p99_s']}s vs "
              f"{g['fresh_deploy_ttft_p99_s']}s "
              f"({g['ratio']}x, max {g['max_ratio']}x)", flush=True)

    if args.trace_out:
        from repro.serving.telemetry import export_jsonl, export_perfetto
        doc = export_perfetto(trace_tracer, args.trace_out)
        spans_path = args.trace_out + ".spans.jsonl"
        n_spans = export_jsonl(trace_tracer, spans_path)
        results["meta"]["trace_out"] = {
            "path": args.trace_out,
            "source": "faults" if args.faults else "telemetry",
            "events": len(doc["traceEvents"]),
            "spans": n_spans,
        }
        print(f"wrote {args.trace_out} ({len(doc['traceEvents'])} events; "
              f"load in https://ui.perfetto.dev) and {spans_path} "
              f"({n_spans} spans)")

    Path(args.out).write_text(json.dumps(results, indent=2) + "\n")
    print(f"wrote {args.out}")

    if args.compare:
        prev = json.loads(Path(args.compare).read_text())
        regressions = compare_results(results, prev, args.tolerance)
        if regressions:
            print(f"PERF REGRESSION vs {args.compare}:", file=sys.stderr)
            for r in regressions:
                print(f"  {r}", file=sys.stderr)
            sys.exit(1)
        print(f"no regression vs {args.compare} (tol {args.tolerance})")
    return results


if __name__ == "__main__":
    main()
