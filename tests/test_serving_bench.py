"""The serving benchmark harness itself is CI-covered: ``--smoke`` runs the
baseline preset on a tiny corpus and must emit a well-formed
BENCH_serving.json (QPS/TTFT/TPOT + recall + hot-path metrics), the
``--compare`` regression gate must pass against the run's own output, and
``compare_results`` must catch fabricated regressions."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow        # full engine build + jit in a subprocess

REPO = Path(__file__).resolve().parent.parent


def _bench_module():
    sys.path.insert(0, str(REPO / "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)
    return serving_bench


def test_serving_bench_smoke(tmp_path):
    out = tmp_path / "BENCH_serving.json"
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "serving_bench.py"),
         "--smoke", "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]

    data = json.loads(out.read_text())
    assert data["meta"]["smoke"] is True
    assert data["meta"]["calibration"]["ivfpq_scan_bytes_per_s"] > 0
    presets = data["presets"]
    assert "baseline" in presets
    for backend in ("exact", "ivfpq"):
        row = presets["baseline"][backend]
        assert row["n_done"] == row["n_requests"] > 0
        assert row["qps"] > 0
        assert row["ttft_s"] > 0 and row["tpot_s"] > 0
        assert 0.0 <= row["recall_at_k_vs_exact"] <= 1.0
        # fused decode hot path: <= 1 sync per step
        m = row["metrics"]
        assert m["decode_host_syncs"] <= m["decode_steps"]
    # the approximate backend must stay close to exact on the tiny corpus
    assert presets["baseline"]["ivfpq"]["recall_at_k_vs_exact"] >= 0.8
    # per-stage wall-time accounting rides along in the metrics
    for backend in ("exact", "ivfpq"):
        t = presets["baseline"][backend]["metrics"]["stage_time_s"]
        assert t["prefill"] > 0 and t["decode"] > 0

    # the observability row: tracing overhead measured and under the cap,
    # spans well-formed, span-derived latencies agreeing with timestamps
    tele = data["telemetry"]
    assert tele["overhead_frac"] <= tele["max_overhead_frac"]
    assert tele["spans_well_formed"] is True and tele["violations"] == []
    assert tele["spans"] > 0 and tele["dropped_spans"] == 0
    assert tele["latency_crosscheck"]["n"] > 0
    assert tele["latency_crosscheck"]["max_err_s"] < 0.05
    assert tele["slo"]["ttft_p99_s"] > 0
    assert "queue" in tele["slo"]["ttft_p99_breakdown_s"]

    # the regression gate passes against the run's own output (CLI path,
    # in-process: no second bench subprocess)
    bench = _bench_module()
    assert bench.compare_results(data, data) == []


def test_compare_results_detects_regression():
    bench = _bench_module()
    prev = {"presets": {"baseline": {"exact": {"qps": 4.0, "tpot_s": 0.05}}}}

    ok = {"presets": {"baseline": {"exact": {"qps": 3.8, "tpot_s": 0.055}}}}
    assert bench.compare_results(ok, prev, tolerance=0.25) == []

    slow = {"presets": {"baseline": {"exact": {"qps": 2.0,
                                               "tpot_s": 0.05}}}}
    regs = bench.compare_results(slow, prev, tolerance=0.25)
    assert len(regs) == 1 and "qps" in regs[0]

    laggy = {"presets": {"baseline": {"exact": {"qps": 4.0,
                                                "tpot_s": 0.09}}}}
    regs = bench.compare_results(laggy, prev, tolerance=0.25)
    assert len(regs) == 1 and "tpot" in regs[0]

    missing = {"presets": {}}
    regs = bench.compare_results(missing, prev)
    assert len(regs) == 1 and "missing" in regs[0]


def test_compare_results_gates_p99_tail():
    """A change that keeps the means but blows up the p99 tail fails the
    gate (at 2x tolerance); within-headroom tail noise passes."""
    bench = _bench_module()
    prev = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05,
        "ttft_p99_s": 0.2, "tpot_p99_s": 0.08}}}}

    tail_ok = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05,
        "ttft_p99_s": 0.28, "tpot_p99_s": 0.11}}}}     # < 2x0.25 growth
    assert bench.compare_results(tail_ok, prev, tolerance=0.25) == []

    tail_bad = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05,
        "ttft_p99_s": 0.5, "tpot_p99_s": 0.2}}}}
    regs = bench.compare_results(tail_bad, prev, tolerance=0.25)
    assert len(regs) == 2
    assert any("ttft_p99_s" in r for r in regs)
    assert any("tpot_p99_s" in r for r in regs)

    # old files without percentile fields are not gated on them
    legacy_prev = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05}}}}
    assert bench.compare_results(tail_bad, legacy_prev,
                                 tolerance=0.25) == []


def test_compare_results_gates_decode_step_time():
    """A kernel change that doubles per-step decode wall time fails the
    gate (2x tolerance, like the p99 tails); legacy files without the
    field are not gated on it."""
    bench = _bench_module()
    prev = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05, "decode_step_s": 0.02}}}}

    ok = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05, "decode_step_s": 0.028}}}}
    assert bench.compare_results(ok, prev, tolerance=0.25) == []

    slow = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05, "decode_step_s": 0.05}}}}
    regs = bench.compare_results(slow, prev, tolerance=0.25)
    assert len(regs) == 1 and "decode_step_s" in regs[0]

    legacy_prev = {"presets": {"baseline": {"exact": {
        "qps": 4.0, "tpot_s": 0.05}}}}
    assert bench.compare_results(slow, legacy_prev, tolerance=0.25) == []


def test_compare_results_gates_handoff_bytes():
    """A disaggregated run that starts shipping more KV bytes per handoff
    (e.g. page dedup silently broken) fails the gate; legacy files
    without handoff accounting are not gated on it."""
    bench = _bench_module()
    prev = {"presets": {}, "optimized": {"baseline": {
        "handoff": {"bytes": 100, "bytes_full": 200,
                    "bytes_per_handoff": 100.0}}}}

    ok = {"presets": {}, "optimized": {"baseline": {
        "handoff": {"bytes": 110, "bytes_full": 200,
                    "bytes_per_handoff": 110.0}}}}
    assert bench.compare_results(ok, prev, tolerance=0.25) == []

    fat = {"presets": {}, "optimized": {"baseline": {
        "handoff": {"bytes": 200, "bytes_full": 200,
                    "bytes_per_handoff": 200.0}}}}
    regs = bench.compare_results(fat, prev, tolerance=0.25)
    assert len(regs) == 1 and "bytes_per_handoff" in regs[0]

    legacy = {"presets": {}, "optimized": {"baseline": {}}}
    assert bench.compare_results(fat, legacy, tolerance=0.25) == []
    assert bench.compare_results(legacy, prev, tolerance=0.25) == []


def test_compare_cli_exits_nonzero_on_regression(tmp_path):
    """--compare is the slow-tier perf gate: against a fabricated faster
    'previous' run the CLI must exit nonzero (smallest possible bench:
    one preset, one backend)."""
    prev = {"presets": {"baseline": {"exact": {"qps": 1e9,
                                               "tpot_s": 1e-9}}}}
    prev_file = tmp_path / "prev.json"
    prev_file.write_text(json.dumps(prev))
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "serving_bench.py"),
         "--smoke", "--backends", "exact",
         "--out", str(tmp_path / "out.json"),
         "--compare", str(prev_file)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=540)
    assert res.returncode != 0
    assert "PERF REGRESSION" in res.stderr


def test_serving_bench_faults_smoke(tmp_path):
    """--faults drives the pinned chaos schedule through a 2+2 cluster and
    must report the termination invariant intact with nonzero recovery
    activity; with --trace-out the whole chaos run exports as a valid
    Perfetto trace plus a JSONL span log."""
    out = tmp_path / "BENCH_serving.json"
    trace = tmp_path / "trace.json"
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "serving_bench.py"),
         "--smoke", "--backends", "exact", "--faults", "--out", str(out),
         "--trace-out", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]
    data = json.loads(out.read_text())
    row = data["faults"]
    assert row["schedule"] == "combined"
    assert row["all_terminal"] is True and row["no_leaks"] is True
    assert row["faults_fired"] > 0
    assert 0.0 <= row["goodput"] <= 1.0
    assert (row["n_done"] + 0) <= row["n_requests"]
    rec = row["recovery"]
    assert rec["requests_retried"] > 0      # the schedule forced recovery
    # the chaos run's own trace is well-formed (gated by --compare too)
    assert row["telemetry"]["spans_well_formed"] is True
    assert row["telemetry"]["spans"] > 0

    # the trace artifact: valid Perfetto JSON with engine + request
    # tracks, faults visible as instants, and a span log beside it
    doc = json.loads(trace.read_text())
    meta = data["meta"]["trace_out"]
    assert meta["source"] == "faults"
    assert meta["events"] == len(doc["traceEvents"]) > 0
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"prefill0", "prefill1", "decode0", "decode1",
            "cluster"} <= tracks
    assert any(t.startswith("req ") for t in tracks)
    instants = {e["name"] for e in doc["traceEvents"] if e["ph"] == "i"}
    assert any(n.startswith("FAULT:") for n in instants)
    spans_log = trace.with_name(trace.name + ".spans.jsonl")
    rows = [json.loads(line) for line in
            spans_log.read_text().splitlines()]
    assert len(rows) == meta["spans"] > 0
    assert {"kind", "t0", "t1", "rid"} <= set(rows[0])

    # the gate passes against the run's own output
    bench = _bench_module()
    assert bench.compare_results(data, data) == []


def test_serving_bench_autoscale_smoke(tmp_path):
    """--autoscale drives the scripted workload shift through a 1+1
    cluster with the live controller attached: it must re-plan off
    measured calibration, resize without dropping a request, keep greedy
    outputs bit-identical to the unresized run, and land post-resize p99
    TTFT within 2x of a fresh deploy at the final size."""
    out = tmp_path / "BENCH_serving.json"
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "serving_bench.py"),
         "--smoke", "--backends", "exact", "--autoscale",
         "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]
    row = json.loads(out.read_text())["autoscale"]
    # the shift was detected and acted on, with calibration applied
    assert row["replans"] >= 1 and row["resizes"] >= 1
    assert row["engines_added"] >= 1
    assert row["final"]["decode"] > row["initial"]["decode"]
    assert any(row["calibrated"].values())
    assert row["calibration"]           # plan.detail["calibration"]
    # the zero-drop invariant: a resize delays, never drops
    assert row["dropped"] == 0 and row["n_done"] == row["n_requests"]
    assert row["goodput"] == 1.0
    assert row["all_terminal"] is True and row["no_leaks"] is True
    # migration is exact: outputs match the unresized run bit for bit
    assert row["bit_identical_vs_static"] is True
    # post-settle p99 within 2x of the fresh deploy, on paired samples
    gate = row["p99_gate"]
    assert gate["n_samples"] > 0
    assert gate["ratio"] is not None and gate["ratio"] <= gate["max_ratio"]
    # the gate passes against the run's own output
    bench = _bench_module()
    data = json.loads(out.read_text())
    assert bench.compare_results(data, data) == []


def test_compare_results_gates_autoscale():
    """Control-plane regressions fail the gate unconditionally: a dropped
    request, broken bit-parity, a shift that produced no re-plan/resize,
    or a blown post-resize p99 ratio; goodput is tolerance-gated vs the
    previous run, and legacy files without the row are not gated."""
    bench = _bench_module()
    good = {"presets": {}, "autoscale": {
        "dropped": 0, "all_terminal": True, "no_leaks": True,
        "bit_identical_vs_static": True, "replans": 1, "resizes": 1,
        "goodput": 1.0,
        "p99_gate": {"ratio": 1.4, "max_ratio": 2.0}}}
    assert bench.compare_results(good, good, tolerance=0.25) == []
    assert bench.compare_results(good, {"presets": {}}) == []

    def broke(**kw):
        row = {**good["autoscale"], **kw}
        return {"presets": {}, "autoscale": row}

    regs = bench.compare_results(broke(dropped=2), good)
    assert len(regs) == 1 and "dropped" in regs[0]

    regs = bench.compare_results(broke(bit_identical_vs_static=False),
                                 good)
    assert len(regs) == 1 and "diverge" in regs[0]

    regs = bench.compare_results(broke(replans=0, resizes=0), good)
    assert len(regs) == 1 and "no re-plan" in regs[0]

    regs = bench.compare_results(
        broke(p99_gate={"ratio": 3.1, "max_ratio": 2.0}), good)
    assert len(regs) == 1 and "fresh deploy" in regs[0]
    # a row with no measurable gate (no post-settle samples) also fails
    regs = bench.compare_results(
        broke(p99_gate={"ratio": None, "max_ratio": 2.0}), good)
    assert len(regs) == 1

    regs = bench.compare_results(broke(all_terminal=False,
                                       no_leaks=False), good)
    assert len(regs) == 2

    regs = bench.compare_results(broke(goodput=0.5), good,
                                 tolerance=0.25)
    assert len(regs) == 1 and "goodput" in regs[0]
    # legacy current file without the row: nothing to gate
    assert bench.compare_results({"presets": {}}, good) == []


def test_compare_results_gates_telemetry():
    """Observability regressions fail the gate in the CURRENT run
    unconditionally: tracing overhead past the row's cap, or a traced run
    whose spans are not well-formed (in the overhead run or under
    faults); legacy files without the rows are not gated."""
    bench = _bench_module()
    good = {"presets": {}, "telemetry": {
        "overhead_frac": 0.01, "max_overhead_frac": 0.05,
        "spans_well_formed": True, "violations": []}}
    assert bench.compare_results(good, good, tolerance=0.25) == []
    assert bench.compare_results(good, {"presets": {}}) == []

    heavy = {"presets": {}, "telemetry": {
        "overhead_frac": 0.11, "max_overhead_frac": 0.05,
        "spans_well_formed": True, "violations": []}}
    regs = bench.compare_results(heavy, good)
    assert len(regs) == 1 and "overhead" in regs[0]

    torn = {"presets": {}, "telemetry": {
        "overhead_frac": 0.01, "max_overhead_frac": 0.05,
        "spans_well_formed": False,
        "violations": ["rid 3: open spans after terminal"]}}
    regs = bench.compare_results(torn, good)
    assert len(regs) == 1 and "well-formed" in regs[0]
    assert "rid 3" in regs[0]

    # the chaos run's trace is gated through the faults row
    chaos_torn = {"presets": {}, "faults": {
        "schedule": "combined", "goodput": 1.0,
        "all_terminal": True, "no_leaks": True,
        "telemetry": {"spans_well_formed": False, "violations": []}}}
    regs = bench.compare_results(chaos_torn, {"presets": {}})
    assert len(regs) == 1 and "well-formed" in regs[0]

    # legacy current files without the rows: nothing to gate
    assert bench.compare_results({"presets": {}}, good) == []


def test_compare_results_gates_goodput_under_faults():
    """Robustness regressions fail the gate: goodput under the pinned
    chaos schedule dropping past tolerance, or the termination invariant
    breaking in the CURRENT run (gated even without a previous row)."""
    bench = _bench_module()
    prev = {"presets": {}, "faults": {
        "schedule": "combined", "goodput": 1.0,
        "all_terminal": True, "no_leaks": True}}

    ok = {"presets": {}, "faults": {
        "schedule": "combined", "goodput": 0.9,
        "all_terminal": True, "no_leaks": True}}
    assert bench.compare_results(ok, prev, tolerance=0.25) == []

    lossy = {"presets": {}, "faults": {
        "schedule": "combined", "goodput": 0.5,
        "all_terminal": True, "no_leaks": True}}
    regs = bench.compare_results(lossy, prev, tolerance=0.25)
    assert len(regs) == 1 and "goodput" in regs[0]

    broken = {"presets": {}, "faults": {
        "schedule": "combined", "goodput": 1.0,
        "all_terminal": False, "no_leaks": False}}
    regs = bench.compare_results(broken, prev, tolerance=0.25)
    assert len(regs) == 2
    assert any("termination invariant" in r for r in regs)
    assert any("leak" in r for r in regs)
    # invariant is gated even without a previous faults row
    regs = bench.compare_results(broken, {"presets": {}}, tolerance=0.25)
    assert len(regs) == 2

    # schedule changed -> goodput not comparable, invariant still gated
    other = {"presets": {}, "faults": {
        "schedule": "prefill_crash", "goodput": 0.1,
        "all_terminal": True, "no_leaks": True}}
    assert bench.compare_results(other, prev, tolerance=0.25) == []

    # legacy files without a faults row are not gated
    assert bench.compare_results({"presets": {}}, prev) == []
