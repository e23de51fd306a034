"""The benchmark's harness: one cell, one seed, one run.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything that belongs to one of them
sits in a file of its own, found by name:

  bench/configs/<config>.json    sizes as run, the source, the reference's
                                 name
  bench/references/<ref>.py      the plain float32 reference
  bench/traffic/<mix>.json       shape of the traffic
  bench/cells/<cell>.json        what the deployment fixes (slots, rate,
                                 the PQ scan's path) and the limits of
                                 the output check
  bench/metrics/<metric>.py      one reader per metric: ``read(run)``

A run serves the cell's traffic through ``RAGServer`` over one collocated
``RAGEngine`` (IVF-PQ retrieval, bucketed prefill, paged fused decode with
the Pallas kernel), in four phases:

  set-up   weights, index and passages from the seed; one request per
           question length, in turn, so every program the traffic uses is
           compiled (or loaded from the persistent cache); then the
           cell's own traffic for ``warmup_s``, so the window opens on a
           loaded system
  window   ``--seconds`` of the same traffic; with ``--trace 1`` the
           last ``trace_s`` of it under the profiler, and spans on
  drain    open loop: arrivals go on until every request that arrived in
           the window has finished or ``drain_limit_s`` has passed;
           closed loop: no new requests, the ones in flight finish
  check    the program's state is freed, and the reference scores a
           sample of what the window served
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# The cell, from its files
# ---------------------------------------------------------------------------

@dataclass
class Spec:
    workload: dict
    model: dict
    mix: dict
    cell: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path

    @property
    def name(self) -> str:
        return self.workload["name"]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_spec(workload: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Spec:
    spec = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    bench_dir = Path(bench_dir)
    return Spec(
        workload=w,
        model=json.loads((Path(root) / conf["file"]).read_text()),
        mix=json.loads((bench_dir / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        cell=json.loads((bench_dir / "cells" / f"{workload}.json")
                        .read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir)


def _load_module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(bench_dir: Path, metric: str):
    """``read(run) -> float | None`` of ``bench/metrics/<metric>.py``."""
    path = Path(bench_dir) / "metrics" / f"{metric}.py"
    return _load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def reference(bench_dir: Path, model: dict):
    path = Path(bench_dir) / "references" / f"{model['reference']}.py"
    return _load_module(path, "bench_reference_" + model["reference"])


# ---------------------------------------------------------------------------
# Devices, compilation cache, compile clock
# ---------------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of the cache's key).  Every program is cached, however short its
    compile."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileClock:
    """Backend compiles (count, seconds, when) and persistent-cache hits."""

    def __init__(self):
        import jax
        self.times: list[tuple[float, float]] = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append((time.monotonic(), duration))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.times if t0 <= t < t1)

    @property
    def seconds(self) -> float:
        return sum(d for _, d in self.times)


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# The system under test, built from the seed
# ---------------------------------------------------------------------------

def _transformer_config(tr, model: dict, name: str, causal: bool = True):
    return tr.TransformerConfig(
        name=name, n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], d_head=model["head_dim"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        rotary_frac=float(model["partial_rotary_factor"]),
        norm_eps=float(model["rms_norm_eps"]), causal=causal)


def encoder_model(model: dict) -> dict:
    return dict(model["encoder"], vocab_size=model["vocab_size"])


def make_data(model: dict, seed: int) -> dict:
    """The run's data, all from the seed: bf16 weights of the generator
    and the encoder, the IVF-PQ shard, the passage table."""
    import jax
    from bench import model as bm
    ix = model["index"]
    # passages first: their device copy is gone before the weights exist
    passages = bm.make_passages(bm.key_of(seed, 4),
                                ix["n_lists"] * ix["list_len"],
                                model["passage_tokens"], model["vocab_size"])
    params = bm.make_params(bm.key_of(seed, 1), bm.arch_of(model))
    enc = bm.make_params(bm.key_of(seed, 2), bm.arch_of(encoder_model(model)),
                         with_head=False)
    index = bm.make_index_arrays(
        bm.key_of(seed, 3), ix["n_lists"], ix["list_len"], ix["dim"],
        ix["n_subq"], float(ix["centroid_norm"]), float(ix["residual_norm"]))
    jax.block_until_ready((params, enc, index))
    return {"params": params, "enc_params": enc, "index": index,
            "passages": passages}


def build_server(spec: Spec, data: dict, **engine_overrides):
    """``RAGServer`` over one collocated ``RAGEngine`` at the cell's
    deployment: the baseline (Case I) pipeline, IVF-PQ retrieval over the
    prebuilt shard, no exact fallback (the shard's vectors are never held
    uncompressed), the paged pool and the fused decode step."""
    import jax
    from repro.configs import rag_pipelines
    from repro.models import transformer as tr
    from repro.retrieval.backend import IVFPQBackend
    from repro.retrieval.ivf_pq import IVFPQIndex
    from repro.serving.engine import Component, EngineConfig, RAGEngine
    from repro.serving.server import RAGServer
    from bench.traffic import max_output

    model, mix, ix = spec.model, spec.mix, spec.model["index"]
    gen = Component(_transformer_config(tr, model, model["name"]),
                    data["params"])
    enc = Component(_transformer_config(tr, encoder_model(model),
                                        model["name"] + "-encoder",
                                        causal=False), data["enc_params"])
    # The backend class builds its index by k-means at construction; the
    # shard here is made directly, so the backend is assembled around it.
    backend = IVFPQBackend.__new__(IVFPQBackend)
    backend.index = IVFPQIndex(n_vectors=ix["n_lists"] * ix["list_len"],
                               **data["index"])
    backend.nprobe = ix["nprobe"]
    # the program's own choice (the Pallas scan on TPU) unless the cell
    # or a test says otherwise
    use_kernel = engine_overrides.pop(
        "use_pq_kernel", spec.cell.get("pq_scan_kernel"))
    backend.use_kernel = (jax.default_backend() == "tpu"
                          if use_kernel is None else bool(use_kernel))
    cfg = EngineConfig.from_schema(
        rag_pipelines.baseline(), decode_slots=spec.cell["decode_slots"],
        s_max=mix["s_max"], retrieval_k=mix["passages_per_request"],
        max_new_tokens=max_output(mix), retrieval_backend="ivfpq",
        nprobe=ix["nprobe"], retrieval_fallback=False,
        kv_spare_pages=mix["kv_spare_pages"],
        **{"attn_impl": "auto", **engine_overrides})
    engine = RAGEngine(gen, enc, data["passages"], cfg,
                       db_vectors=np.zeros((0, ix["dim"]), np.float32),
                       backend=backend)
    return RAGServer(engine)


# ---------------------------------------------------------------------------
# Driving the traffic
# ---------------------------------------------------------------------------

@dataclass
class Rec:
    """One request as the client saw it (host clock, seconds)."""
    i: int
    due: float
    q_len: int
    n_want: int
    handle: object = None
    sent: float = 0.0
    times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)

    def on_token(self, _handle, tok) -> None:
        self.times.append(time.monotonic())
        self.tokens.append(int(tok))

    @property
    def ok(self) -> bool:
        from repro.serving.request import State
        return (self.handle is not None
                and self.handle.state is State.DONE
                and len(self.tokens) == self.n_want)

    @property
    def finished(self) -> bool:
        return self.handle is not None and self.handle.done


class Loop:
    """Feeds one server with a traffic's requests and steps it."""

    def __init__(self, server, traffic, trace: bool):
        self.server = server
        self.traffic = traffic
        self.recs: list[Rec] = []
        self.next_i = 0
        self.trace = trace

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, due: float) -> Rec:
        i = self.next_i
        self.next_i += 1
        it = self.traffic.item(i)
        rec = Rec(i, due, it.q_len, it.n_out, sent=time.monotonic())
        rec.handle = self.server.submit(
            self.traffic.question(i), max_new_tokens=it.n_out,
            arrival_time=due, on_token=rec.on_token)
        self.recs.append(rec)
        return rec


def warm_shapes(server, traffic) -> None:
    """One short request per question length, one after another: compiles
    (or loads) the encoder at each length, the search at batch 1, the
    prefill bucket, the prompt-length programs of the page install and
    the decode step."""
    seen = set()
    for i in range(len(traffic)):
        q = traffic.item(i).q_len
        if q in seen:
            continue
        seen.add(q)
        h = server.submit(traffic.question(i), max_new_tokens=2)
        server.run_until_idle()
        if not h.done:
            raise RuntimeError(f"warm-up request {h.rid} did not finish")
        if seen == set(traffic.mix["question_tokens"]):
            break


def drive(loop: Loop, t_start: float, t_w0: float, t_w1: float,
          drain_limit: float, on_window=None, trace_s: float = 0.0,
          tracer_dir: str | None = None) -> dict:
    """Run the traffic from ``t_start``; the window is [t_w0, t_w1).
    Returns the host times at which the window's counters were read and
    the traced interval."""
    import jax
    server, traffic = loop.server, loop.traffic
    marks = {}
    offsets = t_start + traffic.offsets() if traffic.open else None
    clients = int(traffic.mix.get("clients", 0))
    live: list[Rec] = []
    tracing = False
    while True:
        with loop.span("bench.loop"):
            now = time.monotonic()
            if "w0" not in marks and now >= t_w0:
                marks["w0"] = now
                if on_window:
                    on_window("w0")
            if (tracer_dir is not None and "tr0" not in marks
                    and now >= t_w1 - trace_s):
                jax.profiler.start_trace(tracer_dir)
                tracing = True
                marks["tr0"] = time.monotonic()
            if "w1" not in marks and now >= t_w1:
                marks["w1"] = now
                if on_window:
                    on_window("w1")
                if tracing:
                    # the profiler's export holds the loop for seconds:
                    # after the window, not inside it
                    marks["tr1"] = time.monotonic()
                    jax.profiler.stop_trace()
                    tracing = False
            in_drain = now >= t_w1
            if in_drain:
                window = [r for r in loop.recs if t_w0 <= r.due < t_w1]
                if all(r.finished for r in window):
                    break
                if now >= t_w1 + drain_limit:
                    break
            with loop.span("bench.submit"):
                if traffic.open:
                    while (loop.next_i < len(offsets)
                           and offsets[loop.next_i] <= now):
                        loop.submit(float(offsets[loop.next_i]))
                elif not in_drain:
                    live = [r for r in live if not r.finished]
                    while len(live) < clients:
                        live.append(loop.submit(time.monotonic()))
            with loop.span("bench.step"):
                busy = server.step()
            if not busy:
                with loop.span("bench.wait"):
                    nxt = (offsets[loop.next_i] if traffic.open
                           and loop.next_i < len(offsets) else now + 0.005)
                    time.sleep(max(0.0, min(nxt - time.monotonic(), 0.005)))
    marks["end"] = time.monotonic()
    return marks


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

COUNTERS = ("prefills", "decode_host_syncs", "retrieved_queries",
            "decode_steps", "pages_shared")


def _counters(engine) -> dict:
    snap = engine.metrics_snapshot()
    out = {k: snap.get(k, 0) for k in COUNTERS}
    out["stage_time_s"] = dict(snap["stage_time_s"])
    return out


def _delta(a: dict, b: dict) -> dict:
    out = {k: b[k] - a[k] for k in COUNTERS}
    out["stage_time_s"] = {k: v - a["stage_time_s"].get(k, 0.0)
                           for k, v in b["stage_time_s"].items()}
    return out


def prompt_of(passages, ids, question, budget: int) -> np.ndarray:
    """The prompt the engine is documented to assemble: the retrieved
    passages in rank order, then the question, cut from the left to the
    prompt budget."""
    docs = np.asarray(passages[np.asarray(ids, np.int64)]).reshape(-1)
    full = np.concatenate([docs.astype(np.int32),
                           np.asarray(question, np.int32)])
    return full[-budget:]


def serve_window(server, traffic, mix: dict, seconds: float, *,
                 trace_dir: str | None = None, warmup_s: float | None = None,
                 drain_limit_s: float | None = None) -> dict:
    """Warm-up traffic, the window, the drain (see the module's doc)."""
    engine = server.engine
    loop = Loop(server, traffic, trace_dir is not None)
    snaps = {}

    def on_window(w):
        snaps[w] = _counters(engine)
        snaps[w + "_queued"] = len(engine.queue)

    t_start = time.monotonic()
    t_w0 = t_start + (mix["warmup_s"] if warmup_s is None else warmup_s)
    t_w1 = t_w0 + seconds
    marks = drive(loop, t_start, t_w0, t_w1,
                  (mix["drain_limit_s"] if drain_limit_s is None
                   else drain_limit_s), on_window=on_window,
                  trace_s=min(mix["trace_s"], seconds), tracer_dir=trace_dir)
    return {"loop": loop, "marks": marks, "snaps": snaps, "t_w0": t_w0,
            "t_w1": t_w1}


def records(server, loop: Loop, traffic, model: dict, mix: dict) -> list:
    """Every request of the run as plain data, then nothing left in
    flight: requests not finished are aborted, which frees their slots."""
    from bench.traffic import prompt_budget
    admits = {}
    if server.tracer.enabled:
        for s in server.tracer.spans():
            if s.kind == "ADMIT" and s.rid is not None:
                admits.setdefault(s.rid, s.t0)
    budget = prompt_budget(mix)
    recs = []
    for r in loop.recs:
        req = r.handle.request
        ids = req.retrieved_ids[0] if req.retrieved_ids else []
        recs.append({
            "due": r.due, "sent": r.sent, "times": r.times,
            "tokens": r.tokens,
            "n_want": r.n_want, "q_len": r.q_len, "ok": r.ok,
            "ids": list(ids), "question": traffic.question(r.i),
            "prompt_len": min(len(ids) * model["passage_tokens"] + r.q_len,
                              budget),
            "admit": admits.get(req.rid)})
    for r in loop.recs:
        if not r.finished:
            server.engine.abort_request(r.handle.request,
                                        "benchmark run over")
    return recs


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None, require_tpu: bool = True,
             cache: bool = True, engine_overrides: dict | None = None,
             log=None) -> dict:
    """One run of one cell: the result line's fields, and the ``run``
    record that metric readers read (under ``"run"``)."""
    t_process = time.monotonic() if t_process is None else t_process
    log = log or (lambda **kw: print(json.dumps(kw), file=sys.stderr,
                                     flush=True))
    device = device_info(spec.workload["chips"], require_tpu)
    import jax
    cache_dir = enable_cache() if cache else None
    clock = CompileClock()
    from bench.traffic import Traffic, prompt_budget
    from bench import trace_reduce as trd

    mix, model = spec.mix, spec.model
    horizon = mix["warmup_s"] + seconds + mix["drain_limit_s"]
    traffic = Traffic(mix, spec.cell, seed, model["vocab_size"], horizon)
    data = make_data(model, seed)
    server = build_server(spec, data, **(engine_overrides or {}))
    engine = server.engine
    warm_shapes(server, traffic)
    t_warm = time.monotonic()
    log(phase="compiled", cache_dir=cache_dir, compiles=len(clock.times),
        compile_s=clock.seconds, cache_hits=clock.cache_hits,
        attn_impl=engine.attn_impl,
        pq_kernel=bool(engine.backend.use_kernel),
        since_start_s=t_warm - t_process)

    if trace:
        from repro.serving.telemetry import SpanTracer
        server.set_tracer(SpanTracer())
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    served = serve_window(server, traffic, mix, seconds, trace_dir=trace_dir)
    marks, t_w0, t_w1 = served["marks"], served["t_w0"], served["t_w1"]
    setup_s = t_w0 - t_process
    in_window = clock.between(marks["w0"], marks["end"])
    device["memory_peak_bytes"] = memory_peak(
        jax.devices()[:spec.workload["chips"]])
    budget = prompt_budget(mix)
    recs = records(server, served["loop"], traffic, model, mix)
    # the program's state goes before the check
    del server, engine, served["loop"]
    gc.collect()

    window = [r for r in recs if t_w0 <= r["due"] < t_w1]
    run = {"cell": spec.name, "model": model, "mix": mix, "cell_cfg":
           spec.cell, "seconds": seconds, "window": [t_w0, t_w1],
           "setup_s": setup_s, "requests": window, "all_requests": recs,
           "counters": _delta(served["snaps"]["w0"], served["snaps"]["w1"]),
           "peak": trd.peaks(device["kind"]) if require_tpu else
           {"flops": 1.0, "hbm_bytes_per_s": 1.0}, "trace": None}
    result = {"correct": False, "attempted": len(window),
              "failed": sum(1 for r in window if not r["ok"])}
    breakdown = None
    if trace:
        tr_path = trd.find_trace(trace_dir)
        # the trace stays under TMPDIR, for reading by hand
        log(phase="trace", path=str(tr_path))
        red = trd.reduce_trace(trd.load(tr_path))
        run["trace"] = {"t0": marks["tr0"], "t1": marks["tr1"],
                        "window_s": marks["tr1"] - marks["tr0"], **red}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}

    checks = check_outputs(spec, data, window, seed, budget)
    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        v = reader(spec.bench_dir, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["correct"] = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    late = [r["sent"] - r["due"] for r in window]
    log(phase="window", requests=len(window), all_requests=len(recs),
        generator_late_max_s=max(late, default=0.0),
        compiles_in_window=in_window, setup_s=setup_s,
        window_s=marks["w1"] - marks["w0"],
        drain_s=marks["end"] - marks["w1"],
        counters=run["counters"])
    result.update({"metrics": metrics, "device": device})
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compiles_in_window"] = in_window
    result["checks"] = checks
    result["run"] = run
    return result


def sample_for_check(window: list, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` more drawn from the
    seed."""
    done = [r for r in window if r["ok"]]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, 17])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def check_data(spec: Spec, data: dict, window: list, seed: int,
               budget: int) -> dict | None:
    """What the reference compares: every finished window request's
    question and retrieved ids, and a sample's prompt and served tokens."""
    done = [r for r in window if r["ok"]]
    sample = sample_for_check(window, seed, spec.mix["check_requests"])
    if not done:
        return None
    return {
        "model": spec.model, "params": data["params"],
        "enc_params": data["enc_params"], "index": data["index"],
        "k": spec.mix["passages_per_request"],
        "seq_len": int(spec.mix["s_max"]),
        "questions": [r["question"] for r in done],
        "retrieved": [r["ids"] for r in done],
        "sequences": [{"prompt": prompt_of(data["passages"], r["ids"],
                                           r["question"], budget),
                       "served": np.asarray(r["tokens"], np.int32)}
                      for r in sample]}


def check_outputs(spec, data, window, seed, budget, mode="f32"):
    cd = check_data(spec, data, window, seed, budget)
    if cd is None:
        return {}
    numbers = reference(spec.bench_dir, spec.model).compare(cd, mode)
    limits = spec.cell["limits"]
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def result_line(result: dict) -> str:
    """The last line of standard output: the driver's keys, the count of
    programs compiled inside the window (0 when set-up warmed every
    shape), and the compared numbers last."""
    keys = ["correct", "attempted", "failed", "metrics", "device",
            "breakdown", "compiles_in_window", "checks"]
    return json.dumps({k: result[k] for k in keys if k in result})
