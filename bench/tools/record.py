#!/usr/bin/env python3
"""One run of a cell, as ``bench/run.py`` makes it, that also writes what
the run's spread is made of: every request's due time and token times,
every server step's host interval and whether it admitted, and every
pause of Python's garbage collector.

    python3 bench/tools/record.py --workload <cell> --seed <n> \\
        --seconds <s> --out <file.json> [--trace 0|1]

Prints the run's result line, then a summary line: the end-to-end
metrics, the median step, the steps that took more than three times the
median without an admission (the host stood still), and the collector's
pauses in the window.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", required=True)
    p.add_argument("--set", action="append", default=[],
                   help="key=json: a traffic mix value for this run")
    args = p.parse_args(argv)
    from bench import harness
    from repro.serving.server import RAGServer

    steps, pauses, gc_t = [], [], {}

    def gc_cb(phase, info):
        if phase == "start":
            gc_t["t"] = time.monotonic()
        elif "t" in gc_t:
            pauses.append((gc_t.pop("t"), time.monotonic(),
                           info["generation"]))

    gc.callbacks.append(gc_cb)
    step = RAGServer.step

    def timed_step(self):
        t0 = time.monotonic()
        queued = len(self.engine.queue)
        busy = step(self)
        if busy or self.engine.active:
            # admitted in this step: the queue only shrinks inside it
            steps.append((t0, time.monotonic(),
                          queued - len(self.engine.queue)))
        return busy

    RAGServer.step = timed_step
    spec = harness.load_spec(args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        spec.mix[k] = json.loads(v)
    res = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS)
    print(harness.result_line(res), flush=True)
    run = res["run"]
    w0, w1 = run["window"]
    st = np.asarray([s for s in steps if w0 <= s[0] < w1] or
                    [(0.0, 0.0, 0)])
    dur = st[:, 1] - st[:, 0]
    med = float(np.median(dur))
    stalls = [(float(a), float(b - a)) for a, b, n in st
              if n == 0 and b - a > 3 * med]
    gcw = [(a, b - a, g) for a, b, g in pauses if w0 <= a < w1]
    summary = {
        "seed": args.seed, "set": args.set,
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "attempted": res["attempted"], "failed": res["failed"],
        "compiles_in_window": res["compiles_in_window"],
        "steps": len(st), "step_med_ms": 1e3 * med,
        "step_p90_ms": 1e3 * float(np.quantile(dur, 0.9)),
        "admit_steps": int((st[:, 2] > 0).sum()),
        "stalls": len(stalls), "stall_s": sum(d for _, d in stalls),
        "stall_max_s": max((d for _, d in stalls), default=0.0),
        "gc_pauses": len(gcw), "gc_s": sum(d for _, d, _ in gcw),
        "gc_max_s": max((d for _, d, _ in gcw), default=0.0),
        "gc_gen2": sum(1 for *_, g in gcw if g == 2)}
    print(json.dumps(summary), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({
        "summary": summary, "window": [w0, w1],
        "requests": [{k: r[k] for k in ("due", "sent", "times", "n_want",
                                        "ok", "q_len", "prompt_len")}
                     for r in run["all_requests"]],
        "steps": [list(map(float, s)) for s in steps],
        "gc": pauses, "checks": res["checks"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
