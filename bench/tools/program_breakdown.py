#!/usr/bin/env python3
"""Where a traced run's device time went, by the program's own names.

    python3 bench/tools/program_breakdown.py <run.xplane.pb> \\
        [--workload <cell>]

Prints one JSON object: ``bench/program_trace.summary`` of the trace (the
device time of each ``jit_rago_*`` program, each program's operations by
named scope, the idle time by ``rago.*`` span), each program's mean, the
decode step's scopes per step, and how far programs plus idle close the
traced window.  The cell gives the page pool's shape.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace")
    p.add_argument("--workload", default="granite-3-2b.rag-decode")
    args = p.parse_args(argv)
    from bench import harness
    from bench import program_trace as pt
    spec = harness.load_spec(args.workload)
    rows, row = pt.pool_geometry({"model": spec.model, "mix": spec.mix,
                                  "cell_cfg": spec.cell})
    s = pt.reduce_space(pt.read_space(args.trace), rows, row)
    if s is None:
        print(json.dumps(None))
        return 1
    progs = s["programs"]
    n = progs.get("rago_decode", {}).get("n", 0) or 1
    decode = s["by_scope"].get("rago_decode", {})
    total = sum(decode.values()) or 1.0
    closed = (sum(v["ms"] for v in progs.values()) + s["other_ms"]
              + s["idle_ms"])
    s["mean_ms"] = {k: v["ms"] / v["n"] for k, v in progs.items()}
    s["decode_per_step_ms"] = {k: v / n for k, v in decode.items()}
    s["decode_scoped_share"] = 1.0 - decode.get("unscoped", 0.0) / total
    s["kv_pool_per_step_ms"] = s["kv_pool_ms"] / n
    s["idle_per_step_ms"] = s["idle_ms"] / n
    s["closes_window"] = closed / s["window_ms"]
    print(json.dumps(s, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
