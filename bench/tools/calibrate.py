#!/usr/bin/env python3
"""Readings that the limits of the output check are set from, on the chip:
for each seed, serve a short window of the cell's own traffic, then score
what it served (every finished request, the warm-up's too, so that about
as many are compared as in a full run's window) with the float32
reference (the program's reading) and with the control, the same
reference in float8 (the control's reading), each through the run's own
comparison (``harness.check_outputs``) against the cell's limits.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 20] [--control-seeds 1,2,3]

One process: the programs compile once and every seed after the first
finds them.  Prints one JSON line per seed.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=20.0)
    args = p.parse_args(argv)
    from bench import harness
    from bench.traffic import Traffic, prompt_budget
    spec = harness.load_spec(args.workload)
    device = harness.device_info(spec.workload["chips"])
    harness.enable_cache()
    print(json.dumps({"device": device}), flush=True)
    model, mix = spec.model, spec.mix
    control = {int(s) for s in args.control_seeds.split(",") if s}
    ref = harness.reference(spec.bench_dir, model)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        horizon = mix["warmup_s"] + args.seconds + mix["drain_limit_s"]
        traffic = Traffic(mix, spec.cell, seed, model["vocab_size"], horizon)
        data = harness.make_data(model, seed)
        server = harness.build_server(spec, data)
        harness.warm_shapes(server, traffic)
        served = harness.serve_window(server, traffic, mix, args.seconds)
        recs = harness.records(server, served["loop"], traffic, model, mix)
        del server, served
        gc.collect()
        # every finished request, warm-up's too: as many as a full run's
        # window compares
        cd = harness.check_data(spec, data, recs, seed, prompt_budget(mix))
        line = {"seed": seed, "requests": len(recs),
                "failed": sum(1 for r in recs if not r["ok"]),
                "compared": len(cd["questions"]),
                "sampled_tokens": sum(len(s["served"])
                                      for s in cd["sequences"])}
        modes = ["f32"] + (["fp8"] if seed in control else [])
        for mode in modes:
            # the run's own comparison, against the cell's limits
            t1 = time.monotonic()
            checks = harness.check_outputs(spec, data, recs, seed,
                                           prompt_budget(mix), mode=mode)
            line["program" if mode == "f32" else "control"] = {
                "correct": all(c["value"] <= c["limit"]
                               for c in checks.values()),
                **{k: c["value"] for k, c in checks.items()}}
            line["reference_s_" + mode] = time.monotonic() - t1
            # how the per-position gaps spread, beside their widest
            g = ref.position_gaps(cd, mode)
            line["gaps_" + mode] = {
                "positions": int(g.size), "off_top": int((g > 0).sum()),
                "p99": float(np.quantile(g, 0.99)), "max": float(g.max())}
        line["seconds"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)
        del data, cd
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
