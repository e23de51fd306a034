#!/usr/bin/env python3
"""Knee sweep of an open-loop cell on the chip: one process, one build,
one offered rate after another on the same server.

    python3 bench/tools/sweep.py --workload <cell> --seed <n> \\
        --rates 2,3,4,5 [--seconds 15] [--warmup 8] [--drain 20]

Prints one JSON line per rate: offered and completed requests per second
over the window, the queue at the window's end, the time the window's
requests took to drain, and TTFT/TPOT percentiles.  The knee is the
highest rate whose window's requests all complete with no queue left
growing; a cell's rate is set at four fifths of it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--warmup", type=float, default=8.0)
    p.add_argument("--drain", type=float, default=20.0)
    args = p.parse_args(argv)
    from bench import harness
    from bench.stats import nearest_rank, tpot, ttft
    from bench.traffic import Traffic
    spec = harness.load_spec(args.workload)
    device = harness.device_info(spec.workload["chips"])
    harness.enable_cache()
    print(json.dumps({"device": device}), flush=True)
    model, mix = spec.model, spec.mix
    horizon = args.warmup + args.seconds + args.drain
    data = harness.make_data(model, args.seed)
    server = harness.build_server(spec, data)
    harness.warm_shapes(server, Traffic(mix, spec.cell, args.seed,
                                        model["vocab_size"], horizon))
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = dict(spec.cell, rate_per_s=rate)
        traffic = Traffic(mix, cell, args.seed + n + 1, model["vocab_size"],
                          horizon)
        served = harness.serve_window(server, traffic, mix, args.seconds,
                                      warmup_s=args.warmup,
                                      drain_limit_s=args.drain)
        recs = harness.records(server, served["loop"], traffic, model, mix)
        t0, t1 = served["t_w0"], served["t_w1"]
        win = [r for r in recs if t0 <= r["due"] < t1]
        done_in = [r for r in recs if r["ok"] and t0 <= r["times"][-1] < t1]
        tt = [ttft(r) for r in win]
        tp = [tpot(r) for r in win]
        m = served["marks"]
        print(json.dumps({
            "rate": rate, "window_requests": len(win),
            "failed": sum(1 for r in win if not r["ok"]),
            "offered_per_s": len(win) / args.seconds,
            "completed_per_s": len(done_in) / args.seconds,
            "queued_at_w0": served["snaps"]["w0_queued"],
            "queued_at_w1": served["snaps"]["w1_queued"],
            "drain_s": m["end"] - m["w1"],
            "ttft_p50_s": nearest_rank(tt, 0.5),
            "ttft_p90_s": nearest_rank(tt, 0.9),
            "tpot_p50_s": nearest_rank(tp, 0.5),
            "tpot_p90_s": nearest_rank(tp, 0.9),
            "decode_steps": (served["snaps"]["w1"]["decode_host_syncs"]
                             - served["snaps"]["w0"]["decode_host_syncs"]),
            "at": time.monotonic()}), flush=True)
        # let the server go idle before the next rate
        server.run_until_idle()
    return 0


if __name__ == "__main__":
    sys.exit(main())
