#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from the root of a checkout.  The cells, configurations and
metrics are listed in ``BENCHMARK.json``; ``bench/harness.py`` says what a
run does.  With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, the device's busy time
and a breakdown of the traced window.  Progress goes to standard error,
ending with one line per number of the output check and its limit; the
last line of standard output is the result as one JSON object.  Without
a TPU, or with fewer chips than the cell asks for, the run exits 3 and
prints no result.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    import repro  # noqa: F401  -- the system under test must be here
    from bench import harness
    spec = harness.load_spec(args.workload)
    try:
        result = harness.run_cell(spec, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(harness.result_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
