"""A checkout-shaped directory that holds the benchmark's own readers and
references and a tiny configuration with two traffic mixes, for tests
that drive the harness on the CPU."""

import json
import shutil
from pathlib import Path

from bench import harness

TINY = Path(__file__).resolve().parent / "tiny"
OPEN, CLOSED = "tiny-gqa.rag-decode", "tiny-gqa.rag-longctx"
# Set from the tiny cell's readings on the CPU (7 seeds): sound runs read
# logit_gap <= 0.030 and retrieval_gap 0; the float8 control logit_gap
# >= 0.108 and retrieval_gap >= 0.075, and fails one of the two on every
# seed; the planted faults of test_bench_correct fail them too.
LIMITS = {"logit_gap": 0.06, "retrieval_gap": 0.01}
CELLS = {OPEN: ("tiny-open", {"decode_slots": 4, "rate_per_s": 8,
                              "limits": LIMITS}),
         CLOSED: ("tiny-closed", {"decode_slots": 2, "limits": LIMITS})}
# the Pallas kernels in interpret mode: the timed path's code, off the chip
KERNELS = {"attn_impl": "pallas", "use_pq_kernel": True}


def make_root(tmp: Path) -> Path:
    """``tmp`` with a BENCHMARK.json of the two tiny cells (the real
    metric list, re-pointed at them) and a ``bench`` directory of copies."""
    bench = tmp / "bench"
    for d in ("metrics", "references"):
        shutil.copytree(harness.BENCH_DIR / d, bench / d)
    for d in ("traffic", "cells", "configs"):
        (bench / d).mkdir(parents=True)
    shutil.copy(TINY / "tiny-gqa.json", bench / "configs")
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny-gqa", "source": "test",
                        "file": "bench/configs/tiny-gqa.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = []
    for name, (mix, cell) in CELLS.items():
        shutil.copy(TINY / f"{mix}.json", bench / "traffic")
        (bench / "cells" / f"{name}.json").write_text(json.dumps(cell))
        spec["workloads"].append({"name": name, "config": "tiny-gqa",
                                  "traffic": mix, "chips": 1,
                                  "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [OPEN if "decode" in w else CLOSED
                              for w in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, cell: str, trace: bool = False, seed: int = 2**31 + 7,
        seconds: float = 2.0, overrides=None) -> dict:
    spec = harness.load_spec(cell, root=root, bench_dir=root / "bench")
    return harness.run_cell(spec, seed, seconds, trace, require_tpu=False,
                            cache=False,
                            engine_overrides=dict(overrides or KERNELS))
