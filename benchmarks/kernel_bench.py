"""Kernel microbenchmark sweep: writes ``BENCH_kernels.json``.

Sweeps the serving hot-path kernels over their tuning axes:

  * ``paged_attention`` -- the ragged paged-decode attention kernel
    (``repro.kernels.paged_attention``): page size x DMA staging depth
    (double vs quad buffering), on a ragged batch.  The figure of merit
    is achieved KV streaming bandwidth: bytes of K/V actually touched
    (``sum_b ceil(len_b / page) * page`` rows -- the ragged early-exit
    means idle tail pages are NOT read) divided by wall time.
  * ``pq_scan`` -- the IVF-PQ ADC scan: candidate block size, bytes of
    PQ codes scanned per second.

The best measured paged-attention bandwidth feeds
``core/cost_model.calibrate_xpu_decode``: decode is memory-bound, so the
achieved fraction of HBM bandwidth IS the decode efficiency, and every
row reports the calibrated spec + before/after analytical decode-TPOT
prediction (same contract as serving_bench's ``xpu_calibration`` rows).
On this CPU container the numbers calibrate the analytical model to the
dev environment, not a TPU -- the sweep's job in CI is the RELATIVE
regression gate (``--compare``), the absolute numbers come from running
the same sweep on real hardware.

Modes:
    PYTHONPATH=src python benchmarks/kernel_bench.py            # full sweep
    ... --smoke                        # one page size per kernel (CI)
    ... --compare PREV.json [--tolerance 0.25]
                                       # nonzero exit when any row's
                                       # bytes_per_s dropped > 2*tolerance
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

# batch shape shared by every paged-attention row: 4 ragged sequences
# (empty / short / medium / near-full), GQA 4:2 heads
BATCH, HEADS, KV_HEADS, HEAD_DIM = 4, 4, 2, 64
S_MAX = 128


def _time_call(fn, *args, reps: int = 3) -> float:
    """Steady-state seconds per call of a jitted fn (1 warmup + reps)."""
    import jax
    jax.block_until_ready(fn(*args))                 # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def bench_paged_attention(page_size: int, num_buffers: int,
                          reps: int = 3) -> dict:
    import jax.numpy as jnp

    from repro.kernels.paged_attention.ops import paged_decode_attention

    rng = np.random.default_rng(0)
    m_pages = S_MAX // page_size
    n_pool = BATCH * m_pages + 1
    q = jnp.asarray(rng.standard_normal(
        (BATCH, HEADS, HEAD_DIM)), jnp.bfloat16)
    k_pages = jnp.asarray(rng.standard_normal(
        (n_pool, page_size, KV_HEADS * HEAD_DIM)), jnp.bfloat16)
    v_pages = jnp.asarray(rng.standard_normal(
        (n_pool, page_size, KV_HEADS * HEAD_DIM)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(BATCH * m_pages)[:BATCH * m_pages]
                         .reshape(BATCH, m_pages), jnp.int32)
    # ragged: empty, one page, half, full
    lengths_np = np.asarray(
        [0, min(page_size, S_MAX), S_MAX // 2, S_MAX], np.int64)
    lengths = jnp.asarray(lengths_np, jnp.int32)

    wall = _time_call(paged_decode_attention, q, k_pages, v_pages, tables,
                      lengths, num_buffers, reps=reps)
    # K+V rows the ragged kernel actually streams (2 bytes/elt bf16)
    pages_read = int(np.sum(-(-lengths_np // page_size)))
    kv_bytes = 2 * pages_read * page_size * KV_HEADS * HEAD_DIM * 2
    return {
        "kernel": "paged_attention",
        "page_size": page_size,
        "num_buffers": num_buffers,
        "batch": BATCH,
        "lengths": lengths_np.tolist(),
        "wall_us": round(wall * 1e6, 1),
        "kv_bytes": kv_bytes,
        "bytes_per_s": round(kv_bytes / wall, 1),
    }


def bench_pq_scan(block_n: int, n_codes: int = 4096, n_sub: int = 16,
                  reps: int = 3) -> dict:
    import jax.numpy as jnp

    from repro.kernels.pq_scan.ops import pq_scan

    rng = np.random.default_rng(0)
    lut = jnp.asarray(rng.standard_normal((2, n_sub, 256)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, 256, (2, n_codes, n_sub)), jnp.uint8)
    wall = _time_call(pq_scan, lut, codes, block_n, reps=reps)
    code_bytes = int(codes.size)                     # 1 byte per PQ code
    return {
        "kernel": "pq_scan",
        "block_n": block_n,
        "n_codes": n_codes,
        "n_subquantizers": n_sub,
        "wall_us": round(wall * 1e6, 1),
        "code_bytes": code_bytes,
        "bytes_per_s": round(code_bytes / wall, 1),
    }


def _decode_calibration(bytes_per_s: float) -> dict:
    """Measured decode-attention bandwidth -> calibrated decode TPOT
    prediction (``calibrate_xpu_decode``), reported per row like
    serving_bench's ``xpu_calibration``."""
    from repro.configs.rag_pipelines import PRESETS
    from repro.core.cost_model import calibrate_xpu_decode, decode_tpot
    from repro.core.hardware import XPU_C

    schema = PRESETS["baseline"]()
    spec = calibrate_xpu_decode(XPU_C, bytes_per_s)
    shape = schema.generative
    return {
        "decode_bytes_per_s": round(bytes_per_s, 1),
        "mem_eff_before": round(XPU_C.mem_eff, 8),
        "mem_eff_after": round(spec.mem_eff, 8),
        "predicted_tpot_before_s": round(
            decode_tpot(shape, XPU_C, 1, BATCH, schema.prefix_len), 6),
        "predicted_tpot_after_s": round(
            decode_tpot(shape, spec, 1, BATCH, schema.prefix_len), 6),
    }


def compare_results(cur: dict, prev: dict, tolerance: float = 0.25) -> list:
    """Per-row bandwidth regressions of ``cur`` vs a previous
    BENCH_kernels.json.

    Rows are matched on their full tuning key (kernel + sweep axes); a
    matched row's ``bytes_per_s`` must not drop more than
    ``2 * tolerance`` (doubled like serving_bench's p99 gates:
    interpret-mode microbenchmarks on shared CI are noisy, but a kernel
    that got 2x slower still fails).  Rows present only in one file are
    skipped -- sweep axes may legitimately change between PRs."""
    def key(row):
        return tuple(sorted((k, v) for k, v in row.items()
                            if k in ("kernel", "page_size", "num_buffers",
                                     "block_n")))

    regressions = []
    cur_rows = {key(r): r for r in cur.get("rows", [])}
    for old in prev.get("rows", []):
        new = cur_rows.get(key(old))
        if new is None:
            continue
        if not old.get("bytes_per_s") or new.get("bytes_per_s") is None:
            continue
        tol = 2.0 * tolerance
        bound = old["bytes_per_s"] * (1.0 - tol)
        if new["bytes_per_s"] < bound:
            name = ", ".join(f"{k}={v}" for k, v in key(old))
            regressions.append(
                f"{name}: bytes_per_s {new['bytes_per_s']} < {bound:.1f} "
                f"(prev {old['bytes_per_s']}, tol {tol})")
    return regressions


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="one configuration per sweep axis (CI)")
    p.add_argument("--out", default="BENCH_kernels.json")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--compare", default=None, metavar="PREV.json",
                   help="exit nonzero on bandwidth regression vs a "
                        "previous BENCH_kernels.json")
    p.add_argument("--tolerance", type=float, default=0.25,
                   help="fractional tolerance for --compare (doubled "
                        "for the bandwidth gate)")
    args = p.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    page_sizes = [16] if args.smoke else [8, 16, 32]
    block_ns = [512] if args.smoke else [256, 512, 1024]

    rows = []
    for page in page_sizes:
        for nb in (2, 4):
            row = bench_paged_attention(page, nb, reps=args.reps)
            rows.append(row)
            print(f"paged_attention page={page} buffers={nb}: "
                  f"{row['wall_us']}us, "
                  f"{row['bytes_per_s'] / 1e6:.1f} MB/s", flush=True)
    best = max(r["bytes_per_s"] for r in rows)
    for row in [r for r in rows if r["kernel"] == "paged_attention"]:
        row["xpu_calibration"] = _decode_calibration(row["bytes_per_s"])
    for bn in block_ns:
        row = bench_pq_scan(bn, reps=args.reps)
        rows.append(row)
        print(f"pq_scan block_n={bn}: {row['wall_us']}us, "
              f"{row['bytes_per_s'] / 1e6:.1f} MB/s", flush=True)

    results = {
        "meta": {
            "smoke": bool(args.smoke),
            "jax_backend": jax.default_backend(),
            "interpret": jax.default_backend() != "tpu",
            "best_decode_bytes_per_s": best,
            # the calibration a deployment would feed into plan search
            "decode_calibration": _decode_calibration(best),
        },
        "rows": rows,
    }
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
