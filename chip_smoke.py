#!/usr/bin/env python3
"""Bring-up smoke run: serve granite-3-2b at its full published widths and
depth on one TPU chip, through the library API a user would call.

    python chip_smoke.py [--seed N]

One process, no children.  Phases, each reported on its own JSON line with
its compile time (XLA backend compiles during the phase), wall times and
the device's peak memory so far:

  build      granite-3-2b with bf16 parameters initialised under jit from
             ``--seed`` (no float32 tree is ever materialised), the repo's
             small bidirectional encoder stand-in, a topical corpus of 4096
             passages of 128 tokens.
  baseline   ``EngineConfig.from_schema(baseline(), retrieval_backend=
             "ivfpq")`` -> ``RAGEngine`` -> ``RAGServer``: 8 requests, each
             to DONE with 32 tokens.  Mid-run, the Pallas paged-decode
             kernel is checked against the engine's gather reference on the
             live KV pool.
  checks     the IVF-PQ Pallas scan against the jnp scan (identical top-k
             ids) and finite first-token logits.
  iterative  the ``iterative`` preset: retrievals during decode append
             passages through ``paged_chunk_extend``.
  disagg     a 1 prefill + 1 decode ``RAGCluster``: the KV handoff with its
             CRC check on the device.

It exits nonzero on any failure, and before building anything when JAX
finds no TPU: nothing falls back to the CPU.  On the chip it asserts that
``attn_impl="auto"`` resolved to the Pallas kernel and the IVF-PQ backend
to the Pallas scan.  The last line of standard output is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set,
``<repo>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from dataclasses import dataclass, replace
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import granite_3_2b, rag_pipelines  # noqa: E402
from repro.data.synthetic import topical_corpus  # noqa: E402
from repro.kernels.paged_attention.ops import (  # noqa: E402
    paged_decode_attention)
from repro.kernels.paged_attention.ref import engine_ref_attn  # noqa: E402
from repro.models import transformer as tr  # noqa: E402
from repro.retrieval import ivf_pq  # noqa: E402
from repro.serving.cluster import RAGCluster  # noqa: E402
from repro.serving.engine import (Component, EngineConfig,  # noqa: E402
                                  RAGEngine, bucket_len)
from repro.serving.request import State  # noqa: E402
from repro.serving.server import RAGServer  # noqa: E402

# Pallas kernel vs the engine's gather reference on the live pool.  The
# reference casts its softmax probabilities to bf16 before the PV product
# and rounds the output to bf16; the kernel keeps f32 probabilities.
ATTN_ATOL, ATTN_RTOL = 3e-2, 2e-2


@dataclass(frozen=True)
class Sizes:
    """Workload of one smoke run (the defaults are the chip run's)."""
    n_docs: int = 4096
    doc_len: int = 128
    retrieval_k: int = 4
    s_max: int = 1024
    max_new_tokens: int = 32
    decode_slots: int = 8
    kv_spare_pages: int = 16
    n_baseline: int = 8
    n_iterative: int = 4
    n_disagg: int = 4
    iterative_interval: int = 8


class CompileClock:
    """Sums XLA backend-compile seconds (persistent-cache loads count as
    their short retrieval time) and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.cache_hits

    def since(self, mark):
        return {"compile_s": self.seconds - mark[0],
                "cache_hits": self.cache_hits - mark[1]}


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def report(phase: str, **fields) -> dict:
    line = {"phase": phase, **fields, "peak_bytes": peak_bytes()}
    print(json.dumps(line), flush=True)
    return line


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def build_stack(gen_cfg: tr.TransformerConfig, sizes: Sizes, seed: int):
    """Generative model (bf16, initialised on the device), encoder
    stand-in, corpus and a question sampler."""
    key_gen, key_enc = jax.random.split(jax.random.PRNGKey(seed))
    init = jax.jit(partial(tr.init_params, cfg=gen_cfg, dtype=jnp.bfloat16))
    gen = Component(gen_cfg, jax.block_until_ready(init(key_gen)))
    enc_cfg = tr.TransformerConfig(
        name="encoder-standin", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=64, vocab_size=gen_cfg.vocab_size,
        causal=False)
    enc = Component(enc_cfg, tr.init_params(key_enc, enc_cfg))
    corpus, _topics, make_q = topical_corpus(
        sizes.n_docs, sizes.doc_len, gen_cfg.vocab_size, n_topics=8,
        seed=seed)
    return gen, enc, corpus, make_q


def engine_config(schema, sizes: Sizes, **overrides) -> EngineConfig:
    return EngineConfig.from_schema(
        schema, decode_slots=sizes.decode_slots, s_max=sizes.s_max,
        retrieval_k=sizes.retrieval_k, max_new_tokens=sizes.max_new_tokens,
        retrieval_backend="ivfpq", kv_spare_pages=sizes.kv_spare_pages,
        **overrides)


def serve(server: RAGServer, questions, max_new: int) -> list:
    """Submit, drain, and require every request DONE with max_new tokens."""
    handles = [server.submit(q) for q in questions]
    server.run_until_idle()
    for h in handles:
        check(h.state is State.DONE,
              f"request {h.rid} ended {h.state.value}: "
              f"{h.request.fail_reason}")
        check(len(h.output) == max_new,
              f"request {h.rid} has {len(h.output)} tokens, not {max_new}")
    return handles


def check_live_attention(engine: RAGEngine, seed: int) -> dict:
    """Pallas paged attention vs the engine's gather path on the live
    pool (last layer), over the slots that hold a sequence."""
    cfg, pool = engine.gen.cfg, engine.pool
    lengths = np.asarray(pool.lengths)
    live = lengths > 0
    check(live.any(), "no live sequence in the pool")
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (pool.n_slots, 1, cfg.n_heads, cfg.d_head),
                          jnp.bfloat16)
    kp = pool.cache["k"][cfg.n_layers - 1]
    vp = pool.cache["v"][cfg.n_layers - 1]
    tables = jnp.asarray(pool.block_tables())
    lens = jnp.asarray(lengths)
    out = paged_decode_attention(q, kp, vp, tables, lens,
                                 num_buffers=engine.cfg.attn_num_buffers)
    ref = engine_ref_attn(q, kp, vp, tables, lens, cfg.q_per_kv)
    out = np.asarray(out, np.float32)[live]
    ref = np.asarray(ref, np.float32)[live]
    err = np.abs(out - ref)
    check(np.isfinite(out).all(), "non-finite Pallas attention output")
    check(bool((err <= ATTN_ATOL + ATTN_RTOL * np.abs(ref)).all()),
          f"Pallas vs gather attention: max abs error {err.max()}")
    return {"live_slots": int(live.sum()), "max_abs_err": float(err.max()),
            "atol": ATTN_ATOL, "rtol": ATTN_RTOL}


def check_pq_scan(engine: RAGEngine, questions, k: int) -> dict:
    """IVF-PQ search through the Pallas scan vs the jnp scan."""
    ivf = engine.backend.chain[0]
    qv = engine._embed_batched(np.stack(questions))
    _, ids_kernel = ivf_pq.search(ivf.index, qv, nprobe=ivf.nprobe, k=k,
                                  use_kernel=True)
    _, ids_ref = ivf_pq.search(ivf.index, qv, nprobe=ivf.nprobe, k=k,
                               use_kernel=False)
    check(np.array_equal(np.asarray(ids_kernel), np.asarray(ids_ref)),
          "pq_scan kernel top-k ids differ from pq_scan_ref")
    return {"queries": len(questions),
            "scan_shape": [ivf.nprobe * len(questions),
                           int(ivf.index.list_ids.shape[1]),
                           ivf.index.n_subq]}


def check_first_logits(engine: RAGEngine, prompt) -> dict:
    """The prefill program's logits for one prompt are finite."""
    n = len(prompt)
    bucket = bucket_len(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    logits = engine._prefill_jit[bucket](engine.gen.params,
                                         jnp.asarray(padded))[0]
    row = np.asarray(logits[0, n - 1, :engine.gen.cfg.vocab_size],
                     np.float32)
    check(bool(np.isfinite(row).all()), "NaN/inf in first-token logits")
    return {"prompt_tokens": n, "logit_absmax": float(np.abs(row).max())}


def run_phases(gen_cfg: tr.TransformerConfig, sizes: Sizes, seed: int,
               **engine_overrides) -> list[dict]:
    """Every smoke phase; raises on the first failure.  ``engine_overrides``
    are for rehearsals off the chip (e.g. forcing the kernels on in
    interpret mode); the chip run passes none."""
    clock = CompileClock()
    lines = []
    mark, t0 = clock.mark(), time.monotonic()
    gen, enc, corpus, make_q = build_stack(gen_cfg, sizes, seed)
    lines.append(report("build", model=gen_cfg.name,
                        layers=gen_cfg.n_layers, d_model=gen_cfg.d_model,
                        params=gen_cfg.param_count(),
                        wall_s=time.monotonic() - t0, **clock.since(mark)))

    # -- baseline: collocated engine, IVF-PQ retrieval ----------------------
    mark, t0 = clock.mark(), time.monotonic()
    cfg = engine_config(rag_pipelines.baseline(), sizes, **engine_overrides)
    engine = RAGEngine(gen, enc, corpus, cfg)
    setup_s = time.monotonic() - t0
    server = RAGServer(engine)
    questions = [make_q(i % 8) for i in range(sizes.n_baseline)]
    t1 = time.monotonic()
    serve(server, questions[:1], sizes.max_new_tokens)
    warm_s = time.monotonic() - t1
    # steady: the rest, with the kernel check taken on the live pool once
    # every slot that will decode has been filled
    before = engine.metrics_snapshot()
    handles = [server.submit(q) for q in questions[1:]]
    t1 = time.monotonic()
    while not any(len(h.output) >= sizes.max_new_tokens // 2
                  for h in handles):
        check(server.step(), "server went idle before any request "
              "reached half its tokens")
    steady_s = time.monotonic() - t1
    attn = check_live_attention(engine, seed)
    t1 = time.monotonic()
    server.run_until_idle()
    steady_s += time.monotonic() - t1
    for h in handles:
        check(h.state is State.DONE and
              len(h.output) == sizes.max_new_tokens,
              f"baseline request {h.rid}: {h.state.value} "
              f"{len(h.output)} tokens")
    snap = engine.metrics_snapshot()
    stages = {k: v - before["stage_time_s"].get(k, 0.0)
              for k, v in snap["stage_time_s"].items()}
    lines.append(report(
        "baseline", requests=sizes.n_baseline, setup_s=setup_s,
        warm_s=warm_s, steady_s=steady_s,
        steady_tokens=(sizes.n_baseline - 1) * sizes.max_new_tokens,
        steady_decode_steps=snap["decode_steps"] - before["decode_steps"],
        steady_stage_s=stages, attn_impl=engine.attn_impl,
        pq_kernel=engine.backend.chain[0].use_kernel, attn_check=attn,
        **clock.since(mark)))

    # -- checks: PQ scan kernel, first-token logits -------------------------
    mark, t0 = clock.mark(), time.monotonic()
    pq = check_pq_scan(engine, questions, sizes.retrieval_k)
    logits = check_first_logits(engine, handles[0].request.prompt)
    lines.append(report("checks", pq_scan=pq, first_logits=logits,
                        wall_s=time.monotonic() - t0, **clock.since(mark)))
    shared = dict(db_vectors=engine.db_vectors, backend=engine.backend)
    del engine, server, handles
    gc.collect()

    # -- iterative: retrievals during decode append through chunk extend ----
    mark, t0 = clock.mark(), time.monotonic()
    cfg = engine_config(rag_pipelines.iterative(), sizes,
                        iterative_interval=sizes.iterative_interval,
                        **engine_overrides)
    engine = RAGEngine(gen, enc, corpus, cfg, **shared)
    handles = serve(RAGServer(engine),
                    [make_q(i % 8) for i in range(sizes.n_iterative)],
                    sizes.max_new_tokens)
    appended = sum(h.request.retrievals_done for h in handles)
    snap = engine.metrics_snapshot()
    check(appended > 0 and snap["append_compiles"] > 0,
          "iterative phase appended no retrieved context")
    lines.append(report("iterative", requests=sizes.n_iterative,
                        retrievals=appended,
                        append_compiles=snap["append_compiles"],
                        wall_s=time.monotonic() - t0, **clock.since(mark)))
    del engine, handles
    gc.collect()

    # -- disagg: 1 prefill + 1 decode engine, KV handoff with CRC -----------
    mark, t0 = clock.mark(), time.monotonic()
    cfg = engine_config(rag_pipelines.baseline(), sizes, **engine_overrides)
    cluster = RAGCluster(
        [RAGEngine(gen, enc, corpus, replace(cfg, decode_slots=1),
                   **shared)],
        [RAGEngine(gen, enc, corpus, cfg, **shared)])
    serve(RAGServer(cluster),
          [make_q(i % 8) for i in range(sizes.n_disagg)],
          sizes.max_new_tokens)
    m = cluster.metrics.snapshot()
    check(m["handoffs"] == sizes.n_disagg and m["handoff_corrupt"] == 0,
          f"disagg handoffs {m['handoffs']}, corrupt "
          f"{m['handoff_corrupt']}")
    lines.append(report("disagg", requests=sizes.n_disagg,
                        handoffs=m["handoffs"],
                        handoff_bytes=m["handoff_bytes"],
                        wall_s=time.monotonic() - t0, **clock.since(mark)))
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the random weights and the corpus")
    args = p.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(json.dumps({"phase": "device", **device}), flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this run "
              "only counts on the chip", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(json.dumps({"phase": "cache", "dir": cache}), flush=True)
    lines = run_phases(granite_3_2b.CONFIG, Sizes(), args.seed)
    served = {ln["phase"]: ln for ln in lines}
    check(served["baseline"]["attn_impl"] == "pallas",
          "attn_impl 'auto' did not resolve to the Pallas kernel")
    check(served["baseline"]["pq_kernel"] is True,
          "the IVF-PQ backend did not select the Pallas scan")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
