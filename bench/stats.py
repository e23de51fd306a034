"""Arithmetic that the metric readers share: percentiles, and what each
request's client-side record says about time and work."""

from __future__ import annotations

import math

from bench import model as bm
from bench import trace_reduce as trd

INF = float("inf")


def nearest_rank(values, q: float):
    """The q-quantile by nearest rank (a missing or failed sample is +inf
    and counts as a miss); None when the tail is itself a miss."""
    if not values:
        return None
    v = sorted(values)
    x = v[max(0, math.ceil(q * len(v)) - 1)]
    return x if math.isfinite(x) else None


def ttft(r) -> float:
    """Time from when the request was due to its first token."""
    return r["times"][0] - r["due"] if r["ok"] else INF


def tpot(r) -> float:
    """Mean gap between the request's tokens after the first."""
    t = r["times"]
    return (t[-1] - t[0]) / (len(t) - 1) if r["ok"] and len(t) > 1 else INF


def decode_contexts(recs, t0, t1) -> list[int]:
    """Cached positions each decode step of the interval attended over:
    token i >= 1 comes from a step over ``prompt_len + i`` positions."""
    return [r["prompt_len"] + i for r in recs
            for i, t in enumerate(r["times"]) if i >= 1 and t0 <= t < t1]


def prefilled(recs, t0, t1) -> list:
    """Requests whose first token (prefill, and the retrieval just before
    it in the same tick) landed in the interval."""
    return [r for r in recs if r["times"] and t0 <= r["times"][0] < t1]


def decode_flops(run, t0, t1) -> float:
    arch = bm.arch_of(run["model"])
    n_layers, _, h, _, dh, _, _ = arch
    return trd.decoder_flops(bm.matmul_params(arch), n_layers, h, dh,
                             decode_contexts(run["all_requests"], t0, t1))


def prefill_flops(run, t0, t1) -> float:
    """Prompt tokens of the generator plus the question's tokens of the
    encoder (a bidirectional pass: each attends over the question)."""
    m = run["model"]
    arch = bm.arch_of(m)
    enc = bm.arch_of(dict(m["encoder"], vocab_size=m["vocab_size"]))
    n_layers, _, h, _, dh, _, _ = arch
    el, _, eh, _, edh, _, _ = enc
    gen_mm = bm.matmul_params(arch)
    enc_mm = bm.matmul_params(enc, with_head=False)
    total = 0.0
    for r in prefilled(run["all_requests"], t0, t1):
        total += trd.decoder_flops(gen_mm, n_layers, h, dh,
                                   trd.prefill_contexts(r["prompt_len"]))
        total += trd.decoder_flops(enc_mm, el, eh, edh,
                                   [r["q_len"]] * r["q_len"])
    return total


def window_mfu(run, flops: float):
    if flops <= 0:
        return None
    return 100.0 * flops / (run["seconds"] * run["peak"]["flops"])


def stage_ms(run, stages, count_key):
    """Host milliseconds per call of engine stages over the window."""
    c = run["counters"]
    n = c[count_key]
    if n <= 0:
        return None
    return 1e3 * sum(c["stage_time_s"].get(s, 0.0) for s in stages) / n


def kernel_share(run, kernel, flops_bytes):
    """Roofline share (%) of the kernel the trace names ``kernel``; None
    without a trace or without the kernel in it."""
    tr = run["trace"]
    if tr is None:
        return None
    secs = trd.kernel_seconds(tr["op_s"], kernel)
    flops, nbytes = flops_bytes(tr["t0"], tr["t1"])
    if secs is None or nbytes <= 0:
        return None
    return trd.roofline_share(flops, nbytes, secs, run["peak"])
