"""From a profiler trace and a run's counts to device numbers.

* ``load``: the events of an ``.xplane.pb`` (or its text form), as
  ``Event(plane, line, name, start_ns, dur_ns)``.
* ``reduce_trace``: device busy time as the union of the intervals in
  which an operation ran, device time per operation and per kernel name,
  and the idle gaps, each named by what the host's serving loop was doing
  at its middle.
* The peak table, keyed by ``device_kind``; a device not in it is an error.
* Work and bytes that an algorithm needs, from shapes and lengths: the
  paged-decode attention kernel, the PQ scan, and a decoder's model FLOPs.
  A roofline share is the least time the chip could take (the larger of
  FLOPs over peak and bytes over bandwidth) over the measured time.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

# Published peaks of one chip.  Source: Google Cloud documentation, "TPU
# v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

# Device lines that hold one event per executed operation; program-level
# lines ("XLA Modules", "Steps") would count the same time twice.
OPS_LINE = "XLA Ops"
HOST_LOOP = "bench.loop"          # the serving loop's own host span


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak numbers for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(path) -> list[Event]:
    """Every event of a trace file: ``*.xplane.pb``, or an XSpace in text
    proto form (``*.pbtxt``)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".pbtxt":
        data = ProfileData.from_text_proto(path.read_text())
    else:
        data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def find_trace(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _gaps(intervals, lo, hi):
    """Idle intervals inside [lo, hi] left by the busy intervals."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _innermost(host: list[Event], idle) -> list[str]:
    """For each idle interval (sorted), the name of the innermost host span
    open at its middle: one sweep over the thread's spans, which nest, with
    the open ones on a stack."""
    names, stack, j = [], [], 0
    for s, e in idle:
        mid = 0.5 * (s + e)
        while j < len(host) and host[j].start_ns <= mid:
            while stack and stack[-1].end_ns <= host[j].start_ns:
                stack.pop()
            stack.append(host[j])
            j += 1
        while stack and stack[-1].end_ns <= mid:
            stack.pop()
        names.append(stack[-1].name if stack else "no host span")
    return names


# Control flow whose event spans the operations of its body, which the
# line lists too: counting it would count their time twice.
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names each operation by its
    whole instruction text, ``%name.N = type op(operands...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kind(name: str) -> str:
    """An operation's name without its instance number (``fusion.12`` and
    ``fusion.3`` are one kind of operation)."""
    return re.sub(r"\.\d+$", "", name)


def reduce_trace(events: list[Event], top: int = 10) -> dict:
    """Busy time averaged over the device planes, device seconds per
    operation (control-flow containers left out), and the idle gaps,
    each named by the innermost host span of the serving loop's thread
    at its middle."""
    dev = [e for e in events if is_device(e.plane) and e.line == OPS_LINE]
    planes = sorted({e.plane for e in dev})
    if not planes:
        return {"planes": [], "busy_s": 0.0, "op_s": {},
                "device_ops": [], "idle_gaps": []}
    busy = {p: union_ns([(e.start_ns, e.end_ns) for e in dev
                         if e.plane == p]) for p in planes}
    op_s = defaultdict(float)
    for e in dev:
        name = op_name(e.name)
        if kind(name) not in CONTAINERS:
            op_s[name] += e.dur_ns * 1e-9
    by_kind = defaultdict(float)
    for name, s in op_s.items():
        by_kind[kind(name)] += s
    # host: the thread that ran the serving loop
    loop = [e for e in events if e.name == HOST_LOOP]
    gaps = defaultdict(float)
    if loop:
        thread = (loop[0].plane, loop[0].line)
        host = sorted((e for e in events if (e.plane, e.line) == thread),
                      key=lambda e: (e.start_ns, -e.dur_ns))
        lo = min(e.start_ns for e in loop)
        hi = max(e.end_ns for e in loop)
        idle = _gaps([(x.start_ns, x.end_ns) for x in dev
                      if x.plane == planes[0]], lo, hi)
        for (s, e), name in zip(idle, _innermost(host, idle)):
            gaps[name] += (e - s) * 1e-9
    return {
        "planes": planes,
        "busy_s": sum(busy.values()) * 1e-9 / len(planes),
        "op_s": dict(op_s),
        "device_ops": sorted(([k, v] for k, v in by_kind.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def kernel_seconds(op_s: dict, name: str) -> float | None:
    """Device seconds of the operations of one kind (``name`` as the
    compiled program calls it); None when none ran."""
    hits = [s for op, s in op_s.items() if kind(op) == name]
    return sum(hits) if hits else None


# ---------------------------------------------------------------------------
# Work an algorithm needs
# ---------------------------------------------------------------------------

def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Percent of the roofline: least time over measured time."""
    least = max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def paged_attention_work(contexts, *, n_layers, n_heads, n_kv_heads,
                         head_dim, itemsize=2) -> tuple[float, float]:
    """(FLOPs, bytes) of single-token decode attention over cached
    contexts of the given lengths (one entry per sequence per step): the
    K and V of every live token, the query and the output."""
    toks = float(sum(contexts))
    n = len(contexts)
    kv_bytes = 2.0 * toks * n_kv_heads * head_dim * itemsize
    qo_bytes = 2.0 * n * n_heads * head_dim * itemsize
    flops = 4.0 * toks * n_heads * head_dim
    return n_layers * flops, n_layers * (kv_bytes + qo_bytes)


def pq_scan_work(calls: int, *, nprobe, list_len, n_subq
                 ) -> tuple[float, float]:
    """(adds, bytes) of ADC scans: uint8 codes of the probed lists, the
    float32 lookup tables and the float32 distances out."""
    codes = nprobe * list_len * n_subq
    lut = nprobe * n_subq * 256 * 4
    out = nprobe * list_len * 4
    return float(calls * codes), float(calls * (codes + lut + out))


def decoder_flops(matmul_params: int, n_layers: int, n_heads: int,
                  head_dim: int, contexts) -> float:
    """Model FLOPs of tokens each attending over ``contexts[i]`` positions:
    two per matmul parameter, four per attended position per head dim."""
    toks = len(contexts)
    return (2.0 * matmul_params * toks
            + 4.0 * n_layers * n_heads * head_dim * float(sum(contexts)))


def prefill_contexts(length: int) -> range:
    """Causal prefill of ``length`` tokens: token i attends over i+1."""
    return range(1, length + 1)
