"""The program's own names on a traced run's device trace.

The serving path names what it runs (``src/repro``):

* each jitted program: ``jit_rago_encode``, ``jit_rago_search``,
  ``jit_rago_prefill``, ``jit_rago_decode``, ``jit_rago_page_install``,
  ``jit_rago_chunk_extend`` on the device's ``XLA Modules`` line;
* the operations inside them, by ``jax.named_scope`` (``embed``,
  ``kv_write``, ``attention``, ``ffn``, ``head``; ``coarse``, ``adc``,
  ``topk`` in the search), in each operation's metadata, which the trace
  keeps in the HLO of every program it saw;
* the engine's stages and decode sub-steps and the server's delivery, by
  ``rago.*`` profiler spans on the serving loop's thread, each with its
  ``time.monotonic`` start (``mono_ns``) and the engine's tick.

``summary`` reduces one traced run, on the trace's one clock, to the
device time of each named program, the device time of the decode step's
operations by scope, and the device's idle time in the traced window, each
idle interval put down to the innermost ``rago.*`` span open at its middle
(Python frames and ``bench.*`` spans are skipped).  A trace without a
device (a CPU run) reduces to ``None``.

The file is read with a minimal description of the XSpace protobuf, so the
metadata of events and the HLO of programs, which ``jax.profiler
.ProfileData`` does not expose, can be read too.
"""

from __future__ import annotations

import functools
import math
import re
import statistics
import tempfile
import time
from collections import defaultdict
from pathlib import Path

from bench import trace_reduce as trd

PROGRAMS = ("rago_encode", "rago_search", "rago_prefill", "rago_decode",
            "rago_page_install", "rago_chunk_extend")
SCOPES = ("embed", "kv_write", "attention", "ffn", "head",
          "coarse", "adc", "topk")
KERNELS = ("paged_decode_attention", "pq_scan")
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "rago."
NO_SPAN = "no program span"

# ---------------------------------------------------------------------------
# Reading the file
# ---------------------------------------------------------------------------

_I64, _U64, _DBL, _STR, _BYTES, _MSG = 3, 4, 1, 9, 12, 11
# (message, [(field, number, type, repeated, message type)]): the parts of
# tsl's xplane.proto and xla's hlo.proto this module reads, field numbers
# as there; protobuf skips the fields left out.
_SCHEMA = [
    ("XStat", [("metadata_id", 1, _I64, 0, None), ("double_value", 2, _DBL, 0,
               None), ("uint64_value", 3, _U64, 0, None),
               ("int64_value", 4, _I64, 0, None), ("str_value", 5, _STR, 0,
               None), ("bytes_value", 6, _BYTES, 0, None),
               ("ref_value", 7, _U64, 0, None)]),
    ("XEvent", [("metadata_id", 1, _I64, 0, None), ("offset_ps", 2, _I64, 0,
                None), ("duration_ps", 3, _I64, 0, None),
                ("stats", 4, _MSG, 1, "XStat")]),
    ("XLine", [("name", 2, _STR, 0, None), ("timestamp_ns", 3, _I64, 0, None),
               ("events", 4, _MSG, 1, "XEvent")]),
    ("XEventMetadata", [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None),
                        ("stats", 5, _MSG, 1, "XStat")]),
    ("XStatMetadata", [("id", 1, _I64, 0, None), ("name", 2, _STR, 0, None)]),
    ("EventMetadataEntry", [("key", 1, _I64, 0, None),
                            ("value", 2, _MSG, 0, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, _I64, 0, None),
                           ("value", 2, _MSG, 0, "XStatMetadata")]),
    ("XPlane", [("name", 2, _STR, 0, None), ("lines", 3, _MSG, 1, "XLine"),
                ("event_metadata", 4, _MSG, 1, "EventMetadataEntry"),
                ("stat_metadata", 5, _MSG, 1, "StatMetadataEntry"),
                ("stats", 6, _MSG, 1, "XStat")]),
    ("XSpace", [("planes", 1, _MSG, 1, "XPlane")]),
    ("OpMetadata", [("op_name", 2, _STR, 0, None)]),
    ("HloInstructionProto", [("name", 1, _STR, 0, None),
                             ("metadata", 7, _MSG, 0, "OpMetadata"),
                             ("id", 35, _I64, 0, None),
                             ("operand_ids", 36, _I64, 1, None)]),
    ("HloComputationProto", [("instructions", 2, _MSG, 1,
                              "HloInstructionProto")]),
    ("HloModuleProto", [("computations", 3, _MSG, 1,
                         "HloComputationProto")]),
    ("HloProto", [("hlo_module", 1, _MSG, 0, "HloModuleProto")]),
]


@functools.cache
def _messages() -> dict:
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_program_trace.proto", package="bench_program_trace")
    for name, fields in _SCHEMA:
        msg = fd.message_type.add(name=name)
        for fname, number, ftype, repeated, tname in fields:
            f = msg.field.add(name=fname, number=number, type=ftype,
                              label=3 if repeated else 1)
            if tname:
                f.type_name = f".bench_program_trace.{tname}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"bench_program_trace.{name}"))
        for name, _ in _SCHEMA}


_VALUES = ("str_value", "int64_value", "uint64_value", "double_value",
           "bytes_value", "ref_value")


def _stat_value(st):
    for f in _VALUES:
        if st.HasField(f):
            return getattr(st, f)
    return None


def parse(data: bytes):
    """An XSpace message from the bytes of an ``.xplane.pb``."""
    xs = _messages()["XSpace"]()
    xs.ParseFromString(data)
    return xs


def read_space(path):
    """The XSpace of ``path``: an ``.xplane.pb``, or its text form
    (``.pbtxt``, as the committed test traces are kept)."""
    path = Path(path)
    if path.suffix == ".pbtxt":
        from jax.profiler import ProfileData
        return parse(ProfileData.text_proto_to_serialized_xspace(
            path.read_text()))
    return parse(path.read_bytes())


class _Plane:
    """One plane's events as plain tuples, with its metadata resolved."""

    def __init__(self, plane):
        self.name = plane.name
        self.event_names = {e.key: e.value.name for e in plane.event_metadata}
        self.stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        self._plane = plane

    def stats(self, stats) -> dict:
        """Name -> value; a ``ref_value`` is the name of another stat's
        metadata (how a trace stores a repeated string)."""
        out = {}
        for s in stats:
            v = _stat_value(s)
            if s.HasField("ref_value"):
                v = self.stat_names.get(v, "")
            out[self.stat_names.get(s.metadata_id, "")] = v
        return out

    def lines(self):
        return self._plane.lines

    def events(self, line, with_stats=False):
        """``(start_ns, end_ns, name[, stats])`` of each event of a line."""
        t0 = line.timestamp_ns
        names = self.event_names
        for e in line.events:
            s = t0 + e.offset_ps * 1e-3
            out = (s, s + e.duration_ps * 1e-3, names.get(e.metadata_id, ""))
            yield out + (self.stats(e.stats),) if with_stats else out

    def metadata_stats(self) -> dict:
        return {e.value.name: self.stats(e.value.stats)
                for e in self._plane.event_metadata}


# ---------------------------------------------------------------------------
# Names
# ---------------------------------------------------------------------------

_MODULE = re.compile(r"^jit_(\w+?)(?:\((\d+)\))?$")


def program_of(module: str) -> str | None:
    """``rago_decode`` from ``jit_rago_decode(12)``; None for a program
    that is not the serving path's."""
    m = _MODULE.match(module)
    return m.group(1) if m and m.group(1) in PROGRAMS else None


def scope_of(op_name: str | None) -> str | None:
    """The innermost named scope in an operation's ``op_name`` metadata
    (``jit(rago_decode)/kv_write/while/body/closed_call/ffn/dot_general``
    is ``ffn``)."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return None


_SHAPE = re.compile(r"^%?[^ ]+ = [a-z0-9]+\[([0-9,]*)\]")


def out_dims(event_name: str) -> tuple | None:
    """The dimensions of an operation's array output, from the HLO text a
    TPU trace names it by; None for a tuple or a bare name."""
    m = _SHAPE.match(event_name)
    if not m:
        return None
    return tuple(int(d) for d in m.group(1).split(",") if d)


def _hlo_op_names(blob: bytes) -> dict:
    """Instruction name -> op_name of one program's HLO; an instruction
    with none takes its first operand's (a copy of a loop's output is
    the loop's)."""
    hlo = _messages()["HloProto"]()
    hlo.ParseFromString(blob)
    by_id, names, operands = {}, {}, {}
    for comp in hlo.hlo_module.computations:
        for ins in comp.instructions:
            by_id[ins.id] = ins.name
            names[ins.name] = ins.metadata.op_name
            operands[ins.name] = list(ins.operand_ids)
    out = {}
    for name in names:
        seen, cur = set(), name
        while not names.get(cur) and operands.get(cur) and cur not in seen:
            seen.add(cur)
            cur = by_id.get(operands[cur][0], "")
        out[name] = names.get(cur) or ""
    return out


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

def _loop_thread(planes):
    """The host line that ran the serving loop (holds ``bench.loop``)."""
    for p in planes:
        if trd.is_device(p.name):
            continue
        loop_ids = {k for k, v in p.event_names.items() if v == trd.HOST_LOOP}
        if not loop_ids:
            continue
        for line in p.lines():
            if any(e.metadata_id in loop_ids for e in line.events):
                return p, line
    return None, None


def reduce_space(xs, pool_rows: int | None = None,
                 row: int | None = None) -> dict | None:
    """Device time by named program and by scope, and idle time by span,
    inside the traced window (the extent of the loop's ``bench.loop``
    spans).  ``pool_rows``/``row``: the page pool's rows (pages times page
    size) and row width, which say which operations move the pool."""
    planes = [_Plane(p) for p in xs.planes]
    devices = sorted((p for p in planes if trd.is_device(p.name) and any(
        line.name == MODULES_LINE for line in p.lines())),
        key=lambda p: p.name)
    host, loop_line = _loop_thread(planes)
    if not devices or loop_line is None:
        return None
    dev = devices[0]
    host_events = list(host.events(loop_line, with_stats=True))
    loops = [e for e in host_events if e[2] == trd.HOST_LOOP]
    lo, hi = min(e[0] for e in loops), max(e[1] for e in loops)
    hlo = {}
    for p in planes:
        for name, stats in p.metadata_stats().items():
            blob = stats.get("Hlo Proto")
            if isinstance(blob, bytes) and blob:
                hlo[name] = blob
    modules, ops = [], []
    for line in dev.lines():
        if line.name == MODULES_LINE:
            modules = sorted(dev.events(line))
        elif line.name == trd.OPS_LINE:
            ops = sorted(dev.events(line))

    # programs: device time of each named program inside the window
    programs = defaultdict(lambda: {"n": 0, "ms": 0.0})
    busy, other_ms = [], 0.0
    for s, e, name in modules:
        cs, ce = max(s, lo), min(e, hi)
        if ce <= cs:
            continue
        busy.append((cs, ce))
        prog = program_of(name)
        if prog is None:
            other_ms += (ce - cs) * 1e-6
            continue
        if s >= lo and e <= hi:          # whole programs for the means
            programs[prog]["n"] += 1
            programs[prog]["ms"] += (e - s) * 1e-6

    # each named program's operations by scope; the decode step's pool
    op_names = {}
    scopes = defaultdict(lambda: defaultdict(float))
    kv_pool = 0.0
    j = 0
    for s, e, module in modules:
        prog = program_of(module)
        if prog is None or s < lo or e > hi:
            continue
        if module not in op_names:
            op_names[module] = _hlo_op_names(hlo[module]) \
                if module in hlo else {}
        names = op_names[module]
        while j < len(ops) and ops[j][0] < s:
            j += 1
        k = j
        while k < len(ops) and ops[k][0] < e:
            os_, oe, text = ops[k]
            k += 1
            ins = trd.op_name(text)
            if trd.kind(ins) in trd.CONTAINERS:
                continue
            scope = scope_of(names.get(ins))
            label = scope or (trd.kind(ins) if trd.kind(ins) in KERNELS
                              else "unscoped")
            ms = (oe - os_) * 1e-6
            scopes[prog][label] += ms
            dims = out_dims(text)
            if (prog == "rago_decode" and scope in (None, "kv_write")
                    and dims and pool_rows and dims[-1] == row
                    and math.prod(dims[:-1]) % pool_rows == 0):
                kv_pool += ms

    # idle: no program on the device; put down to the innermost rago span
    spans = sorted((trd.Event(host.name, loop_line.name, n, s, e - s)
                    for s, e, n, _ in host_events
                    if n.startswith(SPAN_PREFIX)),
                   key=lambda ev: (ev.start_ns, -ev.dur_ns))
    idle = trd._gaps(busy, lo, hi)
    by_span = defaultdict(float)
    for (s, e), name in zip(idle, trd._innermost(spans, idle)):
        by_span[NO_SPAN if name == "no host span" else name] += (e - s) * 1e-6
    return {
        "window_ms": (hi - lo) * 1e-6,
        "programs": {k: dict(v) for k, v in programs.items()},
        "other_ms": other_ms,
        "idle_ms": sum(by_span.values()),
        "idle_by_span": dict(by_span),
        "by_scope": {p: dict(v) for p, v in scopes.items()},
        "kv_pool_ms": kv_pool,
        "offset_ns": clock_offset(host_events),
    }


def profiler_spans(xs) -> list:
    """``(start_ns, end_ns, name, stats)`` of every ``rago.*`` span on the
    host's lines."""
    out = []
    for p in map(_Plane, xs.planes):
        if trd.is_device(p.name):
            continue
        for line in p.lines():
            out += [e for e in p.events(line, with_stats=True)
                    if e[2].startswith(SPAN_PREFIX)]
    return out


def clock_offset(events) -> float | None:
    """The profiler's clock less ``time.monotonic`` (ns): the median over
    the ``rago.*`` spans of their start less the ``mono_ns`` they carry."""
    offsets = [s - st["mono_ns"] for s, _, n, st in events
               if n.startswith(SPAN_PREFIX) and "mono_ns" in st]
    return statistics.median(offsets) if offsets else None


# ---------------------------------------------------------------------------
# From a run (what the readers call)
# ---------------------------------------------------------------------------

def trace_path(run) -> Path | None:
    """The traced run's ``.xplane.pb``: ``run["trace"]["path"]`` where the
    harness records it, else the profile the harness left in the newest
    ``bench_trace_*`` directory under TMPDIR that was written after this
    run's traced window opened."""
    tr = run.get("trace")
    if not tr:
        return None
    if tr.get("path"):
        return Path(tr["path"])
    wall_t0 = time.time() - (time.monotonic() - tr["t0"])
    found = [p for d in Path(tempfile.gettempdir()).glob("bench_trace_*")
             for p in d.rglob("*.xplane.pb") if p.stat().st_mtime >= wall_t0]
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def pool_geometry(run) -> tuple[int, int]:
    """(rows, row width) of the cell's page pool, as the harness deploys
    it: the engine's page size, ``decode_slots`` slots of ``s_max``
    positions and the traffic's spare pages."""
    from repro.serving.engine import EngineConfig
    m, mix, cell = run["model"], run["mix"], run["cell_cfg"]
    page = EngineConfig.page_size
    pages = (cell["decode_slots"] * -(-mix["s_max"] // page)
             + mix["kv_spare_pages"])
    return pages * page, m["num_key_value_heads"] * m["head_dim"]


@functools.lru_cache(maxsize=4)
def _summary_of(path: str, pool_rows: int, row: int):
    return reduce_space(read_space(path), pool_rows, row)


def summary(run) -> dict | None:
    path = trace_path(run)
    if path is None:
        return None
    return _summary_of(str(path), *pool_geometry(run))


def _count(s: dict, program: str) -> int:
    return s["programs"].get(program, {}).get("n", 0)


def program_ms(run, programs, per: str) -> float | None:
    """Device ms of the ``programs`` in the traced window over the number
    of ``per`` programs there; None without a trace or such a program."""
    s = summary(run)
    if s is None or not _count(s, per):
        return None
    return sum(s["programs"].get(p, {}).get("ms", 0.0)
               for p in programs) / _count(s, per)


def per_decode(run, key: str) -> float | None:
    """The summary's ``key`` (ms) over the decode programs in the traced
    window; None without a trace, a decode program or a reading."""
    s = summary(run)
    if s is None or not _count(s, "rago_decode") or not s[key]:
        return None
    return s[key] / _count(s, "rago_decode")
