"""The program's own names on the device trace (``bench/program_trace.py``
and the five readers built on it): a hand-made trace with known answers,
and the shared clock of the engine's profiler spans and its SpanTracer,
on the CPU."""

from pathlib import Path

import pytest

from bench import harness
from bench import program_trace as pt

DATA = Path(__file__).resolve().parent / "data"
HANDMADE = DATA / "program_handmade.pbtxt"

# the hand-made trace's pool: 1 slot of s_max 16 and 1 spare page of 16
# rows, 2 KV heads of 4 (its header has the sums)
READINGS = {"decode_device_ms": 10e-3, "kv_pool_ms": 3e-3,
            "decode_idle_ms": 6.5e-3, "retrieval_device_ms.ttft": 2e-3,
            "prefill_device_ms.ttft": 4e-3}


def _run(path) -> dict:
    return {"trace": {"path": str(path)},
            "model": {"num_key_value_heads": 2, "head_dim": 4},
            "mix": {"s_max": 16, "kv_spare_pages": 1},
            "cell_cfg": {"decode_slots": 1}}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_handmade_reading(metric):
    read = harness.reader(harness.BENCH_DIR, metric)
    assert read(_run(HANDMADE)) == pytest.approx(READINGS[metric])


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_no_named_program_reads_none(metric):
    """A trace without the program's names (as the parent's) and a run
    without a trace read nothing and raise nothing."""
    read = harness.reader(harness.BENCH_DIR, metric)
    assert read(_run(DATA / "handmade.pbtxt")) is None
    assert read({"trace": None}) is None


def test_handmade_breakdown():
    s = pt.reduce_space(pt.read_space(HANDMADE), pool_rows=32, row=8)
    assert s["window_ms"] == pytest.approx(40e-3)
    assert s["by_scope"]["rago_decode"] == pytest.approx(
        {"attention": 8e-3, "kv_write": 7e-3, "ffn": 3e-3, "head": 2e-3})
    assert s["by_scope"]["rago_prefill"] == {"unscoped": 4e-3}
    # the gap under a Python frame goes to the span around it
    assert s["idle_by_span"] == pytest.approx(
        {"rago.admit": 1e-3, "rago.decode.prepare": 1e-3,
         "rago.decode.fetch": 2e-3, "rago.deliver": 1e-3,
         pt.NO_SPAN: 8e-3})
    programs = sum(p["ms"] for p in s["programs"].values())
    assert programs + s["other_ms"] + s["idle_ms"] == \
        pytest.approx(s["window_ms"])
    assert s["offset_ns"] == 1e9


def test_chip_slice():
    """The program, scope and kernel names the readers look for are the
    ones the compiled program carries on the chip."""
    path = DATA / "v5e_program_slice.pbtxt"
    s = pt.reduce_space(pt.read_space(path), pool_rows=24832, row=512)
    assert set(s["programs"]) == {"rago_encode", "rago_search",
                                  "rago_prefill", "rago_page_install",
                                  "rago_decode"}
    assert s["programs"]["rago_decode"]["n"] == 2
    assert {"embed", "kv_write", "attention", "ffn", "head"} <= \
        set(s["by_scope"]["rago_decode"])
    assert {"coarse", "adc", "topk"} <= set(s["by_scope"]["rago_search"])
    # the whole-pool copy, the scan's slice and write-back and the scatter
    assert s["kv_pool_ms"] > 0
    assert set(s["idle_by_span"]) <= {"rago.decode.prepare",
                                      "rago.decode.fetch", "rago.retrieve",
                                      "rago.prefill"}
    from bench import trace_reduce as trd
    op_s = trd.reduce_trace(trd.load(path))["op_s"]
    assert trd.kernel_seconds(op_s, "paged_decode_attention") is not None


def test_names():
    assert pt.program_of("jit_rago_decode(12)") == "rago_decode"
    assert pt.program_of("jit_rago_prefill") == "rago_prefill"
    assert pt.program_of("jit_forward(3)") is None
    assert pt.scope_of("jit(rago_decode)/kv_write/while/body/closed_call/"
                       "ffn/dot_general") == "ffn"
    assert pt.scope_of("jit(rago_decode)/kv_write/while/body/"
                       "dynamic_slice") == "kv_write"
    assert pt.scope_of("") is None
    assert pt.out_dims("%copy.7 = bf16[40,1552,16,512]{3,2,1,0} copy(%x)") \
        == (40, 1552, 16, 512)
    assert pt.out_dims("%t = (s32[], f32[2]) tuple(%a, %b)") is None


def test_spans_share_the_profiler_clock(tmp_path):
    """Moved by the offset their own metadata gives, the engine's
    ``rago.decode`` profiler spans land within 1 ms of the SpanTracer's
    ``DECODE_TICK`` records of the same ticks."""
    import jax

    from bench.tests import tinybench
    from repro.serving.telemetry import SpanTracer
    root = tinybench.make_root(tmp_path / "root")
    spec = harness.load_spec(tinybench.OPEN, root=root,
                             bench_dir=root / "bench")
    server = harness.build_server(spec, harness.make_data(spec.model, 5),
                                  attn_impl="ref", use_pq_kernel=False)
    server.submit([1, 2, 3, 4], max_new_tokens=2)
    server.run_until_idle()                 # compiles outside the trace
    tracer = SpanTracer()
    server.set_tracer(tracer)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    for q in ([5, 6, 7, 8], [9, 10, 11, 12, 13]):
        server.submit(q, max_new_tokens=4)
    server.run_until_idle()
    jax.profiler.stop_trace()
    xs = pt.read_space(next((tmp_path / "trace").rglob("*.xplane.pb")))
    spans = pt.profiler_spans(xs)
    offset = pt.clock_offset(spans)
    ticks = {s.tick: s.t0 for s in tracer.spans() if s.kind == "DECODE_TICK"}
    decode = [(start, st["tick"]) for start, _, name, st in spans
              if name == "rago.decode"]
    assert len(decode) == len(ticks) > 0
    for start, tick in decode:
        assert abs((start - offset) * 1e-9 - ticks[tick]) < 1e-3
    names = {name for _, _, name, _ in spans}
    assert {"rago.admit", "rago.embed", "rago.retrieve", "rago.prefill",
            "rago.decode.prepare", "rago.decode.launch",
            "rago.decode.fetch", "rago.decode.commit",
            "rago.deliver"} <= names
