"""The trace readers at the ``chatglm3-6b.rag-decode`` cell's shapes: 2 KV
heads of 128 (page rows 256 wide, half of granite's 512) over 28 layers.

The paged kernel's work is a hand count, and the pool-traffic rule of
``bench/program_trace.py`` (an operation's output ends in the pool's row
width and its leading dimensions are whole pools' rows) is read on a
hand-made trace of one decode program whose operations carry chatglm3's
shapes: the scatter of new rows and the per-layer slice of the pool count,
the layer scan's slices of ``wk`` and ``wv`` (4096 x 256, the same last
dimension) do not."""

import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace_reduce as trd

CELL = "chatglm3-6b.rag-decode"


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(CELL)


def test_paged_attention_work_hand_count(spec):
    m = spec.model
    contexts = [400, 517, 683]
    flops, nbytes = trd.paged_attention_work(
        contexts, n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"])
    tokens = sum(contexts)
    # K and V of every cached token: 2 KV heads of 128 in bf16, per layer
    kv = 2 * tokens * 2 * 128 * 2
    # each sequence's query and output: 32 heads of 128 in bf16
    qo = 2 * len(contexts) * 32 * 128 * 2
    assert nbytes == 28 * (kv + qo)
    assert flops == 28 * 4 * tokens * 32 * 128


def test_pool_geometry(spec):
    run = {"model": spec.model, "mix": spec.mix, "cell_cfg": spec.cell}
    # 32 slots of 768 positions in pages of 16, and 16 spare pages
    assert pt.pool_geometry(run) == ((32 * 48 + 16) * 16, 256)


# (operation as the TPU trace names it, ns, moves the pool)
OPS = [
    ("%scatter.1 = bf16[28,1552,16,256]{3,2,1,0} scatter(%p.1)", 1000, True),
    ("%dynamic-slice_bitcast_fusion.2 = bf16[1552,16,256]{2,1,0} "
     "fusion(%p.2)", 2000, True),
    ("%constant_dynamic-slice_fusion.3 = bf16[4096,256]{1,0} fusion(%p.3)",
     500, False),
    ("%constant_dynamic-slice_fusion.4 = bf16[1,4096,256]{2,1,0} "
     "fusion(%p.4)", 700, False),
    ("%fusion.5 = bf16[32,256]{1,0} fusion(%p.5)", 300, False),
]


def _event(mid, start_ns, dur_ns):
    return ("events { metadata_id: %d offset_ps: %d duration_ps: %d }"
            % (mid, start_ns * 1000, dur_ns * 1000))


def _metadata(mid, name):
    return ('event_metadata { key: %d value { id: %d name: "%s" } }'
            % (mid, mid, name))


def _trace() -> str:
    """One ``jit_rago_decode`` program of 10 us holding ``OPS`` one after
    another, inside one ``bench.loop`` span on the host."""
    ops, meta, t = [], [_metadata(1, "jit_rago_decode(1)")], 0
    for i, (name, dur, _) in enumerate(OPS):
        ops.append(_event(i + 2, t, dur))
        meta.append(_metadata(i + 2, name))
        t += dur
    device = ('planes { name: "/device:TPU:0" '
              'lines { name: "XLA Modules" timestamp_ns: 0 %s } '
              'lines { name: "XLA Ops" timestamp_ns: 0 %s } %s }'
              % (_event(1, 0, 10000), " ".join(ops), " ".join(meta)))
    host = ('planes { name: "/host:CPU" '
            'lines { name: "python" timestamp_ns: 0 %s } %s }'
            % (_event(1, 0, 10000), _metadata(1, trd.HOST_LOOP)))
    return device + "\n" + host


def test_pool_rule_leaves_out_weight_slices(spec):
    from jax.profiler import ProfileData
    xs = pt.parse(ProfileData.text_proto_to_serialized_xspace(_trace()))
    run = {"model": spec.model, "mix": spec.mix, "cell_cfg": spec.cell}
    s = pt.reduce_space(xs, *pt.pool_geometry(run))
    assert s["programs"]["rago_decode"] == {"n": 1,
                                            "ms": pytest.approx(10e-3)}
    want = sum(dur for _, dur, pool in OPS if pool) * 1e-6
    assert s["kv_pool_ms"] == pytest.approx(want)
