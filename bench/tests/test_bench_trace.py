"""Trace reduction and the roofline arithmetic, on committed traces: a
hand-made one with known answers, and a slice of a chip trace of the
``granite-3-2b.rag-decode`` cell (TPU v5 lite) with the kernels' names as
the compiled program carries them."""

from pathlib import Path

import pytest

from bench import trace_reduce as trd

DATA = Path(__file__).resolve().parent / "data"


def test_handmade_trace():
    red = trd.reduce_trace(trd.load(DATA / "handmade.pbtxt"))
    assert red["planes"] == ["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(4000e-9)
    assert red["op_s"]["paged_decode_kernel"] == pytest.approx(1000e-9)
    # the module line does not count twice
    assert "jit_step" not in red["op_s"]
    assert dict(red["device_ops"]) == pytest.approx(
        {"fusion": 4000e-9, "paged_decode_kernel": 1000e-9})
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.step": 1000e-9, "bench.wait": 5000e-9})
    assert trd.kernel_seconds(red["op_s"], "paged_decode_kernel") == \
        pytest.approx(1000e-9)
    assert trd.kernel_seconds(red["op_s"], "pq_scan") is None


def test_union_and_gaps():
    assert trd.union_ns([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert trd._gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_peaks_table():
    assert trd.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        trd.peaks("cpu")


def test_work_arithmetic():
    # two sequences at 100 and 300 cached tokens, 2 layers, 4 query heads
    # over 2 KV heads of 8: K and V bytes 2*400*2*8*2, q and out 2*2*4*8*2
    flops, nbytes = trd.paged_attention_work(
        [100, 300], n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8)
    assert nbytes == 2 * (2 * 400 * 2 * 8 * 2 + 2 * 2 * 4 * 8 * 2)
    assert flops == 2 * 4 * 400 * 4 * 8
    adds, nbytes = trd.pq_scan_work(3, nprobe=2, list_len=16, n_subq=4)
    assert nbytes == 3 * (2 * 16 * 4 + 2 * 4 * 256 * 4 + 2 * 16 * 4)
    assert trd.decoder_flops(10, 2, 4, 8, [1, 2, 3]) == \
        2 * 10 * 3 + 4 * 2 * 4 * 8 * 6
    peak = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert trd.roofline_share(50.0, 20.0, 4.0, peak) == 50.0   # bytes bound


def test_chip_slice():
    """The kernels' names in a real trace are the ones the roofline
    reader looks for, and the Pallas PQ scan's (kept for its roofline once
    a cell runs the scan)."""
    from bench import harness
    red = trd.reduce_trace(trd.load(DATA / "v5e_decode_slice.pbtxt"))
    assert red["planes"] == ["/device:TPU:0"]
    assert 0 < red["busy_s"] <= sum(red["op_s"].values())
    mod = harness._load_module(
        harness.BENCH_DIR / "metrics" / "paged_attention_roofline.py", "m")
    assert trd.kernel_seconds(red["op_s"], mod.KERNEL) == \
        pytest.approx(493.62e-6)
    assert trd.kernel_seconds(red["op_s"], "pq_scan") == \
        pytest.approx(169.473e-6)
