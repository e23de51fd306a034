"""The harness end to end on the CPU, at a tiny size, with the Pallas
kernels in interpret mode: the result line carries exactly the keys the
benchmark's contract names, and a configuration, a traffic mix and a
metric added as files are found by name with no edit to any file that
is there."""

import hashlib
import json
import shutil

import pytest

from bench import harness
from bench.tests import tinybench

KEYS = ["correct", "attempted", "failed", "metrics", "device",
        "compiles_in_window", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinybench.make_root(tmp_path_factory.mktemp("root"))


def _line(result):
    return json.loads(harness.result_line(result))


@pytest.mark.parametrize("cell", [tinybench.OPEN, tinybench.CLOSED])
def test_result_line_keys(root, cell):
    line = _line(tinybench.run(root, cell))
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    spec = harness.load_spec(cell, root=root, bench_dir=root / "bench")
    assert sorted(line["metrics"]) == sorted(m["name"]
                                             for m in spec.end_to_end)
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_result_line(root):
    line = _line(tinybench.run(root, tinybench.OPEN, trace=True))
    assert list(line) == KEYS[:5] + ["breakdown"] + KEYS[5:]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # per-layer metrics that a CPU run can read (not the device's)
    assert {"queue_wait_p90_s", "retrieval_ms.ttft", "prefill_ms.ttft",
            "decode_step_ms"} <= set(line["metrics"])
    assert "ttft_p90_s" not in line["metrics"]


def _digest(path):
    return {p.relative_to(path): hashlib.sha1(p.read_bytes()).hexdigest()
            for p in sorted(path.rglob("*")) if p.is_file()}


def test_new_files_found_by_name(root, tmp_path):
    """A new configuration, traffic mix, cell and metric, each a file of
    its own plus entries in BENCHMARK.json."""
    new = tinybench.make_root(tmp_path)
    before = _digest(new / "bench")
    bench = new / "bench"
    cfg = json.loads((bench / "configs" / "tiny-gqa.json").read_text())
    cfg["name"] = "tiny-mha"
    cfg["num_key_value_heads"] = cfg["num_attention_heads"]
    (bench / "configs" / "tiny-mha.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "tiny-open.json").read_text())
    mix["output_tokens"] = [2, 4]
    (bench / "traffic" / "tiny-short.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny-mha.short.json").write_text(
        json.dumps({"decode_slots": 2, "rate_per_s": 6,
                    "limits": tinybench.LIMITS}))
    (bench / "metrics" / "tokens_per_s.py").write_text(
        "def read(run):\n"
        "    t0, t1 = run['window']\n"
        "    n = sum(1 for r in run['all_requests'] for t in r['times']\n"
        "            if t0 <= t < t1)\n"
        "    return n / run['seconds']\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-mha", "source": "test",
                            "file": "bench/configs/tiny-mha.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-mha.short", "config": "tiny-mha",
                              "traffic": "tiny-short", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "tokens_per_s", "unit": "tokens/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["tiny-mha.short"]})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench)
    assert all(after[p] == d for p, d in before.items())
    line = _line(tinybench.run(new, "tiny-mha.short"))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "tokens_per_s"}
    assert line["metrics"]["tokens_per_s"]["value"] > 0
    shutil.rmtree(new)


@pytest.mark.parametrize("block", [None, 8])
def test_seed_reorders_fixed_work(block):
    """Two seeds draw the same work, block by block, in another order."""
    from bench.traffic import Traffic
    mix = json.loads((harness.BENCH_DIR / "traffic" / "rag-decode.json")
                     .read_text())
    mix.pop("shuffle_block")
    if block:
        mix["shuffle_block"] = block
    a, b = (Traffic(mix, {"rate_per_s": 2.4}, s, 100, 60.0)
            for s in (7, 2**40 + 3))
    n = len(a)
    step = block or n
    for k in range(0, n, step):
        ia, ib = a.items[k:k + step], b.items[k:k + step]
        for f in ("q_len", "n_out", "gap_s"):
            assert sorted(getattr(i, f) for i in ia) == pytest.approx(
                sorted(getattr(i, f) for i in ib))
        assert sum(i.gap_s for i in ia) == pytest.approx(len(ia) / 2.4)
    assert [i.n_out for i in a.items] != [i.n_out for i in b.items]
    outs = [i.n_out for i in a.items[:step]]
    assert min(outs) == mix["output_tokens"][0]
    assert max(outs) == mix["output_tokens"][1]
