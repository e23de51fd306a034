"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases run end to end at a tiny size with the Pallas kernels forced on in
interpret mode (the rehearsal before every chip run)."""

import json
import sys
from pathlib import Path

import jax
import pytest

from repro.models import transformer as tr

REPO = Path(__file__).resolve().parents[1]


def _chip_smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    return chip_smoke


def test_refuses_without_tpu(capsys):
    """No TPU: exit code 1 and no result line -- nothing falls back to the
    CPU."""
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    assert _chip_smoke().main([]) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert not any(json.loads(line).get("ok") for line in out)


@pytest.mark.slow
def test_phases_tiny_interpret():
    """Every phase serves its requests to DONE, the live-pool kernel check
    and the PQ-scan check pass, and each phase reports its numbers."""
    cs = _chip_smoke()
    cfg = tr.TransformerConfig(name="smoke-tiny", n_layers=2, d_model=48,
                               n_heads=4, n_kv_heads=2, d_head=16, d_ff=64,
                               vocab_size=128)
    sizes = cs.Sizes(n_docs=64, doc_len=10, retrieval_k=2, s_max=96,
                     max_new_tokens=6, decode_slots=3, kv_spare_pages=4,
                     n_baseline=4, n_iterative=2, n_disagg=2,
                     iterative_interval=2)
    lines = cs.run_phases(cfg, sizes, seed=0, attn_impl="pallas",
                          use_pq_kernel=True)
    by = {ln["phase"]: ln for ln in lines}
    assert list(by) == ["build", "baseline", "checks", "iterative", "disagg"]
    assert by["baseline"]["attn_impl"] == "pallas"
    assert by["baseline"]["pq_kernel"] is True
    assert by["baseline"]["attn_check"]["live_slots"] == 3
    assert by["iterative"]["retrievals"] > 0
    assert by["disagg"]["handoffs"] == 2
    assert all(ln["compile_s"] >= 0 for ln in lines)
