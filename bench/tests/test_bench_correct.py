"""The output check fails what it must: the control (the reference in
float8, the step below the configurations' bf16) and the faults a served
cell can have, each planted under a run that is otherwise whole.

The tiny cells' limits are their own (``tinybench.LIMITS``), set from
their readings on the CPU; the chip cells' limits and the readings they
come from are in PERF.md."""

import pytest

from bench import harness
from bench.tests import tinybench
from bench.traffic import prompt_budget


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinybench.make_root(tmp_path_factory.mktemp("root"))


def _fails(checks: dict) -> bool:
    return any(c["value"] > c["limit"] for c in checks.values())


def test_control_is_not_correct(root):
    spec = harness.load_spec(tinybench.OPEN, root=root,
                             bench_dir=root / "bench")
    seed = 2**31 + 21
    sound = tinybench.run(root, tinybench.OPEN, seed=seed)
    assert sound["correct"] is True
    run = sound["run"]
    t0, t1 = run["window"]
    window = [r for r in run["all_requests"] if t0 <= r["due"] < t1]
    data = harness.make_data(spec.model, seed)
    control = harness.check_outputs(spec, data, window, seed,
                                    prompt_budget(spec.mix), mode="fp8")
    assert _fails(control), control


def _alter_token(monkeypatch):
    """Every decode step's token comes out one id off."""
    from repro.models import transformer as tr
    real = tr.paged_decode_step

    def step(*a, **kw):
        logits, cache = real(*a, **kw)
        return logits[..., list(range(1, logits.shape[-1])) + [0]], cache
    monkeypatch.setattr(tr, "paged_decode_step", step)


def _state_unchanged(monkeypatch):
    """The decode step hands back the pool it was given: no K/V is kept."""
    from repro.models import transformer as tr
    real = tr.paged_decode_step

    def step(params, cache, *a, **kw):
        logits, _ = real(params, cache, *a, **kw)
        return logits, cache
    monkeypatch.setattr(tr, "paged_decode_step", step)


def _alter_answer(monkeypatch):
    """The search returns the id next to each one it found."""
    from repro.retrieval import backend
    real = backend.search

    def search(index, queries, **kw):
        d, ids = real(index, queries, **kw)
        return d, (ids + 1) % index.n_vectors
    monkeypatch.setattr(backend, "search", search)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _alter_answer],
                         ids=["token_altered", "state_unchanged",
                              "answer_altered"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    result = tinybench.run(root, tinybench.OPEN, seed=2**31 + 33)
    assert result["correct"] is False, result["checks"]
    assert _fails(result["checks"])
