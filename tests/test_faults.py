"""Fault injection, recovery, and graceful degradation.

Fast tier: FaultInjector occurrence counting / determinism, the handoff
payload checksum, the extended request lifecycle (RETRYING / FAILED,
double-terminal prevention, retry reset), and the retrieval fallback
ladder over stub backends.

Slow tier (builds engines): the deterministic chaos matrix -- every named
schedule in ``CHAOS_SCHEDULES`` runs against a 2-prefill + 2-decode
cluster and must leave EVERY submitted request in exactly one terminal
state with no leaked slots or pages, and every non-degraded DONE request
bit-identical to the unfaulted run (retry parity).  Plus targeted tests
for each degradation path: no-context answers, whole-group death,
brownout shedding, retry-budget exhaustion, backoff expiry, and the
server-level stall / abort semantics.
"""

import time
from dataclasses import replace

import numpy as np
import pytest

from repro.models import transformer as tr
from repro.serving.faults import (CHAOS_SCHEDULES, EngineHealth, FaultInjector,
                                  FaultPlan, FaultSpec)
from repro.serving.kv_cache import PagedKVCachePool, payload_checksum
from repro.serving.request import (LEGAL_TRANSITIONS, TERMINAL_STATES,
                                   Request, State)

VOCAB = 64


# ---------------------------------------------------------------------------
# FaultInjector: deterministic occurrence counting (fast)
# ---------------------------------------------------------------------------

def test_injector_fires_on_nth_occurrence():
    inj = FaultInjector(FaultPlan([FaultSpec("decode_crash", at=3)]))
    assert inj.fire("decode_crash") is None
    assert inj.fire("decode_crash") is None
    assert inj.fire("decode_crash") is not None
    assert inj.fire("decode_crash") is None          # window is one-shot
    assert inj.log == [("decode_crash", 3, None, None)]


def test_injector_count_window_and_filters():
    inj = FaultInjector(FaultPlan([
        FaultSpec("handoff_corrupt", at=2, count=2, engine=1),
    ]))
    # engine 0 occurrences never match the spec
    assert inj.fire("handoff_corrupt", engine=0) is None
    assert inj.fire("handoff_corrupt", engine=1) is None      # occurrence 1
    assert inj.fire("handoff_corrupt", engine=1) is not None  # 2: fires
    assert inj.fire("handoff_corrupt", engine=0) is None
    assert inj.fire("handoff_corrupt", engine=1) is not None  # 3: fires
    assert inj.fire("handoff_corrupt", engine=1) is None      # window over
    assert len(inj.log) == 2


def test_injector_rid_filter_and_unknown_point():
    inj = FaultInjector(FaultPlan([FaultSpec("stage_error", rid=7)]))
    assert inj.fire("stage_error", rid=3) is None
    assert inj.fire("stage_error", rid=7) is not None
    with pytest.raises(ValueError):
        FaultInjector(FaultPlan([FaultSpec("not_a_point")]))
    with pytest.raises(ValueError):
        FaultInjector(FaultPlan([FaultSpec("stage_error", at=0)]))


def test_injector_is_deterministic_across_runs():
    """Two injectors built from the same plan fire at identical points and
    corrupt identical bytes -- the property that makes chaos runs CI-able."""
    def run(inj):
        fired = [bool(inj.fire("decode_crash", engine=i % 2))
                 for i in range(6)]
        payload, _ = _exported_prefix()
        inj.corrupt(payload)
        return fired, np.asarray(payload.pages[0]["k"]).copy()

    plan = CHAOS_SCHEDULES["decode_crash"]
    a = run(FaultInjector(FaultPlan.from_schedule(plan, seed=11)))
    b = run(FaultInjector(FaultPlan.from_schedule(plan, seed=11)))
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])
    c = run(FaultInjector(FaultPlan.from_schedule(plan, seed=12)))
    assert not np.array_equal(a[1], c[1])      # seed moves the corruption


# ---------------------------------------------------------------------------
# Handoff checksum (fast)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return tr.TransformerConfig(name="ck", n_layers=2, d_model=32,
                                n_heads=4, n_kv_heads=2, d_head=8,
                                d_ff=64, vocab_size=VOCAB)


def _exported_prefix(prefix_len=11):
    import jax.numpy as jnp
    cfg = _tiny_cfg()
    pool = PagedKVCachePool(cfg, n_slots=2, s_max=16, page_size=8)
    rng = np.random.default_rng(0)
    cache = {k: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, 1, prefix_len, cfg.n_kv_heads, cfg.d_head)),
        jnp.bfloat16) for k in ("k", "v")}
    slot = pool.alloc(rid=1)
    pool.write_prefix(slot, cache, prefix_len)
    kv, length = pool.export_slot(slot)
    pool.release(slot)
    return kv, length


def test_checksum_stable_and_detects_corruption():
    kv, _ = _exported_prefix()
    before = payload_checksum(kv)
    assert payload_checksum(kv) == before          # pure function
    inj = FaultInjector(FaultPlan(seed=3))
    inj.corrupt(kv)
    assert payload_checksum(kv) != before          # single bit flip caught


def test_checksum_detects_corruption_dense_payload():
    """A payload that lost a page on the wire no longer checks out."""
    kv, _ = _exported_prefix()
    assert len(kv.pages) == 2
    before = payload_checksum(kv)
    del kv.pages[1]
    assert payload_checksum(kv) != before


# ---------------------------------------------------------------------------
# Request lifecycle: RETRYING / FAILED (fast)
# ---------------------------------------------------------------------------

def test_legal_transitions_cover_retry_and_failure():
    """Every non-terminal state can enter the recovery path (RETRYING) and
    the forced-failure path (FAILED); terminals go nowhere."""
    for state, allowed in LEGAL_TRANSITIONS.items():
        if state in TERMINAL_STATES:
            assert allowed == frozenset()
        elif state is State.RETRYING:
            assert allowed == frozenset(
                {State.QUEUED, State.EXPIRED, State.FAILED})
        else:
            assert State.FAILED in allowed
            assert State.RETRYING in allowed
    assert TERMINAL_STATES == frozenset(
        {State.DONE, State.EXPIRED, State.FAILED})


def test_retry_lifecycle_walk_is_legal():
    req = Request(question=np.arange(4, dtype=np.int32))
    req.state = State.PREFILL
    req.reset_for_retry(now=100.0, backoff=0.5)
    assert req.state is State.RETRYING
    assert req.retries == 1 and req.t_retry == 100.5
    req.state = State.QUEUED                       # backoff elapsed
    req.state = State.PREFILL
    req.state = State.HANDOFF
    req.state = State.DECODE
    req.state = State.DONE
    assert req.state_history.count(State.RETRYING) == 1


def test_reset_for_retry_clears_per_attempt_state():
    req = Request(question=np.arange(4, dtype=np.int32))
    req.state = State.PREFILL
    req.prompt = np.arange(9, dtype=np.int32)
    req.output = [1, 2]
    req.slot = 3
    req.candidate_ids = np.array([1, 2])
    req.retrievals_done = 2
    req.t_first_token = 5.0
    t_arrive = req.t_arrive
    req.reset_for_retry(now=1.0, backoff=0.0)
    assert req.prompt is None and req.output == [] and req.slot is None
    assert req.candidate_ids is None and req.retrievals_done == 0
    assert req.t_first_token is None
    assert req.t_arrive == t_arrive        # TTFT keeps the recovery delay


def test_double_terminal_is_prevented():
    req = Request(question=np.arange(3, dtype=np.int32))
    req.state = State.PREFILL
    req.state = State.FAILED
    for target in (State.DONE, State.EXPIRED, State.QUEUED,
                   State.RETRYING):
        with pytest.raises(RuntimeError, match="terminal"):
            req.state = target
    assert req.state is State.FAILED


# ---------------------------------------------------------------------------
# Retrieval fallback ladder over stub backends (fast)
# ---------------------------------------------------------------------------

class _StubBackend:
    def __init__(self, name, fill, fail=False):
        self.name, self.fill, self.fail = name, fill, fail
        self.calls = 0

    def search(self, queries, k):
        from repro.retrieval.backend import RetrievalError
        self.calls += 1
        if self.fail:
            raise RetrievalError(self.name)
        n = np.asarray(queries).shape[0]
        return (np.zeros((n, k), np.float32),
                np.full((n, k), self.fill, np.int64))

    @property
    def bytes_per_query(self):
        return 128.0


def test_fallback_chain_transparent_then_degrades():
    from repro.retrieval.backend import FallbackBackend
    primary = _StubBackend("primary", fill=1)
    backup = _StubBackend("backup", fill=2)
    fb = FallbackBackend([primary, backup])
    q = np.zeros((2, 4), np.float32)
    _, ids = fb.search(q, 3)
    assert ids[0, 0] == 1 and fb.last_level == 0    # bit-transparent
    assert fb.metrics == {"fallbacks": 0, "no_context": 0}
    primary.fail = True
    _, ids = fb.search(q, 3)
    assert ids[0, 0] == 2 and fb.last_level == 1    # degraded to backup
    assert fb.metrics["fallbacks"] == 1
    backup.fail = True
    scores, ids = fb.search(q, 3)
    assert fb.last_level == -1                      # no-context
    assert (ids == -1).all() and np.isneginf(scores).all()
    assert fb.metrics["no_context"] == 1


def test_fallback_injected_timeout_skips_primary_only():
    from repro.retrieval.backend import FallbackBackend
    primary = _StubBackend("primary", fill=1)
    backup = _StubBackend("backup", fill=2)
    fb = FallbackBackend([primary, backup])
    fb.injector = FaultInjector(FaultPlan.from_schedule(
        [{"point": "retrieval_timeout", "at": 1}]))
    _, ids = fb.search(np.zeros((1, 4), np.float32), 2)
    assert ids[0, 0] == 2 and primary.calls == 0    # primary timed out
    _, ids = fb.search(np.zeros((1, 4), np.float32), 2)
    assert ids[0, 0] == 1                           # back to primary


def test_fallback_injected_blackout_fails_every_level():
    from repro.retrieval.backend import FallbackBackend
    fb = FallbackBackend([_StubBackend("primary", fill=1)])
    fb.injector = FaultInjector(FaultPlan.from_schedule(
        [{"point": "retrieval_blackout", "at": 1}]))
    _, ids = fb.search(np.zeros((1, 4), np.float32), 2)
    assert (ids == -1).all() and fb.last_level == -1


# ---------------------------------------------------------------------------
# Chaos matrix on a 2+2 cluster (slow)
# ---------------------------------------------------------------------------

def _component(seed, causal=True):
    import jax
    cfg = tr.TransformerConfig(name=f"fz{seed}", n_layers=2, d_model=32,
                               n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
                               vocab_size=VOCAB, causal=causal)
    from repro.serving.engine import Component
    return Component(cfg, tr.init_params(jax.random.PRNGKey(seed), cfg))


@pytest.fixture(scope="module")
def stack():
    from repro.data.synthetic import topical_corpus
    gen = _component(0)
    enc = _component(1, causal=False)
    corpus, _topics, make_q = topical_corpus(32, 8, VOCAB, n_topics=4)
    questions = [make_q(i % 4) for i in range(6)]
    return gen, enc, corpus, questions


def _make_cluster(stack, injector=None, n_prefill=2, n_decode=2, **kw):
    from repro.serving.cluster import RAGCluster
    from repro.serving.engine import EngineConfig, RAGEngine
    gen, enc, corpus, _ = stack
    cluster_kw = {k: kw.pop(k) for k in
                  ("max_retries", "retry_backoff", "brownout_headroom")
                  if k in kw}
    cluster_kw.setdefault("retry_backoff", 0.001)
    kw.setdefault("decode_slots", 2)
    kw.setdefault("s_max", 96)
    kw.setdefault("max_new_tokens", 4)
    cfg = EngineConfig(**kw)
    first = RAGEngine(gen, enc, corpus, replace(cfg, decode_slots=1))
    shared = dict(db_vectors=first.db_vectors, backend=first.backend)
    prefill = [first] + [
        RAGEngine(gen, enc, corpus, replace(cfg, decode_slots=1), **shared)
        for _ in range(n_prefill - 1)]
    decode = [RAGEngine(gen, enc, corpus, cfg, **shared)
              for _ in range(n_decode)]
    return RAGCluster(prefill, decode, injector=injector, **cluster_kw)


def _serve(stack, injector=None, **kw):
    from repro.serving.server import RAGServer
    cluster = _make_cluster(stack, injector, **kw)
    server = RAGServer(cluster)
    handles = [server.submit(q, max_new_tokens=4) for q in stack[3]]
    server.run_until_idle(max_steps=5000)
    return cluster, server, handles


def _assert_no_leaks(cluster):
    """Every pool back to idle: no queued/in-flight work anywhere and all
    page refcounts zero (a leak here means recovery dropped resources)."""
    assert not cluster.queue and not cluster.handoff and not cluster.retrying
    for eng in cluster.prefill_engines + cluster.decode_engines:
        assert not eng.active and not eng.pending_retrievals
        assert not eng.prefilling
        ref = getattr(eng.pool, "ref", None)
        if ref is not None:
            assert int(np.sum(ref)) == 0


@pytest.fixture(scope="module")
def unfaulted(stack):
    """Reference run: outputs every chaos run's survivors must match."""
    cluster, _, handles = _serve(stack)
    assert all(h.request.state is State.DONE for h in handles)
    _assert_no_leaks(cluster)
    return [h.request.output for h in handles]


@pytest.mark.slow
@pytest.mark.parametrize("schedule", sorted(CHAOS_SCHEDULES))
def test_chaos_schedule_terminates_and_recovers(stack, unfaulted, schedule):
    """THE robustness acceptance test: under every named fault schedule,
    every submitted request reaches exactly one terminal state, nothing
    leaks, and every recovered (non-degraded) completion is bit-identical
    to the unfaulted run -- crash recovery is invisible in the tokens."""
    inj = FaultInjector(
        FaultPlan.from_schedule(CHAOS_SCHEDULES[schedule], seed=7))
    cluster, _, handles = _serve(stack, inj)
    assert len(inj.log) > 0, "schedule never fired -- dead chaos test"
    for h in handles:
        assert h.request.state in TERMINAL_STATES
        terminal_entries = [s for s in h.request.state_history
                            if s in TERMINAL_STATES]
        assert len(terminal_entries) == 1          # exactly one terminal
    _assert_no_leaks(cluster)
    for h, expected in zip(handles, unfaulted):
        if h.request.state is State.DONE and not h.request.degraded:
            assert h.request.output == expected    # retry parity


@pytest.mark.slow
def test_decode_crash_recovers_via_reprefill(stack, unfaulted):
    inj = FaultInjector(
        FaultPlan.from_schedule(CHAOS_SCHEDULES["decode_crash"], seed=0))
    cluster, _, handles = _serve(stack, inj)
    assert cluster.metrics["engine_failures"] == 1
    assert cluster.metrics["requests_retried"] >= 1
    assert any(e.health is EngineHealth.DEAD
               for e in cluster.decode_engines)
    # the dead engine's requests finished elsewhere, bit-identically
    assert all(h.request.state is State.DONE for h in handles)
    assert [h.request.output for h in handles] == unfaulted
    # a retried rid passed through decode twice -> history keeps both
    retried = [rid for rid, hist in cluster.decode_history.items()
               if len(hist) > 1]
    assert retried


@pytest.mark.slow
def test_corrupt_handoff_never_decodes(stack, unfaulted):
    """A bit-flipped payload is rejected by checksum and the request
    retried -- outputs still match the unfaulted run exactly."""
    inj = FaultInjector(
        FaultPlan.from_schedule(CHAOS_SCHEDULES["handoff_corrupt"], seed=5))
    cluster, _, handles = _serve(stack, inj)
    assert cluster.metrics["handoff_corrupt"] == 2
    assert all(h.request.state is State.DONE for h in handles)
    assert [h.request.output for h in handles] == unfaulted


@pytest.mark.slow
def test_retrieval_blackout_yields_flagged_degraded_answer(stack):
    inj = FaultInjector(FaultPlan.from_schedule(
        CHAOS_SCHEDULES["retrieval_blackout"], seed=0))
    cluster, _, handles = _serve(stack, inj)
    assert all(h.request.state is State.DONE for h in handles)
    degraded = [h.request for h in handles if h.request.degraded]
    assert degraded                                # someone got no context
    summary = cluster.group_summary()["scheduler"]
    assert summary["retrieval_no_context"] >= 1
    assert summary["degraded_answers"] == len(degraded)


@pytest.mark.slow
def test_retry_budget_exhaustion_fails_terminally(stack):
    """With every handoff dropped, a request can never decode: it must
    end FAILED after max_retries, not spin forever."""
    inj = FaultInjector(FaultPlan.from_schedule(
        [{"point": "handoff_drop", "at": 1, "count": 10_000}]))
    cluster, _, handles = _serve(stack, inj, max_retries=2)
    assert all(h.request.state is State.FAILED for h in handles)
    assert all("retry budget exhausted" in h.request.fail_reason
               for h in handles)
    assert cluster.metrics["retries_exhausted"] == len(handles)
    _assert_no_leaks(cluster)


@pytest.mark.slow
def test_all_decode_engines_dead_fails_waiting_requests(stack):
    """Whole-group death: parking work forever would break the termination
    invariant, so the sweep fails everything still waiting."""
    cluster, server, handles = _serve(stack, None, n_decode=1)
    assert all(h.request.state is State.DONE for h in handles)
    # now resubmit with the lone decode engine pre-killed
    from repro.serving.server import RAGServer
    cluster = _make_cluster(stack, n_decode=1)
    cluster.decode_engines[0].fail("pulled the plug")
    server = RAGServer(cluster)
    handles = [server.submit(q, max_new_tokens=4) for q in stack[3]]
    server.run_until_idle(max_steps=200)
    assert all(h.request.state is State.FAILED for h in handles)
    assert cluster.metrics["failed_no_capacity"] == len(handles)
    _assert_no_leaks(cluster)


@pytest.mark.slow
def test_brownout_sheds_lowest_urgency_first(stack):
    """With a dead decode engine, 1 healthy slot of capacity and headroom
    3.0, only 3 of the 6 queued requests fit the brownout limit -- the
    excess sheds, deadline-free (lowest-urgency) requests first."""
    from repro.serving.server import RAGServer
    gen, enc, corpus, questions = stack
    cluster = _make_cluster(stack, n_decode=2, decode_slots=1,
                            brownout_headroom=3.0)
    cluster.decode_engines[1].fail("injected")
    server = RAGServer(cluster)
    now = time.monotonic()
    with_deadline = [server.submit(q, max_new_tokens=4, deadline=now + 60)
                     for q in questions[:3]]
    no_deadline = [server.submit(q, max_new_tokens=4)
                   for q in questions[3:]]
    server.run_until_idle(max_steps=5000)
    shed = [h for h in with_deadline + no_deadline
            if h.request.fail_reason == "brownout shed"]
    assert cluster.metrics["brownout_shed"] == len(shed) > 0
    # no deadline == lowest urgency: shed before any deadlined request
    assert all(h.request.deadline is None for h in shed)
    assert all(h.request.state is State.DONE for h in with_deadline)
    _assert_no_leaks(cluster)


@pytest.mark.slow
def test_retry_backoff_pool_honors_deadline(stack):
    """A request whose deadline passes while waiting out its retry backoff
    expires there (RETRYING -> EXPIRED) -- the third waiting pool the
    deadline sweep must cover."""
    from repro.serving.server import RAGServer
    inj = FaultInjector(FaultPlan.from_schedule(
        [{"point": "handoff_drop", "at": 1, "count": 10_000}]))
    cluster = _make_cluster(stack, inj, max_retries=50, retry_backoff=30.0)
    server = RAGServer(cluster)
    # deadline long enough to survive first-compile prefill, short enough
    # that it passes while the request waits out the 30 s backoff
    h = server.submit(stack[3][0], max_new_tokens=4,
                      deadline=time.monotonic() + 4.0)
    deadline = h.request.deadline
    while not h.done and time.monotonic() < deadline + 2.0:
        server.step()
        time.sleep(0.01)
    assert h.request.state is State.EXPIRED
    assert State.RETRYING in h.request.state_history
    assert cluster.metrics["expired_retrying"] >= 1
    _assert_no_leaks(cluster)


@pytest.mark.slow
def test_faults_disabled_is_bit_transparent(stack, unfaulted):
    """An injector with an EMPTY plan threaded through every fault point
    changes nothing: same tokens, no fault metrics."""
    inj = FaultInjector(FaultPlan())
    cluster, _, handles = _serve(stack, inj)
    assert [h.request.output for h in handles] == unfaulted
    assert inj.log == []
    m = cluster.metrics
    assert (m["engine_failures"] == m["requests_retried"]
            == m["handoff_corrupt"] == m["handoff_dropped"]
            == m["brownout_shed"] == 0)
