"""Training substrate: checkpoint/restart, compression, elasticity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, hst, settings

from repro.training import checkpoint as ck
from repro.training import compression as comp
from repro.training.elastic import (ElasticMesh, StragglerMonitor,
                                    plan_mesh_shape)
from repro.training.optim import AdamWConfig, adamw_update, init_opt_state
from repro.training.train_loop import TrainConfig, init_state, train


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 4)),
            "b": {"x": jnp.arange(6.0), "n": jnp.zeros((), jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ck.save(tmp_path, 7, t)
    restored, step = ck.restore(tmp_path, t)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_skips_uncommitted(tmp_path):
    ck.save(tmp_path, 1, _tree())
    # fake a torn checkpoint at a later step
    d = tmp_path / "step_00000009"
    d.mkdir()
    (d / "leaf_00000.npy").write_bytes(b"garbage")
    assert ck.latest_step(tmp_path) == 1


def test_checkpoint_prune(tmp_path):
    for s in (1, 2, 3, 4, 5):
        ck.save(tmp_path, s, _tree())
    ck.prune(tmp_path, keep=2)
    assert ck.latest_step(tmp_path) == 5
    _, step = ck.restore(tmp_path, _tree())
    assert step == 5


def test_async_checkpointer(tmp_path):
    w = ck.AsyncCheckpointer(tmp_path, keep=2)
    for s in (10, 20):
        w.save(s, _tree(s))
    w.wait()
    assert ck.latest_step(tmp_path) == 20


def test_train_restart_resumes(tmp_path):
    """Kill-and-restart: second run continues from the checkpoint."""
    cfg = AdamWConfig(lr=1e-2)
    params = {"w": jnp.zeros((4,))}

    def loss(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)

    batches = [jnp.ones(4)] * 10
    state = init_state(params)
    tc = TrainConfig(steps=6, ckpt_dir=str(tmp_path), ckpt_every=3)
    _, hist1 = train(state, batches, loss, tc, cfg)
    assert ck.latest_step(tmp_path) == 6
    # restart with more steps: resumes at 6, runs to 10
    tc2 = TrainConfig(steps=10, ckpt_dir=str(tmp_path), ckpt_every=5)
    _, hist2 = train(init_state(params), batches, loss, tc2, cfg)
    assert hist2[0]["step"] == 7
    assert hist2[-1]["step"] == 10


def test_loss_decreases():
    params = {"w": jnp.zeros((4,))}

    def loss(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)

    _, hist = train(init_state(params), [jnp.ones(4)] * 30, loss,
                    TrainConfig(steps=30),
                    AdamWConfig(lr=5e-2, weight_decay=0.0,
                                warmup_steps=1))
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.5


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(hst.integers(0, 1000))
def test_int8_compression_error_bound(seed):
    g = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * 10
    q, s = comp.compress_int8(g)
    deq = comp.decompress_int8(q, s)
    amax = float(jnp.abs(g).max())
    assert float(jnp.abs(g - deq).max()) <= amax / 127.0 + 1e-6


def test_error_feedback_reduces_bias():
    g = jax.random.normal(jax.random.PRNGKey(0), (128,))
    r = jnp.zeros(128)
    total_true = jnp.zeros(128)
    total_sent = jnp.zeros(128)
    for _ in range(50):
        total_true = total_true + g
        sent, r = comp.with_error_feedback(g, r)
        total_sent = total_sent + sent
    # accumulated transmitted gradient tracks the true sum within residual
    err = float(jnp.abs(total_true - total_sent).max())
    assert err <= float(jnp.abs(g).max()) / 127.0 * 55  # ~1 step of noise


def test_compressed_psum_single_device():
    # axis of size 1: compressed psum must be ~identity
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.distributed.compat import shard_map
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    g = jax.random.normal(jax.random.PRNGKey(0), (16,))
    out = shard_map(lambda x: comp.compressed_psum(x, "d"), mesh=mesh,
                    in_specs=P(), out_specs=P())(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.02)


# ---------------------------------------------------------------------------
# Elasticity + stragglers
# ---------------------------------------------------------------------------

def test_plan_mesh_shape():
    assert plan_mesh_shape(512, 16) == (32, 16)
    assert plan_mesh_shape(511, 16) == (16, 16)   # drop to largest pow2
    assert plan_mesh_shape(16, 16) == (1, 16)
    with pytest.raises(ValueError):
        plan_mesh_shape(8, 16)


def test_elastic_mesh_single_device():
    em = ElasticMesh(model_parallel=1)
    assert em.mesh.shape == {"data": 1, "model": 1}
    from jax.sharding import PartitionSpec as P
    t = {"w": jnp.ones((4, 4))}
    out = em.reshard(t, {"w": P()})
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones((4, 4)))


def test_straggler_monitor_detects_and_evicts():
    m = StragglerMonitor(threshold=3.0, patience=2)
    for step in range(3):
        for h in ("a", "b", "c", "d"):
            m.record(h, 1.0 + 0.01 * step)
        m.record("slow", 10.0)
        flagged = m.stragglers()
        assert "slow" in flagged
    assert "slow" in m.should_evict()
    assert "a" not in m.should_evict()
