"""Distribution substrate tests: checkpoint roundtrip + atomicity, elastic
restore, watchdog/preemption fault handling, quantized ring collectives."""
import os
import signal
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.dist.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.dist.compressed import ring_allreduce_quant
from repro.dist.fault import PreemptionGuard, StepWatchdog, retry_step
from repro.train.state import TrainState
from repro.core.embedding.table import EmbeddingTableState


def make_state(seed=0):
    rng = np.random.default_rng(seed)
    dense = {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
             "b": jnp.zeros((4,), jnp.float32)}
    table = EmbeddingTableState(
        rows=jnp.asarray(rng.normal(size=(32, 4)), jnp.float32),
        accum=jnp.zeros((32,), jnp.float32),
    )
    return TrainState(dense, {"step": jnp.zeros((), jnp.int32)}, table,
                      jnp.full((), 7, jnp.int32))


def test_checkpoint_roundtrip():
    state = make_state()
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, 7)
        assert latest_step(d) == 7
        got = restore_checkpoint(d, state)
        np.testing.assert_array_equal(np.asarray(got.dense["w"]),
                                      np.asarray(state.dense["w"]))
        np.testing.assert_array_equal(np.asarray(got.table.rows),
                                      np.asarray(state.table.rows))
        assert int(got.step) == 7


def test_checkpoint_latest_and_overwrite():
    state = make_state()
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, 5)
        save_checkpoint(d, state, 10)
        assert latest_step(d) == 10
        # incomplete (no manifest) dirs are ignored
        os.makedirs(os.path.join(d, "step_99"))
        assert latest_step(d) == 10


def test_checkpoint_shape_mismatch_rejected():
    state = make_state()
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, 1)
        bad = state._replace(dense={"w": jnp.zeros((9, 4)), "b": state.dense["b"]})
        with pytest.raises(ValueError):
            restore_checkpoint(d, bad)


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(factor=3.0, warmup=2)
    for i in range(5):
        assert not wd.observe(i, 0.1)
    assert wd.observe(5, 1.0)  # 10x EMA
    assert len(wd.events) == 1
    # EMA not polluted by the outlier
    assert wd.ema < 0.2


def test_preemption_guard():
    g = PreemptionGuard(signals=())
    assert not g.should_checkpoint
    g.trigger()
    assert g.should_checkpoint
    g.restore()


def test_retry_step():
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return x + 1

    assert retry_step(flaky, 41, retries=3, backoff_s=0.0) == 42
    assert calls["n"] == 3


def test_retry_step_exhausts():
    def always(x):
        raise RuntimeError("hard")

    with pytest.raises(RuntimeError):
        retry_step(always, 0, retries=1, backoff_s=0.0)


def test_ring_allreduce_quant_single_axis():
    """Degenerate 1-device ring: exact identity."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("d",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(17,)), jnp.float32)

    def f(v):
        out, res = ring_allreduce_quant(v, "d")
        return out, res

    out, res = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=(P(), P()),
                                 check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
    np.testing.assert_allclose(np.asarray(res), 0.0)


def test_ring_allreduce_quant_arbitrary_shapes():
    """Non-1-D leaves ravel through the ring and reshape back: shape and
    (1-device) values preserved exactly, residual zero."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("d",))
    rng = np.random.default_rng(1)
    for shape in ((4, 5), (2, 3, 7), (1, 1), (6,)):
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        f = lambda v: ring_allreduce_quant(v, "d")
        out, res = jax.jit(shard_map(f, mesh=mesh, in_specs=P(),
                                     out_specs=(P(), P()),
                                     check_vma=False))(x)
        assert out.shape == shape and res.shape == shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))
        np.testing.assert_allclose(np.asarray(res), 0.0)


def test_ring_allreduce_quant_tree():
    """Pytree lift: every leaf reduced, structure preserved on both the
    summed tree and the residual tree."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.dist import ring_allreduce_quant_tree
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("d",))
    rng = np.random.default_rng(2)
    tree = {"w": jnp.asarray(rng.normal(size=(3, 4)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32),
            "blocks": [jnp.asarray(rng.normal(size=(2, 2, 2)), jnp.float32)]}

    def f(t):
        return ring_allreduce_quant_tree(t, "d")

    summed, resid = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(), out_specs=(P(), P()),
        check_vma=False))(tree)
    assert jax.tree.structure(summed) == jax.tree.structure(tree)
    assert jax.tree.structure(resid) == jax.tree.structure(tree)
    for leaf, orig in zip(jax.tree.leaves(summed), jax.tree.leaves(tree)):
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(orig))
    for leaf in jax.tree.leaves(resid):
        np.testing.assert_allclose(np.asarray(leaf), 0.0)
