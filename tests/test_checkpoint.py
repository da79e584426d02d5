"""Checkpoint/resume tests (reference resume-consistency contract)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu.training.checkpoint import (
    CheckpointManager,
    restore_checkpoint,
    save_checkpoint,
)


def _state():
    return {
        "params": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path):
    state = _state()
    p = save_checkpoint(str(tmp_path / "ckpt"), state)
    restored = restore_checkpoint(p, broadcast=False)
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_restore_with_broadcast_replicates(tmp_path):
    bps.init()
    state = _state()
    p = save_checkpoint(str(tmp_path / "ckpt"), state)
    restored = restore_checkpoint(p, broadcast=True)
    w = restored["params"]["w"]
    # replicated on the mesh: one shard per device, all identical
    assert w.sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(w), np.arange(6.0).reshape(2, 3))


def test_manager_rolls_and_restores_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ckpts"), save_every=2, keep=2)
    for step in range(1, 7):
        state = {"w": jnp.full((2,), float(step))}
        mgr.maybe_save(state, step)
    # saved at 2, 4, 6; keep last 2 -> {4, 6}
    assert mgr.steps() == [4, 6]
    restored, step = mgr.restore_latest(broadcast=False)
    assert step == 6
    np.testing.assert_allclose(np.asarray(restored["w"]), 6.0)


def test_manager_empty_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "empty"))
    restored, step = mgr.restore_latest()
    assert restored is None and step == -1


def test_resume_training_continuity(tmp_path):
    """Save mid-training, restore, continue — must equal uninterrupted run."""
    tx = optax.sgd(0.1)

    def step_fn(params, opt_state):
        grads = jax.tree_util.tree_map(lambda p: p * 0.5, params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    params = {"w": jnp.ones(4)}
    opt_state = tx.init(params)
    # uninterrupted: 6 steps
    p_ref, o_ref = params, opt_state
    for _ in range(6):
        p_ref, o_ref = step_fn(p_ref, o_ref)

    # interrupted at 3
    p, o = params, opt_state
    for _ in range(3):
        p, o = step_fn(p, o)
    save_checkpoint(str(tmp_path / "mid"), {"params": p, "opt": o})
    restored = restore_checkpoint(str(tmp_path / "mid"), broadcast=False)
    p, o = restored["params"], restored["opt"]
    # orbax restores lists for tuples; rebuild the optax state structure
    o = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(opt_state), jax.tree_util.tree_leaves(o)
    )
    for _ in range(3):
        p, o = step_fn(p, o)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(p_ref["w"]),
                               rtol=1e-6)


def _world4_step():
    """``(step, params, batch, mesh)``: the data-parallel step at world 4
    over a tree whose every leaf divides by 4, and its batches."""
    from byteps_tpu.training import make_data_parallel_step, shard_batch

    bps.init(devices=jax.devices()[:4])
    mesh = bps.mesh()

    def loss_fn(p, model_state, batch):
        h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2), model_state

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    params = {"w1": jax.random.normal(k1, (8, 32)) * 0.3,
              "b1": jnp.zeros((32,)),
              "w2": jax.random.normal(k2, (32, 4)) * 0.3}

    def batch(i):
        k = jax.random.fold_in(jax.random.PRNGKey(11), i)
        return shard_batch({"x": jax.random.normal(k, (8, 8)),
                            "y": jax.random.normal(k, (8, 4))}, mesh)

    step = make_data_parallel_step(loss_fn, optax.adamw(1e-2), mesh,
                                   partition_bytes=256)
    return step, params, batch, mesh


def test_sharded_update_resumes_from_a_checkpoint_at_world_4(tmp_path):
    """The data-parallel step at world 4 keeps a share's parameter and
    moments on dim-0 shares; a checkpoint holds them whole (logical
    shapes, the chain's treedef), the restore broadcasts them
    replicated, and the step's first call puts them back on their
    shares: the three losses after a save / restore equal an
    uninterrupted run's."""
    from jax.sharding import PartitionSpec

    from byteps_tpu.training.step import TrainState

    step, params, batch, _ = _world4_step()

    def run(state, first, n):
        losses = []
        for i in range(first, first + n):
            state, m = step(state, batch(i))
            losses.append(float(m["loss"]))
        return state, losses

    _, want = run(step.init_state(params), 0, 6)

    state, head = run(step.init_state(params), 0, 3)
    assert head == want[:3]
    mu = state.opt_state[-1][0].mu["w1"]
    assert mu.sharding.spec == PartitionSpec("dp") and mu.shape == (8, 32)
    assert state.params["w1"].sharding.spec == PartitionSpec("dp")
    path = save_checkpoint(str(tmp_path / "mid"), tuple(state))
    template = step.init_state(params)
    restored = restore_checkpoint(path, template=tuple(template),
                                  broadcast=True)
    restored = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template),
        jax.tree_util.tree_leaves(restored))
    assert isinstance(restored, TrainState)
    assert restored.opt_state[-1][0].mu["w1"].sharding.is_fully_replicated
    assert restored.params["w1"].sharding.is_fully_replicated
    resumed, tail = run(restored, 3, 3)
    assert tail == want[3:]
    assert resumed.opt_state[-1][0].mu["w1"].sharding.spec == (
        PartitionSpec("dp"))
    assert resumed.params["w1"].sharding.spec == PartitionSpec("dp")
    assert int(resumed.step) == 6


def test_parameters_on_shares_round_trip_whole(tmp_path):
    """After a step at world 4 every parameter lies on its dim-0 shares;
    ``save_checkpoint`` writes each whole, at its logical shape, and
    ``restore_checkpoint`` gives back the same values bit for bit, as
    the gathered state reads them."""
    from jax.sharding import PartitionSpec

    from byteps_tpu.training.checkpoint import whole_on_every_process

    step, params, batch, _ = _world4_step()
    state, _ = step(step.init_state(params), batch(0))
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.sharding.spec == PartitionSpec("dp")
        assert not leaf.is_fully_replicated
    path = save_checkpoint(str(tmp_path / "shares"), tuple(state))
    restored = restore_checkpoint(path, broadcast=False)
    whole = whole_on_every_process(state.params)
    for k, x in whole.items():
        assert x.is_fully_replicated
        got = np.asarray(restored[0][k])
        assert got.shape == params[k].shape
        np.testing.assert_array_equal(got, np.asarray(x))
    # the save took nothing from the state: the run goes on
    state, m = step(state, batch(1))
    assert np.isfinite(float(m["loss"])) and int(state.step) == 2


def test_save_gathers_sharded_leaves_before_the_root_only_write(
        tmp_path, monkeypatch):
    """In a multi-process job a leaf sharded over the mesh is neither
    fully addressable nor fully replicated, so process 0 cannot read it
    whole: ``save_checkpoint`` gathers such leaves on EVERY process (a
    collective) before the non-roots return, and only fully replicated
    (or host) leaves reach ``np.asarray``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from byteps_tpu.training import checkpoint

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    state = {
        "mu": jax.device_put(jnp.arange(32.0).reshape(8, 4),
                             NamedSharding(mesh, PartitionSpec("dp"))),
        "w": jax.device_put(jnp.ones((8, 4)),
                            NamedSharding(mesh, PartitionSpec())),
        "count": np.int32(3),
    }
    assert not state["mu"].is_fully_replicated

    read = []

    class Numpy:
        """numpy, refusing what one process of several could not read"""

        @staticmethod
        def asarray(x):
            if isinstance(x, jax.Array):
                assert x.is_fully_replicated, x.sharding
            read.append(x)
            return np.asarray(x)

    gathers = []
    whole = checkpoint.whole_on_every_process
    monkeypatch.setattr(checkpoint, "np", Numpy)
    monkeypatch.setattr(checkpoint, "whole_on_every_process",
                        lambda s: gathers.append(1) or whole(s))
    path = save_checkpoint(str(tmp_path / "root"), state)
    assert gathers == [1] and len(read) == 3
    monkeypatch.undo()
    restored = restore_checkpoint(path, broadcast=False)
    np.testing.assert_array_equal(np.asarray(restored["mu"]),
                                  np.arange(32.0).reshape(8, 4))

    # a non-root joins the gather and writes nothing
    monkeypatch.setattr(checkpoint, "whole_on_every_process",
                        lambda s: gathers.append(2) or whole(s))
    monkeypatch.setattr(checkpoint.jax, "process_index", lambda: 1)
    other = save_checkpoint(str(tmp_path / "other"), state)
    assert gathers == [1, 2] and not os.path.exists(other)

    # what the gather returns: the same values, whole on every device
    out = whole(state)
    assert out["mu"].is_fully_replicated and out["w"] is state["w"]
    assert out["count"] is state["count"]
    np.testing.assert_array_equal(np.asarray(out["mu"]),
                                  np.asarray(state["mu"]))
