"""End-to-end data-parallel training tests on the 8-device CPU mesh.

Behavioral contracts from the reference's tests (SURVEY.md §4): training
loss decreases (non-hanging, converging loop — test_tensorflow_keras.py),
and the data-parallel step equals a single-device step on the concatenated
batch (sum/average correctness — test_mxnet.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import optax
from jax.sharding import Mesh

import byteps_tpu as bps
from byteps_tpu.models import ResNet18
from byteps_tpu.training import (
    classification_loss_fn,
    create_train_state,
    make_data_parallel_step,
    replicate_state,
    shard_batch,
)


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _mlp_loss_fn(params, model_state, batch):
    x, y = batch["image"], batch["label"]
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    return loss, model_state


def _mlp_params(key, din=8, dh=16, dout=4):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (din, dh)) * 0.1,
        "b1": jnp.zeros((dh,)),
        "w2": jax.random.normal(k2, (dh, dout)) * 0.1,
        "b2": jnp.zeros((dout,)),
    }


def test_dp_step_matches_single_device():
    """8-way data-parallel step == single-device step on the full batch."""
    mesh = _mesh()
    key = jax.random.PRNGKey(0)
    params = _mlp_params(key)
    tx = optax.sgd(0.1)

    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (16, 8)),
        "label": jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 4),
    }

    # single-device reference: plain sgd on the full batch
    def ref_step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: _mlp_loss_fn(p, {}, batch)[0]
        )(params)
        return jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads), loss

    ref_params, ref_loss = ref_step(params, batch)

    step = make_data_parallel_step(_mlp_loss_fn, tx, mesh, donate=False)
    state = step.init_state(params)
    new_state, metrics = step(state, shard_batch(batch, mesh))

    np.testing.assert_allclose(float(metrics["loss"]), float(ref_loss), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(new_state.params),
        jax.tree_util.tree_leaves(ref_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert int(new_state.step) == 1


def test_dp_training_loss_decreases():
    mesh = _mesh()
    params = _mlp_params(jax.random.PRNGKey(0))
    tx = optax.sgd(0.5)
    step = make_data_parallel_step(_mlp_loss_fn, tx, mesh)
    state = step.init_state(params)

    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    y = (x.sum(-1) > 0).astype(jnp.int32)
    batch = shard_batch({"image": x, "label": y}, mesh)

    losses = []
    for _ in range(20):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses


@pytest.mark.slow  # ~11s in-suite, ~31s cold ResNet compile; dp_step_matches_single_device + dp_training_loss_decreases keep fast dp-step coverage
def test_resnet_dp_step_runs():
    """Full flax ResNet with BatchNorm state through the dp step."""
    mesh = _mesh()
    model = ResNet18(num_classes=4, num_filters=8)
    x = jnp.zeros((8, 16, 16, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    params = variables["params"]
    model_state = {"batch_stats": variables["batch_stats"]}

    tx = optax.sgd(0.01, momentum=0.9)
    loss_fn = classification_loss_fn(model)
    step = make_data_parallel_step(loss_fn, tx, mesh)
    state = step.init_state(params, model_state=model_state)
    batch = shard_batch(
        {
            "image": jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 3)),
            "label": jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4),
        },
        mesh,
    )
    state, metrics = step(state, batch)
    assert np.isfinite(metrics["loss"])
    state, metrics2 = step(state, batch)
    assert np.isfinite(metrics2["loss"])
    assert int(state.step) == 2


def test_backward_passes_per_step_accumulates():
    """backward_passes_per_step=k: params only move every k-th call
    (reference torch/__init__.py:107-154)."""
    mesh = _mesh()
    params = _mlp_params(jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    step = make_data_parallel_step(
        _mlp_loss_fn, tx, mesh, backward_passes_per_step=2, donate=False
    )
    state = step.init_state(params)
    batch = shard_batch(
        {
            "image": jax.random.normal(jax.random.PRNGKey(1), (16, 8)),
            "label": jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 4),
        },
        mesh,
    )
    s1, _ = step(state, batch)
    # after 1 of 2 passes params unchanged
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    s2, _ = step(s1, batch)
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(s2.params),
            jax.tree_util.tree_leaves(params),
        )
    )
    assert moved


# --------------------------------------------------------------------------
# The sharded update (world > 1): each worker updates the dim-0 share the
# reduce-scatter left it, with its share of the moments, and the new
# parameters are all-gathered.  The yardstick is today's replicated path,
# written out by hand: ``DistributedOptimizer`` in a ``shard_map`` whose
# state is ``P()`` throughout.
# --------------------------------------------------------------------------

from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from byteps_tpu.observability.metrics import get_registry  # noqa: E402
from byteps_tpu.parallel.collectives import shard_map  # noqa: E402
from byteps_tpu.training.step import TrainState  # noqa: E402

WORLD = 4
PB = 256            # bytes: w1 spans several buckets, b1 / w2 share one


def _share_params():
    """A leaf spanning several buckets (w1), leaves sharing a bucket (b1,
    w2) and a leaf whose dim 0 does not divide by 4 (odd)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w1": jax.random.normal(k1, (8, 64)) * 0.3,
            "b1": jnp.full((64,), 0.1),
            "w2": jax.random.normal(k2, (64, 4)) * 0.3,
            "odd": jnp.ones((3,))}


def _share_loss(p, model_state, batch):
    h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] * p["odd"].sum() - batch["y"]) ** 2), (
        model_state)


def _share_batch(mesh, i=0):
    k = jax.random.fold_in(jax.random.PRNGKey(7), i)
    return shard_batch({"x": jax.random.normal(k, (8, 8)),
                        "y": jax.random.normal(k, (8, 4))}, mesh)


def _replicated_run(tx, mesh, steps, **dist):
    """Today's path by hand: state ``P()``, gradients reduced and
    all-gathered, every worker updates every leaf."""
    dtx = bps.DistributedOptimizer(tx, axis_name=("dp",),
                                   partition_bytes=PB, **dist)

    def local(state, b):
        (loss, _), g = jax.value_and_grad(
            lambda p: _share_loss(p, {}, b), has_aux=True)(state.params)
        u, o = dtx.update(g, state.opt_state, state.params)
        return (TrainState(optax.apply_updates(state.params, u), o, {},
                           state.step + 1),
                jax.lax.psum(loss, "dp") / WORLD)

    fn = jax.jit(shard_map(local, mesh,
                           in_specs=(PartitionSpec(), PartitionSpec("dp")),
                           out_specs=(PartitionSpec(), PartitionSpec())))
    state = replicate_state(create_train_state(_share_params(), dtx), mesh)
    losses = []
    for i in range(steps):
        state, loss = fn(state, _share_batch(mesh, i))
        losses.append(float(loss))
    return state, losses


def _step_run(tx, mesh, steps, **kw):
    step = make_data_parallel_step(_share_loss, tx, mesh,
                                   partition_bytes=PB, **kw)
    state = step.init_state(_share_params())
    losses = []
    for i in range(steps):
        state, m = step(state, _share_batch(mesh, i))
        losses.append(float(m["loss"]))
    return state, losses, step


def _gauges():
    g = get_registry().snapshot()["gauges"]
    return g["optimizer.sharded_bytes"], {
        k.split("reason=")[1].rstrip("}"): v for k, v in g.items()
        if k.startswith("optimizer.replicated_bytes") and v}


def _assert_close_ulp(a, b, maxulp):
    """Equal trees: bit for bit at ``maxulp`` 0; else to the rounding of
    a sum of four taken in another order (the two programs reduce
    differently laid-out buffers and contract ``a * b + c`` differently:
    a few ulp on most elements, more on those near zero)."""
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.shape == y.shape
        if maxulp and jnp.issubdtype(x.dtype, jnp.floating):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=maxulp * 1.2e-7, atol=2e-8)
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _ndim_mask(params):
    return jax.tree_util.tree_map(lambda x: x.ndim > 1, params)


@pytest.mark.parametrize("name,tx", [
    ("adamw", optax.adamw(1e-2)),
    ("adamw-ndim-mask", optax.adamw(1e-2, weight_decay=0.1,
                                    mask=_ndim_mask)),
    ("sgd-momentum", optax.sgd(0.05, momentum=0.9)),
])
def test_sharded_update_equals_the_replicated_path(name, tx):
    """After 3 steps at world 4: parameters and (gathered) moments equal
    the replicated path's, the losses match, parameters stay replicated,
    the moments of dividing leaves lie on dim-0 shares and the leaf that
    does not divide stays whole on every worker."""
    mesh = _mesh(WORLD)
    got, losses, _ = _step_run(tx, mesh, 3)
    want, want_losses = _replicated_run(tx, mesh, 3)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-6)
    assert losses[-1] < losses[0]
    _assert_close_ulp(got.params, want.params, maxulp=8)
    _assert_close_ulp(got.opt_state, want.opt_state, maxulp=8)
    assert (jax.tree_util.tree_structure(got.opt_state)
            == jax.tree_util.tree_structure(want.opt_state))
    for leaf in jax.tree_util.tree_leaves(got.params):
        assert leaf.sharding.is_fully_replicated
    on_shares = NamedSharding(mesh, PartitionSpec("dp"))
    for path, leaf in jax.tree_util.tree_leaves_with_path(got.opt_state):
        whole = leaf.ndim == 0 or "odd" in jax.tree_util.keystr(path)
        assert leaf.sharding.is_fully_replicated == whole, path
        if not whole:
            assert leaf.sharding.is_equivalent_to(on_shares, leaf.ndim)
    sharded, replicated = _gauges()
    nbytes = {k: v.size * 4 for k, v in _share_params().items()}
    assert sharded == sum(nbytes.values()) - nbytes["odd"]
    assert replicated == {"dim0": nbytes["odd"]}


def test_an_ndim_mask_decays_the_same_leaves_on_both_paths():
    """The optimizer sees the parameters' own treedef and ``ndim`` on a
    share, so a mask by ``ndim`` keeps its meaning: after one step (the
    same gradients) against a run with no decay, the matrices moved
    differently, the vectors exactly alike."""
    mesh = _mesh(WORLD)
    decayed, _, _ = _step_run(optax.adamw(1e-2, weight_decay=0.1,
                                          mask=_ndim_mask), mesh, 1)
    plain, _, _ = _step_run(optax.adamw(1e-2, weight_decay=0.0), mesh, 1)
    for k in ("b1", "odd"):
        np.testing.assert_array_equal(np.asarray(decayed.params[k]),
                                      np.asarray(plain.params[k]))
    for k in ("w1", "w2"):
        assert not np.allclose(np.asarray(decayed.params[k]),
                               np.asarray(plain.params[k]), atol=1e-6)


@pytest.mark.parametrize("reason,tx,kw,dist", [
    ("elementwise", optax.chain(optax.clip_by_global_norm(0.5),
                                optax.adamw(1e-2)), {}, {}),
    ("elementwise", optax.lamb(1e-2), {}, {}),
    ("wire_cast", optax.adamw(1e-2), {"compression": "bf16"},
     {"compression": "bf16"}),
    ("multi_step", optax.adamw(1e-2), {"backward_passes_per_step": 2},
     {"backward_passes_per_step": 2}),
], ids=["clip-by-global-norm", "lamb", "wire-cast", "two-passes"])
def test_what_a_share_cannot_do_stays_on_the_replicated_path(
        reason, tx, kw, dist):
    """A reduction over a leaf, a wire cast and accumulation over several
    passes run today's path — state ``P()``, the gauge naming why — and
    give today's numbers."""
    mesh = _mesh(WORLD)
    got, losses, _ = _step_run(tx, mesh, 4, **kw)
    want, want_losses = _replicated_run(tx, mesh, 4, **dist)
    np.testing.assert_array_equal(losses, want_losses)
    _assert_close_ulp(got.params, want.params, maxulp=0)
    _assert_close_ulp(got.opt_state, want.opt_state, maxulp=0)
    for leaf in jax.tree_util.tree_leaves((got.params, got.opt_state)):
        assert leaf.sharding.is_fully_replicated
    sharded, replicated = _gauges()
    assert sharded == 0
    assert replicated == {
        reason: sum(v.size * 4 for v in _share_params().values())}


@pytest.mark.parametrize("abstract", [False, True],
                         ids=["concrete-state", "abstract-P()-state"])
def test_the_benchmarks_call_pattern(abstract):
    """What ``benchmark/builders/gpt2.py`` and ``harness/runners/
    train.py`` do, at CPU size: the state made replicated under
    ``out_shardings=P()``, ``step.lower(state, batch).compile()``, the
    compiled step fed its own output — and ``benchmark/aot_check.py``'s
    variant, lowering against abstract ``P()`` state.  The compiled
    program wants the moments on dim-0 shares whatever it was lowered
    with, and its wrapper puts a replicated state there."""
    import functools

    mesh = _mesh(WORLD)
    replicated = NamedSharding(mesh, PartitionSpec())
    step = make_data_parallel_step(_share_loss, optax.adamw(1e-2), mesh,
                                   partition_bytes=PB)

    @functools.partial(jax.jit, out_shardings=replicated)
    def make_state():
        return create_train_state(_share_params(), step.tx)

    state = make_state()
    handed = state
    if abstract:
        handed = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=replicated),
            jax.eval_shape(make_state))
    compiled = step.lower(handed, _share_batch(mesh)).compile()
    assert "all-gather" in compiled.as_text()     # the wrapper passes on
    want, want_losses = _replicated_run(optax.adamw(1e-2), mesh, 4)
    on_shares = NamedSharding(mesh, PartitionSpec("dp"))
    for i in range(4):
        state, m = compiled(state, _share_batch(mesh, i))
        np.testing.assert_allclose(float(m["loss"]), want_losses[i],
                                   rtol=1e-6)
        for leaf in jax.tree_util.tree_leaves(state.params):
            assert (leaf.sharding.is_fully_replicated
                    and len(leaf.sharding.device_set) == WORLD)
        mu = state.opt_state[-1][0].mu
        for k in ("w1", "b1", "w2"):
            assert mu[k].sharding.is_equivalent_to(on_shares, mu[k].ndim)
            assert mu[k].shape == _share_params()[k].shape
        assert mu["odd"].sharding.is_fully_replicated
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(want.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
    # from the second call on the wrapper finds everything in place
    assert step._place(state) is state
