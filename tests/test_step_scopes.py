"""The ``bps.*`` scopes of the jitted train step (common/tracing.py).

A scope is HLO metadata, so these tests read names, not numbers: the
step is lowered on the CPU mesh and every operation's name stack is
taken from the StableHLO's ``loc("...")`` entries — the string that
becomes the ``op_name`` of the compiled instruction and, on the chip,
of the ``XLA Ops`` event that ``benchmark/harness/scopes.py`` reads.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from byteps_tpu.common import partition, tracing
from byteps_tpu.models import Transformer, TransformerConfig
from byteps_tpu.training import (lm_loss_fn, make_data_parallel_step,
                                 make_zero_step)
from byteps_tpu.training.optimizer import scoped_update
from byteps_tpu.training.overlap import make_delayed_grad_step
from byteps_tpu.training.step import shard_batch

PARTITION_BYTES = 16384        # several buckets for the tiny model
FWD = "jvp(bps.model)"
BWD = "transpose(jvp(bps.model))"


def op_names(lowered):
    """The name stack of every operation of a lowered program."""
    text = lowered.as_text(debug_info=True)
    # (a leading "/" is a source file; a bare word names a region)
    return {n for n in re.findall(r'loc\("([^"]+)"', text)
            if "/" in n and not n.startswith("/")}


def tiny_model():
    model = Transformer(TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=16, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 16), jnp.int32))["params"]
    return model, params


def lowered_step(world, fused_head=True, builder=make_data_parallel_step,
                 partition_bytes=PARTITION_BYTES):
    model, params = tiny_model()
    mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))
    step = builder(lm_loss_fn(model, fused_head=fused_head),
                   optax.adamw(1e-3), mesh, partition_bytes=partition_bytes)
    state = step.init_state(params)
    batch = shard_batch(
        {"tokens": jnp.zeros((2 * world, 16), jnp.int32)}, mesh)
    return step.lower(state, batch), params


@pytest.fixture(scope="module", params=[
    (1, True), (1, False), (4, True), (4, False)],
    ids=["world1-fused", "world1-plain", "world4-fused", "world4-plain"])
def names(request):
    world, fused = request.param
    return world, op_names(lowered_step(world, fused)[0])


def under(names, prefix):
    return {n for n in names if prefix in n}


@pytest.mark.parametrize("scope", [
    tracing.SCOPE_MODEL, tracing.SCOPE_HEAD, tracing.SCOPE_OPTIMIZER,
    tracing.SCOPE_STEP_METRICS])
def test_every_stage_scope_is_in_the_lowered_step(names, scope):
    _, all_names = names
    assert under(all_names, scope), scope


def test_backward_ops_lie_under_transpose_of_the_model_scope(names):
    _, all_names = names
    fwd, bwd = under(all_names, FWD + "/"), under(all_names, BWD + "/")
    assert fwd and bwd and not (fwd & bwd)
    # Flax's own module scopes nest inside, on both sides
    for side in (FWD, BWD):
        for module in ("block_0/attn/q", "block_1/mlp/up", "ln_f"):
            assert any(re.search(re.escape(side) + r"/(Transformer[^/]*/)+"
                                 + module, n)
                       for n in all_names), (side, module)
    # the head is inside the model scope, forward and backward
    assert under(all_names, f"{FWD}/{tracing.SCOPE_HEAD}/")
    assert under(all_names, f"{BWD}/{tracing.SCOPE_HEAD}/")
    # the optimizer and the metrics are no part of the backward pass
    assert not under(bwd, tracing.SCOPE_OPTIMIZER)
    assert not under(bwd, tracing.SCOPE_STEP_METRICS)


def test_push_pull_scopes_exist_only_across_chips(names):
    world, all_names = names
    pp = under(all_names, tracing.SCOPE_PUSH_PULL)
    if world == 1:
        assert not pp              # world == 1 drops DistributedOptimizer
    else:
        assert under(pp, tracing.SCOPE_UNPACK)
        assert under(pp, tracing.SCOPE_PUSH_PULL + "/pack/")
        assert under(pp, tracing.SCOPE_PUSH_PULL + "/reduce/")


def bucket_ids(names, stage):
    pat = re.compile(re.escape(tracing.SCOPE_PUSH_PULL)
                     + rf"/{stage}/b(\d{{3}})/")
    return sorted({int(m.group(1)) for n in names
                   for m in [pat.search(n)] if m})


@pytest.mark.parametrize("partition_bytes", [4096, 16384, 1 << 20])
def test_each_planned_bucket_has_its_pack_and_reduce_scope(partition_bytes):
    lowered, params = lowered_step(4, partition_bytes=partition_bytes)
    plan = partition.plan_buckets(params, partition_bytes)
    all_names = op_names(lowered)
    want = list(range(plan.num_buckets))
    assert bucket_ids(all_names, "pack") == want
    assert bucket_ids(all_names, "reduce") == want
    # one scope per bucket and stage: nothing of bucket i is named j
    for stage in tracing.BUCKET_STAGES:
        for i in want:
            scope = tracing.bucket_scope(stage, i)
            assert under(all_names, scope + "/"), scope
    # the collectives themselves carry their bucket's reduce scope, and
    # the loss average the metrics scope
    reduce_scope = re.compile(
        re.escape(tracing.SCOPE_PUSH_PULL) + r"/reduce/b\d{3}/")
    for n in all_names:
        op = n.rsplit("/", 1)[-1]
        if op in ("reduce_scatter", "all_gather"):
            assert reduce_scope.search(n), n
        if op == "psum":
            assert tracing.SCOPE_STEP_METRICS in n, n


def test_the_delayed_gradient_step_carries_the_bucket_scopes():
    lowered, params = lowered_step(4, builder=make_delayed_grad_step)
    plan = partition.plan_buckets(params, PARTITION_BYTES)
    all_names = op_names(lowered)
    want = list(range(plan.num_buckets))
    assert bucket_ids(all_names, "pack") == want
    assert bucket_ids(all_names, "reduce") == want
    assert under(all_names, tracing.SCOPE_UNPACK)


def test_the_zero_steps_device_program_is_the_scoped_backward_pass(
        monkeypatch):
    """``make_zero_step`` jits the backward pass only; its reduction and
    update run on the host through the PS tier, so its device program
    has the model scope and no bucket scope."""
    seen = {}

    class Zero:
        params = {"w": np.ones(8, np.float32)}

        def step(self, grads):
            seen.update(grads)

    def loss_fn(p, mstate, batch):
        return jnp.mean((batch["x"] @ p["w"].reshape(4, 2)) ** 2), mstate

    batch = {"x": np.ones((3, 4), np.float32)}
    jitted = []
    real_jit = jax.jit
    monkeypatch.setattr(jax, "jit", lambda f, **kw: jitted.append(
        real_jit(f, **kw)) or jitted[-1])
    step = make_zero_step(loss_fn, Zero())
    monkeypatch.undo()
    assert step(batch) > 0 and set(seen) == {"w"}
    (grad_fn,) = jitted
    all_names = op_names(grad_fn.lower(Zero.params, batch))
    assert under(all_names, FWD) and under(all_names, BWD)
    assert not under(all_names, tracing.SCOPE_PUSH_PULL)


@pytest.mark.parametrize("stage,i,want", [
    ("pack", 0, "bps.push_pull/pack/b000"),
    ("reduce", 3, "bps.push_pull/reduce/b003"),
    ("reduce", 345, "bps.push_pull/reduce/b345"),
    ("pack", 1234, "bps.push_pull/pack/b1234")])
def test_bucket_scope_names(stage, i, want):
    assert tracing.bucket_scope(stage, i) == want
    assert tracing.SCOPE_UNPACK == "bps.push_pull/unpack"
    with pytest.raises(ValueError):
        tracing.bucket_scope("unpack", i)   # unpack has no bucket


def test_scoped_update_is_the_same_transformation_under_a_name():
    tx, scoped = optax.adamw(1e-2), scoped_update(optax.adamw(1e-2))
    params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}
    grads = jax.tree_util.tree_map(lambda x: 0.1 * x + 1.0, params)
    s0, s1 = tx.init(params), scoped.init(params)
    assert jax.tree_util.tree_structure(s0) == jax.tree_util.tree_structure(
        s1)
    u0, _ = tx.update(grads, s0, params)
    u1, _ = scoped.update(grads, s1, params)
    for a, b in zip(jax.tree_util.tree_leaves(u0),
                    jax.tree_util.tree_leaves(u1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    lowered = jax.jit(lambda g, s, p: scoped.update(g, s, p)).lower(
        grads, s1, params)
    scoped_ops = under(op_names(lowered), tracing.SCOPE_OPTIMIZER)
    assert any(n.endswith(("/mul", "/sqrt", "/div")) for n in scoped_ops)
