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
    # (every leaf of the tiny model divides by 4: one plan, of shares)
    plan = partition.plan_share_buckets(
        jax.tree_util.tree_leaves(params), 4, partition_bytes)
    all_names = op_names(lowered)
    want = [b.bucket_id for b in plan.buckets]
    # a leaf alone in its bucket is its own payload: nothing to pack
    shared = [b.bucket_id for b in plan.buckets if len(b.slices) > 1]
    assert shared and (len(shared) < len(want) or partition_bytes > 16384)
    assert bucket_ids(all_names, "pack") == shared
    assert bucket_ids(all_names, "reduce") == want
    # one scope per bucket and stage: nothing of bucket i is named j
    for stage, ids in (("pack", shared), ("reduce", want)):
        for i in ids:
            scope = tracing.bucket_scope(stage, i)
            assert under(all_names, scope + "/"), scope
    # the collectives themselves carry their bucket's reduce scope — the
    # scatter of the gradients and the gather of the new parameters, one
    # of each a bucket — and the loss average the metrics scope
    reduce_scope = re.compile(
        re.escape(tracing.SCOPE_PUSH_PULL) + r"/reduce/b(\d{3})/")
    collectives = {"reduce_scatter": [], "all_gather": []}
    for n in all_names:
        op = n.rsplit("/", 1)[-1]
        if op in collectives:
            assert reduce_scope.search(n), n
            collectives[op].append(int(reduce_scope.search(n).group(1)))
        if op == "psum":
            assert tracing.SCOPE_STEP_METRICS in n, n
    assert sorted(collectives["reduce_scatter"]) == want
    assert sorted(collectives["all_gather"]) == want


STAGE_SCOPES = (
    tracing.SCOPE_MODEL, tracing.SCOPE_HEAD,
    tracing.SCOPE_PUSH_PULL + "/pack/", tracing.SCOPE_PUSH_PULL + "/reduce/",
    tracing.SCOPE_UNPACK, tracing.SCOPE_OPTIMIZER,
    tracing.SCOPE_STEP_METRICS)


def test_every_op_of_the_step_lies_under_one_of_the_seven_scopes():
    """``benchmark/harness/scopes.py`` knows seven stages and reads
    anything else as ``unscoped``: the sharded step's own ops — the
    small leaves' shares packed for the forward's all-gathers, the
    all-gathers, both unpacks, the update of the shares — each carry one
    of them."""
    # the compiled program's ``op_name``s: the whole name stack of each
    # instruction, as the device trace carries it
    text = lowered_step(4)[0].compile().as_text()
    every = set(re.findall(r'op_name="([^"]+)"', text))
    inside = {n for n in every if "/shard_map/" in n}
    assert len(inside) > 100
    stray = {re.sub(r"\.\d+$", "", n) for n in inside
             if not any(scope in n for scope in STAGE_SCOPES)}
    # (the step counter's ``+ 1`` is the one op the step leaves unnamed;
    # the compiler names the constants it broadcasts itself)
    assert stray <= {"jit(step_fn)/shard_map/add",
                     "jit(step_fn)/shard_map/broadcast"}, sorted(stray)[:10]
    ops = lambda scope: {n.rsplit("/", 1)[-1]            # noqa: E731
                         for n in under(every, scope)}
    # small leaves are joined into payloads and payloads cut back into
    # leaves
    assert "concatenate" in ops(tracing.SCOPE_PUSH_PULL + "/pack/")
    assert {"reduce_scatter", "all_gather", "div"} <= ops(
        tracing.SCOPE_PUSH_PULL + "/reduce/")
    assert "slice" in ops(tracing.SCOPE_UNPACK)
    # the optimizer updates the parameter shares as they lie between
    # steps: nothing is cut out of a whole parameter
    assert "sqrt" in ops(tracing.SCOPE_OPTIMIZER)
    assert not {"axis_index", "dynamic_slice"} & ops(
        tracing.SCOPE_OPTIMIZER)


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


def test_a_leaf_that_does_not_divide_shares_no_bucket_id_with_the_shares():
    """A leaf whose dim 0 does not divide by the world is reduced whole
    under the flat plan, whose buckets are numbered first; the shares'
    buckets follow, so every collective of the step has a ``reduce``
    scope of its own."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    params = {"w": jnp.ones((8, 64)), "b": jnp.ones((64,)),
              "odd": jnp.ones((3,)), "odd2": jnp.ones((5, 7))}

    def loss_fn(p, model_state, batch):
        h = batch["x"] @ p["w"] + p["b"]
        return jnp.mean(h ** 2) * p["odd"].sum() * p["odd2"].sum(), (
            model_state)

    step = make_data_parallel_step(loss_fn, optax.adamw(1e-3), mesh,
                                   partition_bytes=64)
    names = op_names(step.lower(
        step.init_state(params),
        shard_batch({"x": jnp.ones((8, 8))}, mesh)))
    rest = partition.plan_buckets(
        [params["odd"], params["odd2"]], 64).num_buckets
    shares = partition.plan_share_buckets(
        [params["b"], params["w"]], 4, 64, first_id=rest)
    assert rest > 1 and shares.num_buckets > 1
    assert bucket_ids(names, "reduce") == list(
        range(rest + shares.num_buckets))
    by_id = {}
    for n in names:
        m = re.search(r"/reduce/b(\d{3})/(reduce_scatter|all_gather)", n)
        if m:
            by_id.setdefault(int(m.group(1)), set()).add(m.group(2))
    # the flat buckets scatter and gather the gradient; each share bucket
    # scatters the gradient and gathers the new parameters
    assert all(by_id[i] == {"reduce_scatter", "all_gather"}
               for i in range(rest + shares.num_buckets))
    assert [b.bucket_id for b in shares.buckets] == list(
        range(rest, rest + shares.num_buckets))
