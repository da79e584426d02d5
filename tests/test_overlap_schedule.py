"""Where XLA:CPU places a step's gradient collectives in its schedule.

``tests/test_overlap.py`` shows at the *jaxpr* level that the delayed-grad
step's collectives are independent of the current batch.  These tests look
one level down, at the **optimized, scheduled HLO module** the CPU backend
compiles (``is_scheduled=true`` — instruction order in the entry
computation is the order of issue), and hold two structural properties of
the steps:

  * synchronous step: collectives are issued with backward compute still
    scheduled behind them (nothing forces them to the end of the program);
  * delayed-grad step: the whole reduce chain (through the final all-gather)
    is issued with the batch's forward+backward still pending — impossible
    for a synchronous step, whose update is terminal.

This is a statement about program structure on the CPU backend, not about
time on a chip: a mid-schedule issue does not say the collective is hidden.
How much collective time a step exposes on the v5e is measured by the
benchmark (``collectives.exposed_ms_per_step``, PERF.md §5).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import ShapeDtypeStruct as S
from jax.sharding import Mesh

from byteps_tpu.training import make_data_parallel_step
from byteps_tpu.training.overlap import OverlapState, make_delayed_grad_step
from byteps_tpu.training.step import create_train_state

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")
COMPUTE = ("fusion", "dot", "convolution", "custom-call")


def entry_schedule(compiled_text: str):
    """(index, op) pairs of the ENTRY computation in schedule order."""
    entry, in_entry = [], False
    for ln in compiled_text.splitlines():
        if ln.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry:
            if ln.startswith("}"):
                break
            entry.append(ln)
    op_re = re.compile(r"\b([a-z][a-z0-9\-_\.]*)\(")
    events = []
    for i, ln in enumerate(entry):
        if " = " not in ln:
            continue
        m = op_re.search(ln.split(" = ", 1)[1])
        if m:
            events.append((i, m.group(1)))
    return events


def overlap_stats(compiled_text: str):
    """(first grad-collective index, #compute before it, #compute after it,
    last collective index, #compute after last collective)."""
    ev = entry_schedule(compiled_text)
    coll = [i for i, o in ev if o.startswith(COLLECTIVES)]
    comp = [i for i, o in ev if o in COMPUTE]
    assert coll, "no collectives in compiled module"
    assert comp, "no compute in compiled module"
    first, last = coll[0], coll[-1]
    return (
        first,
        sum(1 for i in comp if i < first),
        sum(1 for i in comp if i > first),
        last,
        sum(1 for i in comp if i > last),
    )


def _loss_fn(params, mstate, batch):
    h = jnp.tanh(batch["x"] @ params["w1"])
    h = jnp.tanh(h @ params["w2"])
    pred = h @ params["w3"]
    return jnp.mean((pred - batch["y"]) ** 2), mstate


_PARAMS = {
    "w1": jnp.zeros((256, 512)),
    "w2": jnp.zeros((512, 512)),
    "w3": jnp.zeros((512, 8)),
}
_BATCH = {"x": S((64, 256), jnp.float32), "y": S((64, 8), jnp.float32)}


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    return Mesh(np.array(jax.devices()), ("dp",))


def test_sync_step_buckets_straddle_backward(mesh):
    """Data-parallel step: the compiled schedule issues the gradient
    collectives with compute still behind them."""
    tx = optax.sgd(0.1, momentum=0.9)
    step = make_data_parallel_step(_loss_fn, tx, mesh)
    state = jax.eval_shape(lambda p: create_train_state(p, step.tx), _PARAMS)
    txt = step._fn.lower(state, _BATCH).compile().as_text()
    assert "is_scheduled=true" in txt

    first, before, after, _, _ = overlap_stats(txt)
    # schedule sandwiches the collectives: real compute on both sides
    assert before >= 2, f"no compute before first collective (idx {first})"
    assert after >= 3, (
        f"collectives scheduled after essentially all compute "
        f"({after} compute ops after)")


def test_delayed_step_collectives_straddle_whole_batch_compute(mesh):
    """Delayed-grad step: the *entire* reduce chain — including the final
    all-gather — is scheduled with this batch's compute still pending,
    which is impossible for a synchronous step (its update is terminal)."""
    tx = optax.sgd(0.1, momentum=0.9)
    step = make_delayed_grad_step(_loss_fn, tx, mesh)
    state = jax.eval_shape(
        lambda p: OverlapState(p, tx.init(p), {}, jnp.zeros((), jnp.int32),
                               jax.tree_util.tree_map(jnp.zeros_like, p)),
        _PARAMS)
    txt = step._fn.lower(state, _BATCH).compile().as_text()
    assert "is_scheduled=true" in txt

    ev = entry_schedule(txt)
    comp = [i for i, o in ev if o in COMPUTE]
    # the *gradient* collectives are the reduce-scatter/all-gather pair
    # (loss/model-state psums lower to plain all-reduce)
    grad_coll = [i for i, o in ev
                 if o.startswith(("reduce-scatter", "all-gather"))]
    assert grad_coll, "no grad bucket collectives found"
    after_last = sum(1 for i in comp if i > grad_coll[-1])
    assert after_last >= 3, (
        "grad reduce chain is scheduled after the batch compute "
        f"({after_last} compute ops after its last collective) — the "
        "cross-iteration independence bought no schedule overlap")

    # and it must beat the synchronous step's placement
    sync = make_data_parallel_step(_loss_fn, tx, mesh)
    sstate = jax.eval_shape(lambda p: create_train_state(p, sync.tx), _PARAMS)
    stxt = sync._fn.lower(sstate, _BATCH).compile().as_text()
    sev = entry_schedule(stxt)
    scomp = [i for i, o in sev if i and o in COMPUTE]
    sgrad = [i for i, o in sev
             if o.startswith(("reduce-scatter", "all-gather"))]
    sync_after = sum(1 for i in scomp if i > sgrad[-1])
    assert after_last >= sync_after, (
        "delayed step should leave at least as much compute after its "
        f"reduce chain as the sync step ({after_last} vs {sync_after})")
