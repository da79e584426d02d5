"""Compiled-schedule overlap proof (VERDICT r2 #2).

``tests/test_overlap.py`` proves at the *jaxpr* level that the delayed-grad
step's collectives are independent of the current batch — necessary but not
sufficient.  These tests assert the property the user actually pays for: in
the **optimized, scheduled HLO module** (``is_scheduled=true`` — instruction
order in the entry computation *is* the execution schedule), the gradient
collectives are placed in the middle of the compute stream, with substantial
compute scheduled after them:

  * sync bucketed step: early buckets' reduce-scatter is issued while later
    backward compute is still scheduled behind it (per-bucket independence —
    the reference's per-tensor hook overlap, torch/__init__.py:112-154);
  * delayed-grad step: the whole reduce chain (through the final all-gather)
    straddles the batch's forward+backward (cross-iteration independence —
    the ByteScheduler barrier removal, bytescheduler/torch/optimizer.py:180-214).

On TPU backends collectives execute on the DMA/ICI queues, so mid-schedule
issue = concurrent execution; the same structural check compiled against a
real TPU topology (AOT, no chips needed) runs in
``scripts/prove_overlap_schedule.py`` and its output is archived in
``docs/overlap_proof.md``.
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
    """Bucketed DP step: the compiled schedule issues bucket collectives
    with compute still behind them — per-bucket overlap with backward.

    History: this carried ``xfail(strict=False)`` for an XLA:CPU
    scheduler regression (collectives sunk to ~the end of the entry
    schedule — PARITY.md) and silently xpassed once the build moved on.
    The mark is dropped so a real schedule regression fails loudly
    again (the delayed-grad variant below lost its mark the same way)."""
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
        f"({after} compute ops after) — no overlap in the schedule")


def test_delayed_step_collectives_straddle_whole_batch_compute(mesh):
    """Delayed-grad step: the *entire* reduce chain — including the final
    all-gather — is scheduled with this batch's compute still pending,
    which is impossible for a synchronous step (its update is terminal).

    History: carried ``xfail(strict=False)`` for an XLA:CPU scheduler
    placement divergence (1 compute op after the reduce chain where the
    assertion demands >= 3 — PARITY.md).  jaxlib 0.9.0 schedules it as
    asserted, so the mark is dropped and a regression fails loudly."""
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
