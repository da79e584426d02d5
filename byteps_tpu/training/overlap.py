"""Cross-iteration communication/compute overlap — the ByteScheduler analog.

The reference's ByteScheduler (bytescheduler/torch/optimizer.py) removes the
global barrier between iterations: per-layer forward **pre-hooks block each
layer only until *its own* parameters' push_pull + update finished**
(optimizer.py:180-214), a poller thread applies per-parameter updates as
handles complete (optimizer.py:151-178), so iteration N+1's forward runs
while iteration N's low-priority buckets are still reducing.

TPU rendering: threads and hooks cannot express this (one traced program
per step), but *program structure* can.  ``make_delayed_grad_step`` builds a
step whose gradient collectives consume the **previous** iteration's local
gradients, carried in the train state:

    g_N        = grad(loss)(params_N, batch_N)        # backward compute
    r_{N-1}    = push_pull(pending = g_{N-1})          # collectives: no data
                                                       #  dependency on batch_N!
    params_N+1 = params_N - lr * r_{N-1}               # 1-step-stale update
    pending'   = g_N

Because the collective chain's operands are program *inputs* (state), not
values produced by this step's compute, XLA's latency-hiding scheduler is
free to run the whole reduce concurrently with the forward+backward — the
same overlap ByteScheduler gets from its barrier removal, with the same
bounded staleness (each parameter update lags its gradient by exactly one
iteration; ByteScheduler's lag is sub-iteration but nonzero per layer).
Whether the chip's schedule uses that freedom has never been measured, and
the synchronous step now hides all but 12.7 ms of its collectives
(PERF_LEDGER.jsonl, PR 28; ROADMAP D8).
``tests/test_overlap.py`` verifies both the exact staleness semantics and,
via jaxpr dependency analysis, that no collective depends on the batch.

Use ``flush()`` after the loop to apply the final pending gradients (the
analog of ByteScheduler's final-step synchronize, optimizer.py:75-97).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from ..common.config import get_config
from ..ops.compression import Compression
from ..parallel.collectives import _axis_size, push_pull_tree, shard_map
from .step import replicate_state


class OverlapState(NamedTuple):
    params: Any
    opt_state: Any
    model_state: Any
    step: jax.Array
    pending: Any  # previous iteration's local (un-reduced) gradients


def make_delayed_grad_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axes: Sequence[str] = ("dp",),
    compression: type = Compression.none,
    partition_bytes: Optional[int] = None,
    donate: bool = True,
):
    """Build the jitted delayed-gradient data-parallel step.

    Same calling convention as ``make_data_parallel_step``
    (``loss_fn(params, model_state, batch) -> (loss, new_model_state)``,
    batch sharded over ``axes``) but with cross-iteration overlap: the
    returned ``DelayedStep`` also exposes ``flush(state)`` to apply the last
    pending gradients after the loop.
    """
    axes = tuple(axes)
    cfg = get_config()
    pb = partition_bytes or cfg.effective_partition_bytes
    wire = getattr(compression, "wire_dtype", None) or cfg.wire_jnp_dtype

    def _reduce_and_update(params, opt_state, pending, world):
        reduced = push_pull_tree(
            pending,
            scatter_axis=axes[-1],
            sum_axes=axes[:-1],
            average=True,
            wire_dtype=wire,
            partition_bytes=pb,
        ) if world > 1 else pending
        updates, new_opt = optimizer.update(reduced, opt_state, params)
        return optax.apply_updates(params, updates), new_opt

    def local_step(state: OverlapState, batch):
        def lf(p):
            return loss_fn(p, state.model_state, batch)

        # this iteration's backward (compute)
        (loss, new_mstate), grads = jax.value_and_grad(lf, has_aux=True)(
            state.params
        )
        n = _axis_size(axes)
        # previous iteration's reduce + update (collectives, independent of
        # `batch` — the overlap invariant; see module docstring)
        new_params, new_opt = _reduce_and_update(
            state.params, state.opt_state, state.pending, n
        )
        loss = jax.lax.psum(loss, axes) / n
        new_mstate = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, axes) / n
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            new_mstate,
        )
        return (
            OverlapState(new_params, new_opt, new_mstate, state.step + 1, grads),
            {"loss": loss},
        )

    def local_flush(state: OverlapState):
        new_params, new_opt = _reduce_and_update(
            state.params, state.opt_state, state.pending, _axis_size(axes)
        )
        zero = jax.tree_util.tree_map(jnp.zeros_like, state.pending)
        return OverlapState(
            new_params, new_opt, state.model_state, state.step, zero
        )

    state_spec = P()
    batch_spec = P(axes)
    jitted = jax.jit(
        shard_map(local_step, mesh, in_specs=(state_spec, batch_spec),
                  out_specs=(state_spec, state_spec)),
        donate_argnums=(0,) if donate else (),
    )
    jitted_flush = jax.jit(
        shard_map(local_flush, mesh, in_specs=(state_spec,),
                  out_specs=state_spec),
        donate_argnums=(0,) if donate else (),
    )
    return DelayedStep(jitted, jitted_flush, optimizer, mesh, local_step)


class DelayedStep:
    """Callable delayed-gradient step; ``flush`` applies the final pending
    gradients (ByteScheduler's end-of-training synchronize)."""

    def __init__(self, fn, flush_fn, tx, mesh, local_fn):
        self._fn = fn
        self._flush = flush_fn
        self.tx = tx
        self.mesh = mesh
        self._local_fn = local_fn  # exposed for jaxpr-level tests

    def __call__(self, state: OverlapState, batch):
        return self._fn(state, batch)

    def flush(self, state: OverlapState) -> OverlapState:
        return self._flush(state)

    def init_state(self, params, model_state=None) -> OverlapState:
        state = OverlapState(
            params=params,
            opt_state=self.tx.init(params),
            model_state=model_state if model_state is not None else {},
            step=jnp.zeros((), jnp.int32),
            pending=jax.tree_util.tree_map(jnp.zeros_like, params),
        )
        return replicate_state(state, self.mesh)

    def lower(self, state, batch):
        return self._fn.lower(state, batch)
