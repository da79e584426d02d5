"""High-level training driver — the analog of the reference's Gluon
``DistributedTrainer`` (mxnet/__init__.py:142-204) and the Keras callback
stack: owns the step function, broadcast-at-start, metric averaging,
checkpointing, and the train loop, so user code is just model + data.

Example::

    trainer = Trainer(
        loss_fn=classification_loss_fn(model),
        optimizer=optax.sgd(warmup_schedule(0.1, bps.size(), 500), momentum=0.9),
        checkpoint_dir="/tmp/ckpts",
    )
    trainer.fit(params, model_state, data_iter, steps=1000)
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import optax

import byteps_tpu as bps

from ..common import logging as bps_log
from ..ops.compression import Compression
from .callbacks import average_metrics
from .checkpoint import CheckpointManager
from .step import (
    TrainState,
    make_data_parallel_step,
    shard_batch,
)


# Bounded dispatch pipelining depth shared by fit/evaluate: unbounded async
# dispatch of data-dependent steps can starve XLA's collective rendezvous
# (the virtual-CPU harness SIGABRTs); blocking on results from this many
# iterations back keeps the pipeline full while bounding it.
_INFLIGHT_WINDOW = 4


class Trainer:
    def __init__(
        self,
        loss_fn: Callable,
        optimizer: optax.GradientTransformation,
        mesh=None,
        axes: Sequence[str] = ("dp",),
        compression: type = Compression.none,
        backward_passes_per_step: int = 1,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1000,
        checkpoint_keep: int = 3,
        log_every: int = 100,
        callbacks: Sequence[Callable] = (),
        async_mode: Optional[bool] = None,
        async_store=None,
        async_interval: int = 1,
        worker_id: Optional[int] = None,
        overlap: bool = False,
    ):
        bps.init()
        from ..common.config import get_config

        self.mesh = mesh if mesh is not None else bps.mesh()
        # --- ByteScheduler mode (reference bytescheduler/torch/optimizer.py):
        # cross-iteration comm/compute overlap via the delayed-gradient step
        # (training/overlap.py).  The reference opts in by wrapping the
        # optimizer; here it is a Trainer flag.  fit() flushes the final
        # pending gradients (the analog of ByteScheduler's last-step
        # synchronize, optimizer.py:75-97).
        self.overlap = bool(overlap)
        if self.overlap:
            if async_mode:
                raise ValueError("overlap=True is a synchronous schedule; "
                                 "it cannot combine with async_mode")
            if backward_passes_per_step != 1:
                raise ValueError("overlap=True does not compose with "
                                 "backward_passes_per_step > 1")
            from .overlap import make_delayed_grad_step

            self.step_fn = make_delayed_grad_step(
                loss_fn, optimizer, self.mesh, axes=tuple(axes),
                compression=compression,
            )
        else:
            self.step_fn = make_data_parallel_step(
                loss_fn, optimizer, self.mesh, axes=tuple(axes),
                compression=compression,
                backward_passes_per_step=backward_passes_per_step,
            )
        self.ckpt = (
            CheckpointManager(checkpoint_dir, checkpoint_every, checkpoint_keep)
            if checkpoint_dir else None
        )
        self.log_every = log_every
        self.callbacks = list(callbacks)
        self.state: Optional[TrainState] = None
        # --- async-PS mode (reference BYTEPS_ENABLE_ASYNC,
        # torch/__init__.py:174-189): intra-mesh reduction stays synchronous
        # (the reference's intra-machine NCCL stage does too); *between*
        # workers sharing a store, weight deltas are pushed and global state
        # pulled with no barrier.  Flag precedence: explicit arg > env.
        self.async_mode = (
            get_config().enable_async if async_mode is None else async_mode
        )
        self.async_interval = max(1, async_interval)
        self.worker_id = worker_id if worker_id is not None else bps.rank()
        self._async_worker = None
        if self.async_mode:
            from ..engine.async_ps import get_async_store

            self.async_store = (
                async_store if async_store is not None else get_async_store()
            )
        else:
            self.async_store = None

    # ------------------------------------------------------------------ api

    def init_state(self, params, model_state=None, root_rank: int = 0,
                   resume: bool = True) -> TrainState:
        """Broadcast-consistent init (reference BroadcastGlobalVariables
        semantics), optionally resuming from the latest checkpoint."""
        state = None
        if self.ckpt is not None and resume:
            state = self.step_fn.init_state(params, model_state=model_state)
            restored, step = self.ckpt.restore_latest(template=tuple(state))
            if restored is not None:
                bps_log.info("resuming from checkpoint step %d", step)
                # reconstruct whatever state type the step uses (TrainState,
                # or OverlapState in overlap mode)
                state = type(state)(*restored)
            else:
                state = None
        if state is None:
            params = bps.broadcast_parameters(params, root_rank=root_rank)
            if model_state:
                model_state = bps.broadcast_parameters(model_state, root_rank)
            state = self.step_fn.init_state(params, model_state=model_state)
        if self.async_mode and self._async_worker is None:
            from ..engine.async_ps import AsyncWorker

            # registers + does the first-push-wins initial push (reference
            # InitTensor's blocking initial push, operations.cc:262-284)
            self._async_worker = AsyncWorker(
                self.async_store,
                jax.device_get(self.step_fn.whole_params(state)),
                worker_id=self.worker_id,
            )
        return state

    def fit(
        self,
        params,
        model_state,
        batches: Iterable,
        steps: Optional[int] = None,
    ) -> TrainState:
        state = self.state or self.init_state(params, model_state)
        t0 = time.time()
        seen = 0
        # Track the step number on host: reading int(state.step) every
        # iteration would force a device sync per step and serialize the
        # async dispatch pipeline whose overlap is the performance story.
        start_step = int(state.step)
        window = _INFLIGHT_WINDOW
        inflight: list = []
        for i, batch in enumerate(batches):
            if steps is not None and i >= steps:
                break
            batch = shard_batch(batch, self.mesh)
            state, metrics = self.step_fn(state, batch)
            # metrics (not state) goes in the window: state buffers are
            # donated into the next step and blocking on a donated array
            # would raise; metrics data-depends on the full step.
            inflight.append(metrics)
            if len(inflight) > window:
                jax.block_until_ready(inflight.pop(0))
            seen += 1
            step_no = start_step + seen
            if self.ckpt is not None:
                self.ckpt.maybe_save(tuple(state), step_no)
            for cb in self.callbacks:
                maybe = cb(state)
                if maybe is not None:
                    state = maybe
                    # A callback may have replaced state (e.g. rollback) —
                    # resync the host-side counter with the device counter
                    # so checkpoint step numbers stay consistent.
                    start_step = int(state.step) - seen
            if self._async_worker is not None and seen % self.async_interval == 0:
                # Pipelined async-PS exchange (reference torch/__init__.py:
                # 174-189, kept off the critical path): adopt the PREVIOUS
                # interval's pulled global state with the catch-up rule
                # params += pulled - submitted (local progress made while
                # the exchange flew is preserved; see AsyncWorker), then
                # submit this interval's exchange on a non-donated device
                # copy.  The train thread never blocks on device_get.
                state = self._adopt_exchange(state)
                # non-donated copy: the step donates state buffers, so the
                # background thread must not read state.params directly
                self._async_worker.begin_push_pull(
                    jax.tree_util.tree_map(
                        jnp.copy, self.step_fn.whole_params(state)))
            if self.log_every and seen % self.log_every == 0:
                avg = average_metrics(
                    {k: v for k, v in metrics.items()}
                )
                rate = seen / max(time.time() - t0, 1e-9)
                bps_log.info(
                    "step %d %s (%.2f steps/s)", step_no,
                    {k: round(v, 4) for k, v in avg.items()}, rate,
                )
        if self._async_worker is not None:
            # drain the last in-flight exchange so the returned state
            # reflects the global store
            state = self._adopt_exchange(state)
        if self.overlap:
            # apply the final pending (1-step-stale) gradients
            state = self.step_fn.flush(state)
        self.state = state
        return state

    def close(self) -> None:
        """Release background resources (the async-PS exchange thread,
        which pins a host param snapshot until stopped).  Idempotent."""
        if self._async_worker is not None:
            self._async_worker.close()
            self._async_worker = None

    def _adopt_exchange(self, state):
        """Fold a completed background exchange into the current params:
        ``params += pulled - submitted`` (catch-up rule — see
        AsyncWorker.take_result).  No-op when nothing is in flight."""
        if not self._async_worker.exchange_in_flight():
            return state
        pulled, submitted = self._async_worker.take_result()
        # the delta lies where its leaf lies (on dim-0 shares or whole)
        new_params = jax.tree_util.tree_map(
            lambda x, p, s: x + jax.device_put(
                jnp.asarray(p - s), x.sharding).astype(x.dtype),
            state.params, pulled, submitted)
        return state._replace(params=new_params)

    def evaluate(self, eval_fn: Callable, batches: Iterable) -> Dict[str, float]:
        """Average ``eval_fn(state, batch) -> {metric: scalar}`` over
        batches and across workers (reference MetricAverageCallback).

        Host reads ride the same bounded in-flight window as ``fit``:
        ``float(v)`` on the newest batch would sync the device per batch
        and serialize dispatch, so summation happens on values from a few
        batches back while newer eval steps are already in flight.
        """
        sums: Dict[str, float] = {}
        n = 0
        window = _INFLIGHT_WINDOW
        inflight: list = []

        def drain(out):
            nonlocal n
            for k, v in out.items():
                sums[k] = sums.get(k, 0.0) + float(v)
            n += 1

        for batch in batches:
            batch = shard_batch(batch, self.mesh)
            inflight.append(eval_fn(self.state, batch))
            if len(inflight) > window:
                drain(inflight.pop(0))
        for out in inflight:
            drain(out)
        means = {k: v / max(n, 1) for k, v in sums.items()}
        return average_metrics(means)
