"""Train-step factories: the framework's hot path.

The reference's hot path is loss.backward() firing per-gradient hooks that
enqueue push_pull tasks drained by C++ threads (SURVEY.md §3.2).  The TPU
rendering is one traced SPMD program per step: ``shard_map`` over the mesh,
local backward, one collective per bucket in priority order
(collectives.py), optax update.

What hides the communication, as measured on the v5e (PERF_LEDGER.jsonl,
``gpt2m_train_dp4``): a bucket packed from several leaves stands between
each leaf's gradient and its collective, and XLA then ran the whole
collective chain after the backward pass — 37.6 ms exposed a step, all of
the collective time (ledger, PR 22).  A collective that is a leaf's own
(``plan_share_buckets``: a leaf whose dim-0 share fills a bucket is
scattered and gathered as it lies) follows that leaf's weight-gradient
product and is hidden inside the backward pass — 12.7 ms exposed (ledger,
PR 28).  So ``make_data_parallel_step`` reduce-scatters leaf by leaf, runs
the optimizer on this worker's share and all-gathers the new parameters;
the flat bucketed ``push_pull_tree`` remains for the replicated path and
the eager API, and has no benchmark cell at world > 1.

``make_data_parallel_step`` is the Horovod-benchmark-equivalent step used by
``benchmark/`` and the examples; model-parallel (tp/sp) steps compose GSPMD
jit with these same pieces (see models/transformer.py and
__graft_entry__.py).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import logging as bps_log
from ..common import partition
from ..common.config import get_config
from ..observability.metrics import get_registry
from ..common.tracing import (SCOPE_HEAD, SCOPE_MODEL, SCOPE_OPTIMIZER,
                              SCOPE_STEP_METRICS)
from ..ops.compression import Compression
from .optimizer import (distributed_links, resolve_compression,
                        resolve_local_axis, resolve_wire_dtype, scoped_update,
                        trace_share_update)
from ..parallel.collectives import (all_gather_tree, push_pull_tree,
                                    reduce_scatter_tree, shard_map)


class TrainState(NamedTuple):
    """Functional train state (params + optimizer state + mutable model
    collections such as BatchNorm running stats + step counter)."""

    params: Any
    opt_state: Any
    model_state: Any
    step: jax.Array


def create_train_state(
    params, tx: optax.GradientTransformation, model_state=None
) -> TrainState:
    return TrainState(
        params=params,
        opt_state=tx.init(params),
        model_state=model_state if model_state is not None else {},
        step=jnp.zeros((), jnp.int32),
    )


def _world1_compression_tx(compression) -> Optional[optax.GradientTransformation]:
    """The single-process rendering of a compression spec: a local optax
    transformation reproducing what the scheme does to each worker's
    contribution on a multi-worker wire, so ``world == 1`` sees the same
    gradient numerics as a multi-process run (the world==1 limit of
    "compress, reduce over one worker, decompress").

    Returns None when nothing needs doing (no/none compression) or — with
    a warning — when the spec is genuinely inapplicable: an object that
    is neither a registry scheme name nor a ``compress``/``decompress``
    Compressor, whose wire behavior we cannot reproduce locally.
    """
    from ..ops.compression import Compression as C

    if compression is None or compression is C.none or compression == "none":
        return None
    if isinstance(compression, str):
        from ..compression import (compression_roundtrip,
                                   error_feedback_compress, get_scheme)

        scheme = get_scheme(compression)  # unknown names fail like multi
        if scheme.biased:
            return error_feedback_compress(scheme)
        return compression_roundtrip(scheme)
    if hasattr(compression, "compress") and hasattr(compression,
                                                    "decompress"):
        def update_fn(updates, state, params=None):
            del params

            def one(g):
                c, ctx = compression.compress(g)
                return compression.decompress(c, ctx)

            return jax.tree_util.tree_map(one, updates), state

        return optax.GradientTransformation(
            lambda params: optax.EmptyState(), update_fn)
    from ..common.logging import get_logger

    get_logger().warning(
        "make_data_parallel_step: world size is 1 and compression=%r is "
        "neither a registry scheme name nor a Compressor — it cannot be "
        "applied locally and is dropped; multi-device meshes will reject "
        "it too", compression)
    return None


def make_data_parallel_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axes: Sequence[str] = ("dp",),
    compression: Any = Compression.none,  # Compressor class or scheme name
    partition_bytes: Optional[int] = None,
    backward_passes_per_step: int = 1,
    donate: bool = True,
    local_axis: Optional[str] = None,
):
    """Build a jitted data-parallel train step.

    ``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` runs
    on the *local* batch shard.  The returned step function has signature
    ``step(state: TrainState, batch) -> (TrainState, metrics)`` where
    ``batch`` is a pytree whose leaves have the global batch on dim 0
    (sharded over ``axes``), and metrics = {"loss": mean loss}.  A
    ``loss_fn`` may return a third element, a dict of counts of its own
    (``lm_loss_fn``: ``moe_assignments_held``, ``moe_rows_computed``);
    they join the metrics summed over the workers, and every call of the
    step adds them to the registry's counters of their names
    (``flush_step_counts``).

    Semantics match the reference benchmark
    (example/pytorch/benchmark_byteps.py): gradients are *averaged* across
    all workers via the bucketed scheduled push_pull; BatchNorm normalizes
    per-replica (torchvision semantics) while running stats are averaged
    across replicas so the state stays replicated.

    ``local_axis`` pins which of ``axes`` hosts the local (ICI)
    reduce-scatter stage of the hierarchical reduction (docs/wire.md
    "Hierarchical reduction"); default: the innermost axis.

    **Where the update runs (world > 1).**  The gradients are
    reduce-scattered so that every worker is left with its contiguous
    1/shards of every leaf's averaged gradient along dim 0 (shards = the
    size of the local scatter axis); the optimizer updates that share
    with its share of the moments, and the new parameter shares are
    all-gathered: the same bytes on the wire as reducing and gathering
    the gradient, 1/shards of the update's memory traffic, the moments
    held once across the axis.  Both collectives follow one plan
    (``partition.plan_share_buckets``): a leaf whose share fills a
    bucket (``partition_bytes / shards``) goes by itself, as it lies —
    the scatter of a leaf along dim 0 leaves its dim-0 share, the gather
    of the shares IS the leaf, nothing is packed or unpacked —, the
    small leaves share buckets, in ``schedule_order``.  The
    optimizer sees the parameters' own treedef and ``ndim`` (path- and
    ``ndim``-based masks keep their meaning); ``state.opt_state`` keeps
    the ``DistributedOptimizer`` chain's treedef and the parameters'
    global shapes, a share's moments placed ``P(scatter_axis)`` on dim
    0; parameters stay replicated.  Which path runs is read from the
    input, not from a switch: a leaf whose dim 0 does not divide is
    reduced and updated whole on every worker, and the whole optimizer
    stays on the replicated path (gradients all-gathered, state ``P()``)
    when its ``update`` is not elementwise in every non-scalar operand
    (``optimizer.trace_share_update``: a share's norm is not the
    leaf's), when a wire cast is set (parameters must not cross the wire
    below master precision) or when ``backward_passes_per_step > 1``;
    the gauges ``optimizer.sharded_bytes`` / ``optimizer.replicated_bytes``
    (label ``reason``) say which, a log line why.  A state that is not
    where the program wants it — fresh from ``create_train_state``,
    restored and broadcast from a checkpoint — is put there by the
    step's first call (a local slice; ``lower`` likewise lowers against
    the wanted shardings whatever it is handed), so callers keep handing
    it replicated state; ``checkpoint.save_checkpoint`` gathers it whole.

    .. note:: At ``world == 1`` (with ``backward_passes_per_step == 1``)
       the DistributedOptimizer wrapper is dropped — matching the
       reference's ``size()==1`` short-circuit — but any ``compression``
       passed is still honored through an equivalent local
       transformation (cast roundtrip, or error-feedback compression for
       biased registry schemes), so single- and multi-process runs see
       the same gradient numerics.  The ``opt_state`` pytree nesting
       still differs from the multi-worker chain, so **checkpoints do
       not transfer between world 1 and larger worlds**.  Between two
       worlds > 1 they do: a checkpoint holds every leaf whole, whatever
       the world that wrote it sharded.
    """
    axes = tuple(axes)
    world = 1
    for ax in axes:
        world *= mesh.shape[ax]
    layouts = None
    if world == 1 and backward_passes_per_step == 1:
        # Single-worker fast path (the reference likewise short-circuits
        # when size()==1): the push_pull wrapper is already a traced no-op
        # at world==1, but its chain nesting in opt_state costs per-call
        # dispatch on small models — drop the wrapper, keep the compression
        # numerics (a compressed multi-worker run and its single-worker
        # debug rerun must not silently diverge).
        comp_tx = _world1_compression_tx(compression)
        tx = scoped_update(optimizer)
        if comp_tx is not None:
            tx = optax.chain(comp_tx, tx)
    else:
        pb = partition_bytes or get_config().partition_bytes
        links = distributed_links(optimizer, compression, axes, True, pb,
                                  None, local_axis)
        tx = optax.chain(*links)
        if backward_passes_per_step > 1:
            tx = optax.MultiSteps(
                tx, every_k_schedule=backward_passes_per_step)
        scatter, sums = resolve_local_axis(axes, local_axis)
        wire = resolve_wire_dtype(resolve_compression(compression)[0])
        layouts = _UpdateLayouts(
            tx, links, mesh, scatter, sums, pb,
            "multi_step" if backward_passes_per_step > 1
            else "wire_cast" if wire is not None else None)

    def local_step(lay, state: TrainState, batch):
        def lf(p):
            # forward here; the backward carries the same scope inside
            # ``transpose(jvp(...))``
            with jax.named_scope(SCOPE_MODEL):
                loss, *aux = loss_fn(p, state.model_state, batch)
            return loss, aux

        (loss, (new_mstate, *counts)), grads = jax.value_and_grad(
            lf, has_aux=True)(state.params)
        if lay is not None and lay.sharded:
            new_params, new_opt = layouts.sharded_update(
                lay, grads, state.opt_state, state.params)
        else:
            # push_pull and the inner update name themselves (``tx``)
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            with jax.named_scope(SCOPE_OPTIMIZER):
                new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope(SCOPE_STEP_METRICS):
            n = jax.lax.psum(1, axes)
            loss = jax.lax.psum(loss, axes) / n
            # keep mutable model state (BN stats) replicated: average
            # across dp
            new_mstate = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, axes) / n
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                new_mstate,
            )
            metrics = {"loss": loss}
            for extra in counts:
                metrics.update({k: jax.lax.psum(v, axes)
                                for k, v in extra.items()})
        return (
            TrainState(new_params, new_opt, new_mstate, state.step + 1),
            metrics,
        )

    def step_fn(state: TrainState, batch):
        # the state's specs depend on its shapes (which leaves' dim 0
        # divides, what the optimizer's state holds), so the shard_map is
        # made where they are known: under the trace
        lay = layouts.of(state) if layouts is not None else None
        state_spec = TrainState(  # params / model state / step replicated
            P(), lay.opt_specs if lay is not None else P(), P(), P())
        return shard_map(
            lambda st, b: local_step(lay, st, b),
            mesh,
            in_specs=(state_spec, P(axes)),
            out_specs=(state_spec, P()),
        )(state, batch)

    jitted = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
    return TrainStep(jitted, tx, mesh, layouts, donate)


_REASONS = ("elementwise", "wire_cast", "multi_step", "dim0")


@dataclasses.dataclass(frozen=True)
class _UpdateLayout:
    """Where one parameter tree's update runs: the leaves (by index in
    the flattened tree) whose dim-0 share each worker updates and the
    rest, their bucket plans, and where every leaf of the optimizer's
    state lies (a share's moments ``P(scatter)`` on dim 0)."""

    sharded: Tuple[int, ...]
    rest: Tuple[int, ...]
    plan: Optional[partition.BucketPlan]        # of the sharded leaves
    rest_plan: Optional[partition.BucketPlan]   # flat, of the rest
    opt_specs: Any                       # pytree of P, as opt_state
    opt_shardings: Optional[List[Any]]   # per opt_state leaf; None = P()
    update: Any = None        # the caller's ``update``, traced on shares


class _UpdateLayouts:
    """The sharded update of ``make_data_parallel_step`` at world > 1:
    which path a parameter tree takes (read from its shapes and from the
    optimizer's own program, once per tree), where the state lies for
    it, and the update itself."""

    def __init__(self, tx, links, mesh, scatter, sums, partition_bytes,
                 gate: Optional[str]):
        self.tx, self.links, self.mesh = tx, links, mesh
        self.scatter, self.sums = scatter, tuple(sums)
        self.shards = mesh.shape[scatter]
        self.partition_bytes = partition_bytes
        self.gate = gate          # what keeps the whole optimizer replicated
        self._on_shares = NamedSharding(mesh, P(scatter))
        self._to_shares = jax.jit(lambda x: x,
                                  out_shardings=self._on_shares)
        self._cache = {}

    def of(self, state) -> _UpdateLayout:
        leaves, treedef = jax.tree_util.tree_flatten(state.params)
        key = (treedef, tuple((x.shape, x.dtype) for x in leaves))
        if key not in self._cache:
            self._cache[key] = self._build(state)
        return self._cache[key]

    def _build(self, state) -> _UpdateLayout:
        leaves, treedef = jax.tree_util.tree_flatten(state.params)
        leaves = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves]
        shards = self.shards
        sharded = () if self.gate else tuple(
            i for i, x in enumerate(leaves)
            if x.ndim and x.shape[0] and x.shape[0] % shards == 0)
        reason, inner_specs, update = self.gate or "dim0", None, None
        if sharded:
            # the caller's optimizer on shares against its state on whole
            # leaves: what changed shape is a share's (moments), the rest
            # (counts) every worker holds
            mixed = list(leaves)
            for i in sharded:
                mixed[i] = partition.dim0_share(leaves[i], shards)
            mixed = treedef.unflatten(mixed)
            inner = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                state.opt_state[-1])
            try:
                on_shares = jax.eval_shape(self.links[-1].init, mixed)
                update, refusal = trace_share_update(
                    self.links[-1], mixed, on_shares, mixed)
            except (TypeError, ValueError) as e:
                refusal = f"`init` not traceable on shares: {e!r}"
            if refusal is None:
                inner_specs = jax.tree_util.tree_map(
                    lambda w, s: P() if w.shape == s.shape
                    else P(self.scatter), inner, on_shares)
            else:
                bps_log.info("data-parallel update: the optimizer stays "
                             "on whole leaves (%s)", refusal)
                sharded, reason = (), "elementwise"
        nbytes = [x.size * x.dtype.itemsize for x in leaves]
        on_shares = sum(nbytes[i] for i in sharded)
        reg = get_registry()
        reg.gauge("optimizer.sharded_bytes").set(on_shares)
        for r in _REASONS:
            reg.gauge("optimizer.replicated_bytes", reason=r).set(
                sum(nbytes) - on_shares if r == reason else 0)
        bps_log.info(
            "data-parallel update: %d parameter bytes on dim-0 shares over "
            "%r (x%d), %d replicated (%s)", on_shares, self.scatter, shards,
            sum(nbytes) - on_shares, reason)
        if not sharded:
            return _UpdateLayout((), (), None, None, P(), None)
        front = state.opt_state[:-1]
        opt_shardings = [None] * len(jax.tree_util.tree_leaves(front)) + [
            None if spec == P() else self._on_shares
            for spec in jax.tree_util.tree_leaves(inner_specs)]
        rest = tuple(i for i in range(len(leaves)) if i not in set(sharded))
        rest_plan = partition.plan_buckets(
            [leaves[i] for i in rest], self.partition_bytes) if rest else None
        # (numbered after the rest's, whose flat pack names its buckets
        # by position)
        plan = partition.plan_share_buckets(
            [leaves[i] for i in sharded], shards, self.partition_bytes,
            first_id=rest_plan.num_buckets if rest else 0)
        return _UpdateLayout(sharded, rest, plan, rest_plan,
                             tuple(P() for _ in front) + (inner_specs,),
                             opt_shardings, update)

    def sharded_update(self, lay: _UpdateLayout, grads, opt_state, params):
        """``tx.update`` + ``apply_updates`` with the reduction split
        around the optimizer: what precedes the push_pull link runs as
        the chain runs it, each bucket is reduce-scattered, the caller's
        optimizer updates this worker's dim-0 share of every sharded leaf
        (and the other leaves whole, from a reduction of today's kind),
        and the new shares are all-gathered into whole parameters.
        Runs under the step's shard_map; returns ``(params, opt_state)``."""
        front = self.links[:-2]
        states = list(opt_state)
        for k, link in enumerate(front):
            grads, states[k] = link.update(grads, states[k], params)
        g, treedef = jax.tree_util.tree_flatten(grads)
        p = treedef.flatten_up_to(params)
        shares = reduce_scatter_tree(
            [g[i] for i in lay.sharded], lay.plan, self.scatter, self.sums)
        if lay.rest:
            reduced = push_pull_tree(
                [g[i] for i in lay.rest], plan=lay.rest_plan,
                scatter_axis=self.scatter, sum_axes=self.sums)
            for i, x in zip(lay.rest, reduced):
                g[i] = x
        with jax.named_scope(SCOPE_OPTIMIZER):
            r = jax.lax.axis_index(self.scatter)
            for i, x in zip(lay.sharded, shares):
                g[i] = x
                p[i] = jax.lax.dynamic_slice_in_dim(
                    p[i], r * x.shape[0], x.shape[0], axis=0,
                    allow_negative_indices=False)
            p_mixed = treedef.unflatten(p)
            # (the program ``_build`` traced and found elementwise)
            updates, states[-1] = lay.update(
                treedef.unflatten(g), states[-1], p_mixed)
            new = treedef.flatten_up_to(
                optax.apply_updates(p_mixed, updates))
        gathered = all_gather_tree(
            [new[i] for i in lay.sharded], lay.plan, self.scatter)
        for i, x in zip(lay.sharded, gathered):
            new[i] = x
        return treedef.unflatten(new), tuple(states)

    def place(self, state, donate: bool):
        """``state`` with every leaf of the optimizer's state that the
        program wants on dim-0 shares put there: a local slice of a
        replicated leaf, its whole copy given up leaf by leaf where the
        step would donate it anyway.  A leaf already there is left alone
        — all of them, from the step's second call on — and the check
        touches no device."""
        lay = self.of(state)
        if lay.opt_shardings is None:
            return state
        leaves, treedef = jax.tree_util.tree_flatten(state.opt_state)
        moved = False
        for k, want in enumerate(lay.opt_shardings):
            x = leaves[k]
            have = getattr(x, "sharding", None)
            if want is None or have == want or (
                    have is not None
                    and have.is_equivalent_to(want, x.ndim)):
                continue
            # a program, so that a replicated leaf is sliced where it lies
            # (``device_put`` would take it through the host)
            leaves[k] = self._to_shares(x)
            if donate and isinstance(x, jax.Array):
                # (the device frees it once the slice has read it)
                x.delete()
            moved = True
        if not moved:
            return state
        return state._replace(opt_state=treedef.unflatten(leaves))

    def abstract(self, state):
        """``state`` as the program is lowered against it, whatever it
        was handed (concrete, or abstract and replicated as
        ``benchmark/aot_check.py`` builds it): a share's moments on
        their shares, every other leaf as it came."""
        lay = self.of(state)
        if lay.opt_shardings is None:
            return state
        leaves, treedef = jax.tree_util.tree_flatten(state.opt_state)
        return state._replace(opt_state=treedef.unflatten([
            x if want is None else jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=want)
            for x, want in zip(leaves, lay.opt_shardings)]))


# counts of steps already dispatched whose values the device has yet to
# produce, oldest first
_PENDING: collections.deque = collections.deque()


def _count_step(counts) -> None:
    """Each count of one step grows the registry counter of its name
    (``moe_assignments_held`` -> ``moe.assignments_held``: the layer's
    prefix becomes the registry's dotted one), ``train.steps_counted``
    by one."""
    reg = get_registry()
    reg.counter("train.steps_counted", instants=False).inc()
    for name, n in counts.items():
        reg.counter(name.replace("_", ".", 1), instants=False).inc(int(n))


def flush_step_counts(wait: bool = True) -> None:
    """Add the counts of the steps dispatched so far to the registry —
    all of them (waits for the device), or with ``wait=False`` those
    whose values are there already."""
    while _PENDING and (wait or all(
            v.is_ready() for v in _PENDING[0].values())):
        _count_step(_PENDING.popleft())


def _note_counts(metrics) -> None:
    """Host side of a step whose loss function counts, after its
    dispatch: the counts wait in ``_PENDING`` until the device has them
    (no host callback in the program, no wait on the step just sent)."""
    if len(metrics) > 1:
        _PENDING.append({k: v for k, v in metrics.items() if k != "loss"})
        flush_step_counts(wait=False)


class _Counting:
    """A lowered or compiled train step whose calls feed the registry's
    counters as ``TrainStep.__call__`` does; everything else
    (``as_text``, ``memory_analysis``, ...) is the wrapped object's."""

    def __init__(self, inner, place):
        self._inner = inner
        self._place = place

    def compile(self, *args, **kwargs):
        return _Counting(self._inner.compile(*args, **kwargs), self._place)

    def __call__(self, state, batch):
        out = self._inner(self._place(state), batch)
        _note_counts(out[1])
        return out

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TrainStep:
    """Callable train step bundling the jitted SPMD program with the
    *wrapped* optimizer (DistributedOptimizer chain) whose state layout the
    program expects — use ``init_state`` to build a matching TrainState."""

    def __init__(self, fn, tx: optax.GradientTransformation, mesh: Mesh,
                 layouts: Optional[_UpdateLayouts] = None,
                 donate: bool = True):
        self._fn = fn
        self.tx = tx
        self.mesh = mesh
        self._layouts = layouts
        self._donate = donate

    def _place(self, state):
        """The state where the program wants it (``_UpdateLayouts.place``;
        nothing to do at world 1)."""
        if self._layouts is None:
            return state
        return self._layouts.place(state, self._donate)

    def __call__(self, state, batch):
        out = self._fn(self._place(state), batch)
        _note_counts(out[1])
        return out

    def init_state(self, params, model_state=None) -> TrainState:
        state = create_train_state(params, self.tx, model_state=model_state)
        return self._place(replicate_state(state, self.mesh))

    def lower(self, state, batch):
        if self._layouts is not None:
            state = self._layouts.abstract(state)
        return _Counting(self._fn.lower(state, batch), self._place)


def make_zero_step(loss_fn, zero, model_state=None, reduce_grads=None):
    """Eager ZeRO-1 train step over the PS tier (training/zero.py).

    ``loss_fn(params, model_state, batch) -> (loss, new_model_state)``
    with ``params`` a flat ``{name: array}`` dict (the replica ``zero``
    holds); the backward pass is jitted, the optimizer/wire half runs
    on the host through ``zero.step`` (push owned span deltas, pull the
    rest — docs/parallel.md).  Returns ``step(batch) -> loss``.

    ``reduce_grads`` maps this worker's raw gradients to the
    group-reduced gradients ``zero.step`` requires (e.g. stacking over
    colocated workers through ``collectives.reduce_scatter_spans``, or
    an allreduce); None means the gradients are already reduced — the
    single-worker / pre-reduced harness case.  Mutable model state is
    not threaded (this is the eager PS path, not
    ``make_data_parallel_step``); pass BN-free losses."""
    import numpy as np

    ms = {} if model_state is None else model_state

    def lf(p, b):
        with jax.named_scope(SCOPE_MODEL):
            return loss_fn(p, ms, b)[0]

    grad_fn = jax.jit(jax.value_and_grad(lf))

    def step(batch):
        loss, grads = grad_fn(zero.params, batch)
        g = {n: np.asarray(v) for n, v in grads.items()}
        if reduce_grads is not None:
            g = reduce_grads(g)
        zero.step(g)
        return float(loss)

    return step


def shard_batch(batch, mesh: Mesh, axes: Sequence[str] = ("dp",)):
    """Place a host batch on the mesh, dim 0 sharded over ``axes``."""
    sharding = NamedSharding(mesh, P(tuple(axes)))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch
    )


def replicate_state(state, mesh: Mesh):
    sharding = NamedSharding(mesh, P())
    # Copy committed jax.Arrays before placing: device_put may alias their
    # buffers into the replicated output, and TrainState is donated into the
    # jitted step — without the copy, donation would delete the caller's
    # arrays too.  Host (numpy/scalar) leaves are always copied by
    # device_put itself, so no extra materialization for them.
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            jnp.array(x) if isinstance(x, jax.Array) else x, sharding
        ),
        state,
    )


def lm_loss_fn(model, fused_head: bool = False,
               block_n: Optional[int] = None, block_v: Optional[int] = None,
               early_exit: Optional[tuple] = None):
    """Next-token cross-entropy loss closure for a causal LM whose batch
    is ``{"tokens": [B, T]}``; fits ``make_data_parallel_step``.

    ``early_exit=(layers, weight)`` adds the LayerSkip auxiliary loss:
    ``weight * CE(first-`layers` exit)`` where the exit is the model's
    own ``ln_f`` + head applied to the truncated depth — exactly the
    truncation ``inference.truncated_draft`` builds, so a model trained
    with this term accepts its own truncated self-draft under
    speculative decoding.  Without it the early-exit readout is
    untrained and the draft is useless no matter how well the full
    model converges (measured: acceptance ~0.002 on a converged
    vanilla-trained 12L model vs 0.70-0.88 with the term).  Requires a
    ``models.transformer.Transformer`` (the truncation slices its
    ``block_i`` param subtree).

    ``fused_head=True`` routes through the Pallas fused LM-head kernel
    (ops/fused_cross_entropy.py): the model's ``hidden`` method supplies
    pre-head states and the ``lm_head`` kernel multiplies inside the
    fused op — the [B, T, vocab] logits never materialize.  The full
    B*T rows go to the kernel (keeping N block-divisible for typical
    sequence lengths); the shift-off last position rides the kernel's
    ignore-index semantics (out-of-range target → loss 0, no grad).
    Requires a model exposing ``hidden`` plus either an ``lm_head``
    Dense or tied embeddings (models/transformer.Transformer, either
    way; for tied models the head weight is the embedding transpose).
    Where the model has a multi-token-prediction module
    (``cfg.mtp_layers``) the loss gains ``cfg.mtp_loss_weight`` times the
    module's cross-entropy on the token after next, through the same
    head; where it has expert layers the closure returns a third
    element, ``{"moe_assignments_held": n, "moe_rows_computed": r}``
    (``parallel/moe.py:served``, summed over its expert layers), which
    the step reports beside ``loss``.
    ``block_n``/``block_v`` are overrides handed to the kernels as they
    are; left ``None`` each of the three kernels reads its own blocks
    from ``(N, d, vocab)`` and the VMEM it may use
    (``ops/fused_cross_entropy.py:choose_blocks``).  They are no remedy
    for a table coprime to 128 such as GPT-2's 50257: pad the table to
    a multiple of 128 (the benchmark's gpt2 cells build 50304 rows).

    Padded streams: pass ``batch["labels"]`` with ``-100`` on ignored
    positions (the HF convention; ``tokens`` keep an embeddable pad id).
    The mean is over *valid* targets — ignored positions contribute
    neither loss nor denominator, in both the fused and plain branches.
    """

    def _head_weight(params, h):
        if "lm_head" in params:
            return params["lm_head"]["kernel"].astype(h.dtype)
        # tied-embedding models (tie_embeddings=True) have no
        # lm_head; the head weight is the embedding transposed.
        # tp-partitioned trees box the leaf in nn.Partitioned.
        import flax.linen as nn

        emb = params["embed"]["embedding"]
        if isinstance(emb, nn.meta.AxisMetadata):
            emb = emb.unbox()
        return emb.T.astype(h.dtype)

    def _hiddens(params, m, tokens):
        """``((h, h_mtp | None), n)``: the pre-head states — with a
        multi-token-prediction module its state too — and the expert
        layers' counts summed over the layers, ``{"moe_<count>": n}``
        (None without experts)."""
        cfg = getattr(m, "cfg", None)
        method = m.hidden_mtp if getattr(cfg, "mtp_layers", 0) else m.hidden
        if getattr(cfg, "moe_experts", 0):
            hs, stats = m.apply({"params": params}, tokens, method=method,
                                mutable=["moe_stats"])
            n = {}
            for path, leaf in jax.tree_util.tree_leaves_with_path(stats):
                name = f"moe_{path[-1].key}"
                n[name] = n.get(name, 0) + leaf
        else:
            hs, n = m.apply({"params": params}, tokens, method=method), None
        return (hs if isinstance(hs, tuple) else (hs, None)), n

    def _mtp_targets(targets):
        # position t of the module predicts token t + 2
        return jnp.roll(targets, -1, axis=1).at[:, -1].set(-100)

    def _with_mtp(head_ce, m, h, h_mtp, targets):
        loss = head_ce(h, targets)
        if h_mtp is not None:
            loss = loss + m.cfg.mtp_loss_weight * head_ce(
                h_mtp, _mtp_targets(targets))
        return loss

    def _fused_ce(params, m, tokens, targets):
        from ..ops.fused_cross_entropy import fused_linear_cross_entropy

        (h, h_mtp), n = _hiddens(params, m, tokens)

        def head_ce(h, targets):
            B, T, d = h.shape
            V = w.shape[-1]
            flat_t = targets.reshape(-1)
            per_row = fused_linear_cross_entropy(
                h.reshape(-1, d), w, flat_t, block_n, block_v,
            )
            # mean over *valid* targets only: with padded token streams
            # (HF -100 convention) a fixed B*(T-1) denominator deflates
            # the loss; the kernel already zeroes ignored rows
            valid = jnp.sum((flat_t >= 0) & (flat_t < V))
            return per_row.sum() / jnp.maximum(valid, 1).astype(
                per_row.dtype)

        with jax.named_scope(SCOPE_HEAD):
            w = _head_weight(params, h)
            return _with_mtp(head_ce, m, h, h_mtp, targets), n

    def _plain_ce(params, m, tokens, targets):
        def ce_of(logits, targets):
            with jax.named_scope(SCOPE_HEAD):
                t = targets[:, :-1]
                valid = (t >= 0) & (t < logits.shape[-1])
                # optax's integer-label CE has no ignore-index:
                # out-of-range labels produce garbage — clamp them and
                # zero their loss
                per_tok = optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], jnp.where(valid, t, 0)
                )
                per_tok = jnp.where(valid, per_tok, 0.0)
                return per_tok.sum() / jnp.maximum(valid.sum(), 1).astype(
                    per_tok.dtype)

        cfg = getattr(m, "cfg", None)
        if not (getattr(cfg, "mtp_layers", 0)
                or getattr(cfg, "moe_experts", 0)):
            # the head's matmul is inside the model's own call here (its
            # Flax scope names it); the scope covers the CE over its
            # logits
            return ce_of(m.apply({"params": params}, tokens), targets), None
        (h, h_mtp), n = _hiddens(params, m, tokens)
        return _with_mtp(
            lambda h, t: ce_of(m.apply({"params": params}, h,
                                       method=m.logits), t),
            m, h, h_mtp, targets), n

    ce = _fused_ce if fused_head else _plain_ce

    def loss_fn(params, model_state, batch):
        tokens = batch["tokens"]
        if "labels" in batch:
            # HF convention: explicit labels with -100 on padded/ignored
            # positions (tokens themselves must stay embeddable pad ids)
            targets = jnp.roll(batch["labels"], -1, axis=1)
        else:
            targets = jnp.roll(tokens, -1, axis=1)
        targets = targets.at[:, -1].set(-100)  # ignore the wrap position
        loss, held = ce(params, model, tokens, targets)
        if early_exit is not None:
            from ..inference import truncated_draft

            e_layers, e_weight = early_exit
            # truncated_draft only filters the pytree, so it traces
            # cleanly under jit/grad — and it is the SAME truncation
            # speculative_generate runs at decode time, keeping the
            # trained exit and the runtime draft in lockstep
            dmodel, dvars = truncated_draft(
                model.cfg, {"params": params}, e_layers)
            loss = loss + e_weight * ce(
                dvars["params"], dmodel, tokens, targets)[0]
        if held is not None:
            return loss, model_state, held
        return loss, model_state

    return loss_fn


def classification_loss_fn(model, train: bool = True, rngs_fn=None):
    """Standard softmax-CE loss closure for a flax vision model with
    (optional) BatchNorm state; fits ``make_data_parallel_step``."""

    def loss_fn(params, model_state, batch):
        images, labels = batch["image"], batch["label"]
        variables = {"params": params, **model_state}
        mutable = list(model_state.keys())
        kwargs = {}
        if rngs_fn is not None:
            kwargs["rngs"] = rngs_fn()
        if mutable:
            logits, new_state = model.apply(
                variables, images, train=train, mutable=mutable, **kwargs
            )
        else:
            logits = model.apply(variables, images, train=train, **kwargs)
            new_state = {}
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()
        return loss, new_state

    return loss_fn
