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
the optimizer on this worker's share and leaves the new parameters on
their shares until the next step, whose forward all-gathers them while
the layers ahead of them compute (ByteScheduler's cross-iteration
overlap: a layer waits only for its own parameters).  The flat bucketed
``push_pull_tree`` remains for the replicated path and the eager API,
and has no benchmark cell at world > 1.

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
from ..common.tracing import (SCOPE_HEAD, SCOPE_LOOP_READOUT,
                              SCOPE_MODEL, SCOPE_OPTIMIZER,
                              SCOPE_STEP_METRICS)
from ..ops.compression import Compression
from .checkpoint import whole_on_every_process
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
    (``lm_loss_fn``: ``moe_assignments_held``, ``moe_rows_computed``,
    ``moe_dma_rows{op=...}``)
    or of means (a looped model's ``loop_ce<t>``, ``loop_exit_p<t>``);
    they join the metrics, a count summed over the workers and a mean
    averaged, and every call of the step adds a count to the registry's
    counter of its name and sets a mean's gauge
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
    with its share of the moments, and the new parameter shares stay
    where they are until the next step, whose forward all-gathers them
    (the gathers of the last layers run behind the first layers'
    compute): the same bytes on the wire as reducing and gathering the
    gradient, 1/shards of the update's memory traffic, the parameters
    between steps and the moments held once across the axis.  Both
    collectives follow one plan
    (``partition.plan_share_buckets``): a leaf whose share fills a
    bucket (``partition_bytes / shards``) goes by itself, as it lies —
    the scatter of a leaf along dim 0 leaves its dim-0 share, the gather
    of the shares IS the leaf, nothing is packed or unpacked —, the
    small leaves share buckets, in ``schedule_order``.  The
    optimizer sees the parameters' own treedef and ``ndim`` (path- and
    ``ndim``-based masks keep their meaning); ``state.opt_state`` keeps
    the ``DistributedOptimizer`` chain's treedef and the parameters'
    global shapes, a share's moments placed ``P(scatter_axis)`` on dim
    0; ``state.params`` keeps the global shapes too, a sharded leaf
    placed ``P(scatter_axis)`` on dim 0 between steps.  Read the
    parameters whole through ``TrainStep.whole_params(state)``: in a
    multi-process job no process holds a share's leaf whole, and every
    process calls it.
    Which path runs is read from the input, not from a switch: a leaf
    whose dim 0 does not divide is reduced and updated whole on every
    worker, and the whole optimizer stays on the replicated path
    (gradients all-gathered, parameters and state ``P()``) when its
    ``update`` is not elementwise in every non-scalar operand
    (``optimizer.trace_share_update``: a share's norm is not the
    leaf's), when a wire cast is set (parameters must not cross the wire
    below master precision) or when ``backward_passes_per_step > 1``;
    the gauges ``optimizer.sharded_bytes`` / ``optimizer.replicated_bytes``
    (label ``reason``) say which, a log line why, and
    ``optimizer.forward_gather_bytes`` (the same value as
    ``optimizer.sharded_bytes``, both 0 on the replicated path and at
    world 1) how many parameter bytes the forward gathers.  A state
    that is not where the program wants it — fresh from
    ``create_train_state``, restored and broadcast from a checkpoint —
    is put there by the step's first call (a local slice; ``lower``
    likewise lowers against the wanted shardings whatever it is
    handed), so callers keep handing it replicated state;
    ``checkpoint.save_checkpoint`` gathers it whole.

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
        _note_shares(0)
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

        on_shares = lay is not None and lay.sharded
        # the shares' gathers lie outside the differentiated function:
        # the gradient is taken of the whole leaves, and reduce-scattered
        # by the bucket plan, not by the gather's transpose
        whole = (layouts.gather_shares(lay, state.params) if on_shares
                 else state.params)
        (loss, (new_mstate, *counts)), grads = jax.value_and_grad(
            lf, has_aux=True)(whole)
        if on_shares:
            new_params, new_opt = layouts.sharded_update(
                lay, grads, state.opt_state, whole, state.params)
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
                # a count is summed over the workers, a mean averaged
                metrics.update({
                    k: jax.lax.psum(v, axes) / n
                    if jnp.issubdtype(jnp.result_type(v), jnp.floating)
                    else jax.lax.psum(v, axes) for k, v in extra.items()})
        return (
            TrainState(new_params, new_opt, new_mstate, state.step + 1),
            metrics,
        )

    def step_fn(state: TrainState, batch):
        # the state's specs depend on its shapes (which leaves' dim 0
        # divides, what the optimizer's state holds), so the shard_map is
        # made where they are known: under the trace
        lay = layouts.of(state) if layouts is not None else None
        state_spec = TrainState(  # model state / step replicated
            *(lay.specs if lay is not None else (P(), P())), P(), P())
        return shard_map(
            lambda st, b: local_step(lay, st, b),
            mesh,
            in_specs=(state_spec, P(axes)),
            out_specs=(state_spec, P()),
        )(state, batch)

    jitted = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
    return TrainStep(jitted, tx, mesh, layouts, donate)


_REASONS = ("elementwise", "wire_cast", "multi_step", "dim0")
def _note_shares(on_shares: int) -> None:
    """``optimizer.sharded_bytes`` and ``optimizer.forward_gather_bytes``
    from one value, on every path: a leaf updated on its dim-0 shares
    lies on them between steps and is gathered in the next forward."""
    reg = get_registry()
    for name in ("optimizer.sharded_bytes",
                 "optimizer.forward_gather_bytes"):
        reg.gauge(name).set(on_shares)


@dataclasses.dataclass(frozen=True)
class _UpdateLayout:
    """Where one parameter tree's update runs: the leaves (by index in
    the flattened tree) whose dim-0 share each worker updates and the
    rest, their bucket plans, and where every leaf of the parameters and
    of the optimizer's state lies between steps (a share's parameter
    and moments ``P(scatter)`` on dim 0)."""

    sharded: Tuple[int, ...]
    rest: Tuple[int, ...]
    plan: Optional[partition.BucketPlan]        # of the sharded leaves
    rest_plan: Optional[partition.BucketPlan]   # flat, of the rest
    specs: Any        # pytrees of P, as (params, opt_state)
    # per leaf of ``(params, opt_state)``; None = P()
    shardings: Optional[List[Any]]
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
        _note_shares(on_shares)
        reg = get_registry()
        for r in _REASONS:
            reg.gauge("optimizer.replicated_bytes", reason=r).set(
                sum(nbytes) - on_shares if r == reason else 0)
        bps_log.info(
            "data-parallel update: %d parameter bytes on dim-0 shares over "
            "%r (x%d), %d replicated (%s)", on_shares, self.scatter, shards,
            sum(nbytes) - on_shares, reason)
        if not sharded:
            return _UpdateLayout((), (), None, None, (P(), P()), None)
        front = state.opt_state[:-1]
        held = set(sharded)
        param_specs = [P(self.scatter) if i in held else P()
                       for i in range(len(leaves))]
        specs = (treedef.unflatten(param_specs),
                 tuple(P() for _ in front) + (inner_specs,))
        shardings = [
            None if spec == P() else self._on_shares
            for spec in param_specs
            + [P()] * len(jax.tree_util.tree_leaves(front))
            + jax.tree_util.tree_leaves(inner_specs)]
        rest = tuple(i for i in range(len(leaves)) if i not in held)
        rest_plan = partition.plan_buckets(
            [leaves[i] for i in rest], self.partition_bytes) if rest else None
        # (numbered after the rest's, whose flat pack names its buckets
        # by position)
        plan = partition.plan_share_buckets(
            [leaves[i] for i in sharded], shards, self.partition_bytes,
            first_id=rest_plan.num_buckets if rest else 0)
        return _UpdateLayout(sharded, rest, plan, rest_plan, specs,
                             shardings, update)

    def gather_shares(self, lay: _UpdateLayout, params):
        """The whole parameters from ``params`` as they lie between steps:
        the sharded leaves' shares all-gathered, bucket by bucket in
        priority order, the rest as they are.  Runs under the step's
        shard_map, ahead of the forward that needs them."""
        p, treedef = jax.tree_util.tree_flatten(params)
        gathered = all_gather_tree(
            [p[i] for i in lay.sharded], lay.plan, self.scatter)
        for i, x in zip(lay.sharded, gathered):
            p[i] = x
        return treedef.unflatten(p)

    def sharded_update(self, lay: _UpdateLayout, grads, opt_state, whole,
                       params):
        """``tx.update`` + ``apply_updates`` with the reduction split
        around the optimizer: what precedes the push_pull link runs as
        the chain runs it (on the ``whole`` parameters), each bucket is
        reduce-scattered, and the caller's optimizer updates this
        worker's dim-0 share of every sharded leaf — ``params`` holds the
        shares, as they lie between steps — and the other leaves whole,
        from a reduction of today's kind.  Runs under the step's
        shard_map; returns ``(params, opt_state)``, the new parameters on
        their shares."""
        front = self.links[:-2]
        states = list(opt_state)
        for k, link in enumerate(front):
            grads, states[k] = link.update(grads, states[k], whole)
        g, treedef = jax.tree_util.tree_flatten(grads)
        shares = reduce_scatter_tree(
            [g[i] for i in lay.sharded], lay.plan, self.scatter, self.sums)
        if lay.rest:
            reduced = push_pull_tree(
                [g[i] for i in lay.rest], plan=lay.rest_plan,
                scatter_axis=self.scatter, sum_axes=self.sums)
            for i, x in zip(lay.rest, reduced):
                g[i] = x
        with jax.named_scope(SCOPE_OPTIMIZER):
            for i, x in zip(lay.sharded, shares):
                g[i] = x
            # (the program ``_build`` traced and found elementwise)
            updates, states[-1] = lay.update(
                treedef.unflatten(g), states[-1], params)
            new = optax.apply_updates(params, updates)
        return new, tuple(states)

    def place(self, state, donate: bool):
        """``state`` with every leaf of the parameters and of the
        optimizer's state that the program wants on dim-0 shares put
        there: a local slice of a replicated leaf, its whole copy given
        up leaf by leaf where the step would donate it anyway.  A leaf
        already there is left alone — all of them, from the step's
        second call on — and the check touches no device."""
        lay = self.of(state)
        if lay.shardings is None:
            return state
        leaves, treedef = jax.tree_util.tree_flatten(
            (state.params, state.opt_state))
        moved = False
        for k, want in enumerate(lay.shardings):
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
        params, opt_state = treedef.unflatten(leaves)
        return state._replace(params=params, opt_state=opt_state)

    def abstract(self, state):
        """``state`` as the program is lowered against it, whatever it
        was handed (concrete, or abstract and replicated as
        ``benchmark/aot_check.py`` builds it): a share's parameter and
        moments on their shares, every other leaf as it came."""
        lay = self.of(state)
        if lay.shardings is None:
            return state
        leaves, treedef = jax.tree_util.tree_flatten(
            (state.params, state.opt_state))
        params, opt_state = treedef.unflatten([
            x if want is None else jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=want)
            for x, want in zip(leaves, lay.shardings)])
        return state._replace(params=params, opt_state=opt_state)


# counts of steps already dispatched whose values the device has yet to
# produce, oldest first
_PENDING: collections.deque = collections.deque()


def _count_step(counts) -> None:
    """Each count of one step grows the registry counter of its name
    (``moe_assignments_held`` -> ``moe.assignments_held``: the layer's
    prefix becomes the registry's dotted one; ``moe_dma_rows{op=combine}``
    -> ``moe.dma_rows`` labelled ``op=combine``), ``train.steps_counted``
    by one; a mean (``loop_ce1``) sets the gauge of its name to the
    step's value."""
    reg = get_registry()
    reg.counter("train.steps_counted", instants=False).inc()
    for name, n in counts.items():
        dotted, _, label = name.replace("_", ".", 1).partition("{")
        labels = dict(kv.split("=") for kv in label[:-1].split(",")
                      if kv)
        if jnp.issubdtype(jnp.result_type(n), jnp.floating):
            reg.gauge(dotted, **labels).set(float(n))
        else:
            reg.counter(dotted, instants=False, **labels).inc(int(n))


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

    def whole_params(self, state):
        """``state.params`` whole: every leaf the program keeps on its
        dim-0 shares between steps all-gathered to a replicated array of
        its global shape, the rest as they lie.  The way to read the
        parameters outside the step; in a multi-process job no process
        holds a share's leaf whole, and the gather is a collective: EVERY
        process calls this, in the same order."""
        return whole_on_every_process(state.params)

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


def _head_weight(params, h):
    """The LM head's ``[d, vocab]`` weight in ``h``'s dtype."""
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


def loop_readouts(params, model, tokens, targets, fused_head: bool = True,
                  block_n: Optional[int] = None,
                  block_v: Optional[int] = None):
    """The R readouts of a looped model (``cfg.loop_steps`` > 1) on
    ``tokens [B, T]`` with next-token ``targets [B, T]`` (out of range:
    ignored): ``(ce [R, B*T], log_p [R, B*T], valid [B*T])`` — each
    pass's cross-entropy of its normed state through the one head (zero
    on an ignored row), the exit distribution in logs
    (``models/transformer.py:exit_log_probs``) and which rows count.
    The passes' heads run as one ``lax.map`` over the states: one head
    program, whatever R.  Everything after the stack lies under
    ``bps.head/loop_readout``."""
    from ..models.transformer import exit_log_probs

    states, exit_logits = model.apply({"params": params}, tokens,
                                      method=model.hidden)
    R, B, T, d = states.shape
    flat_t = targets.reshape(-1)
    vocab = model.cfg.vocab_size
    valid = (flat_t >= 0) & (flat_t < vocab)
    with jax.named_scope(SCOPE_HEAD), jax.named_scope(SCOPE_LOOP_READOUT):
        if fused_head:
            from ..ops.fused_cross_entropy import fused_linear_cross_entropy

            w = _head_weight(params, states)

            def row_ce(h):
                return fused_linear_cross_entropy(h.reshape(-1, d), w,
                                                  flat_t, block_n, block_v)
        else:
            def row_ce(h):
                logits = model.apply({"params": params}, h,
                                     method=model.logits)
                return jnp.where(
                    valid, optax.softmax_cross_entropy_with_integer_labels(
                        logits.reshape(-1, vocab),
                        jnp.where(valid, flat_t, 0)), 0.0)

        ce = jax.lax.map(row_ce, states)
        return ce, exit_log_probs(exit_logits.reshape(R, -1)), valid


# the exit distribution's entropy weight in a looped model's loss: the
# Ouro paper's Stage-I pre-training setting
EXIT_ENTROPY_WEIGHT = 0.1


def exit_weighted_loss(ce, log_p, valid, entropy_weight: float):
    """``(loss, means)`` of a looped model's readouts (``loop_readouts``):
    the mean over valid rows of the exit distribution's expected
    cross-entropy ``sum_t p_t ce_t`` less ``entropy_weight`` times its
    entropy ``-sum_t p_t log p_t``; ``means`` holds each pass's mean
    cross-entropy (``loop_ce<t>``) and exit probability
    (``loop_exit_p<t>``), t from 1."""
    p = jnp.exp(log_p)
    per_row = jnp.sum(p * ce, axis=0) + entropy_weight * jnp.sum(
        p * log_p, axis=0)
    n = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)

    def mean(rows):
        return jnp.sum(jnp.where(valid, rows, 0.0)) / n

    means = {}
    for t in range(ce.shape[0]):
        means[f"loop_ce{t + 1}"] = mean(ce[t])
        means[f"loop_exit_p{t + 1}"] = mean(p[t])
    return mean(per_row), means


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
    (``parallel/moe.py:served``, summed over its expert layers; with
    ``moe_dma_rows{op=...}`` where a gather moves its rows by DMA:
    ``moved``), which the step reports beside ``loss``.  A looped model
    (``cfg.loop_steps`` R > 1) reads its R states out through the same
    head (``loop_readouts``) and returns the exit distribution's
    expected cross-entropy less ``EXIT_ENTROPY_WEIGHT`` times its
    entropy (``exit_weighted_loss``), with each pass's mean
    cross-entropy and exit probability as the third element.
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
    cfg = getattr(model, "cfg", None)
    looped = getattr(cfg, "loop_steps", 1) > 1
    if looped and early_exit is not None:
        raise ValueError("a looped model has an exit gate of its own; "
                         "early_exit truncates a stack run once")

    def _looped(params, m, tokens, targets):
        readouts = loop_readouts(params, m, tokens, targets, fused_head,
                                 block_n, block_v)
        with jax.named_scope(SCOPE_HEAD), jax.named_scope(
                SCOPE_LOOP_READOUT):
            return exit_weighted_loss(*readouts, EXIT_ENTROPY_WEIGHT)

    if looped:
        ce = _looped

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
