"""DistributedOptimizer — the optax rendering of the reference's
``byteps.torch.DistributedOptimizer`` (torch/__init__.py:98-231) and
``DistributedTrainer`` (mxnet/__init__.py:142-204).

The reference hooks the framework's autograd to push_pull each gradient as
it materializes, then ``synchronize()``s before the optimizer step.  In JAX
the whole step is one traced program, so the same behavior is expressed
compositionally: a gradient transformation that allreduces (bucketed, in
priority order) sits in front of the user's optimizer, and XLA overlaps the
resulting collective chain with the backward compute the same way BytePS's
background threads overlapped NCCL with autograd.

``backward_passes_per_step`` (reference torch/__init__.py:107-154) is
honored via optax.MultiSteps: gradients accumulate locally for k steps and
only the k-th triggers communication — the same wire traffic reduction.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Sequence, Union

import jax
import optax

from ..common.config import get_config
from ..common.partition import BucketPlan
from ..common.tracing import SCOPE_OPTIMIZER
from ..ops.compression import Compression
from ..parallel.collectives import push_pull_tree


class PushPullState(NamedTuple):
    """No dynamic state; the bucket plan is trace-time static."""


def resolve_compression(compression):
    """Split a compression spec into ``(cast_compressor, ef_tx)``.

    Cast specs (Compressor classes, ``"none"``/``"bf16"``/``"fp16"``)
    ride the collective's ``wire_dtype`` hook unchanged.  Biased registry
    schemes (``"onebit"``/``"topk"``/``"randomk"``/``"int8"``) become an
    ``error_feedback_compress`` transformation chained BEFORE the
    communication — compress after local aggregation, before the wire —
    with the residual living in the optimizer state (donated,
    checkpointable; compression/error_feedback.py).
    """
    if compression is None:
        return Compression.none, None
    if isinstance(compression, str):
        from ..compression import error_feedback_compress, get_scheme

        scheme = get_scheme(compression)
        if scheme.name in ("none", "bf16", "fp16"):
            return getattr(Compression, scheme.name), None
        return Compression.none, error_feedback_compress(scheme)
    # a registry adapter class (ops.compression.Compression.resolve) carries
    # its Scheme: route biased ones to EF exactly like their string
    # spelling — the cast path would silently ignore them (wire_dtype=None)
    scheme = getattr(compression, "scheme", None)
    if scheme is not None and scheme.biased:
        from ..compression import error_feedback_compress

        return Compression.none, error_feedback_compress(scheme)
    return compression, None


def resolve_local_axis(axes: Sequence[str],
                       local_axis: Optional[str]) -> tuple:
    """Split the reduce axes into ``(scatter_axis, sum_axes)`` — the
    hierarchical structure of the 3-level reduction (docs/wire.md
    "Hierarchical reduction"): the *local* axis (ICI — the reference's
    NCCL reduce-scatter group) is scattered over, everything else (DCN /
    the PS tier) is summed on the scattered shard.  Default: the
    innermost (last) axis, the mesh convention.  ``local_axis`` pins it
    explicitly and is validated against the reduce axes — a wrong local
    axis would scatter over the slow tier and sum over the fast one,
    silently inverting the bandwidth argument."""
    axes = tuple(axes)
    if local_axis is None:
        return axes[-1], axes[:-1]
    if local_axis not in axes:
        raise ValueError(
            f"local_axis={local_axis!r} is not one of the reduce axes "
            f"{axes} — the local reduce-scatter must run over a mesh "
            "axis the gradients are reduced across")
    return local_axis, tuple(a for a in axes if a != local_axis)


def resolve_wire_dtype(cast):
    """The dtype a cast spec puts on the wire: the Compressor class's,
    else the environment's (``BYTEPS_WIRE_DTYPE``), else None — the
    payload crosses in its own precision."""
    wire = getattr(cast, "wire_dtype", None)
    return get_config().wire_jnp_dtype if wire is None else wire


# What an ``update`` may do to a non-scalar operand and still mean the
# same on a worker's share of every leaf as on the whole leaf: act on
# each element alone.  A reduction, a product, a sort or a gather over a
# leaf (a global norm, a trust ratio, factored moments) is in no such
# list; nor is anything that draws random bits.
_ELEMENTWISE = frozenset({
    "abs", "add", "and", "atan2", "cbrt", "ceil", "clamp",
    "convert_element_type", "copy", "cos", "div", "eq", "erf", "erf_inv",
    "exp", "exp2", "expm1", "floor", "ge", "gt", "integer_pow", "is_finite",
    "le", "log", "log1p", "logistic", "lt", "max", "min", "mul", "ne", "neg",
    "nextafter", "not", "or", "pow", "rem", "round", "rsqrt", "select_n",
    "sign", "sin", "sqrt", "square", "sub", "tan", "tanh", "xor"})
_CALLS = frozenset({"jit", "pjit", "closed_call", "core_call",
                    "custom_jvp_call", "custom_vjp_call", "remat",
                    "checkpoint"})


def _not_elementwise(jaxpr) -> Optional[str]:
    """The first primitive of ``jaxpr`` that does more to a non-scalar
    operand than act on each element alone, or None."""
    def small(v):
        return math.prod(getattr(v.aval, "shape", ())) == 1

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if all(small(v) for v in eqn.invars):
            # scalars from scalars (a step count, a schedule), or a
            # scalar broadcast to a leaf's shape: every worker computes
            # the same
            if (all(small(v) for v in eqn.outvars)
                    or name == "broadcast_in_dim"):
                continue
            return name
        if name in _ELEMENTWISE:
            continue
        if name not in _CALLS:
            return name
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                found = _not_elementwise(sub)
                if found is not None:
                    return found
    return None


def trace_share_update(tx: optax.GradientTransformation, grads, state,
                       params):
    """``tx.update`` traced ONCE on a worker's dim-0 share of every leaf
    (the arguments: abstract, on the shares' shapes): ``(update, None)``
    — ``update(grads, state, params)`` binds that traced program, so
    the program that was checked is the program that runs and the
    caller's optimizer is traced once, as on the replicated path — or
    ``(None, why)`` when it only holds on whole leaves.  Updating a
    share with its share of the state is the same mathematics as
    updating the leaf only if every non-scalar operand is treated
    element by element: the jaxpr is walked against ``_ELEMENTWISE``
    (``clip_by_global_norm``, LARS / LAMB trust ratios and Adafactor
    reduce over a leaf, and a share's norm is not the leaf's).  An
    update that cannot be traced on the shares at all (a captured
    per-parameter constant of the whole shape, a mesh axis it names
    itself) is refused likewise."""
    try:
        jaxpr, out = jax.make_jaxpr(tx.update, return_shape=True)(
            grads, state, params)
    except (TypeError, ValueError, NameError) as e:
        return None, f"not traceable on shares: {e!r}"
    found = _not_elementwise(jaxpr.jaxpr)
    if found is not None:
        return None, f"`{found}` over a leaf"
    run = jax.extend.core.jaxpr_as_fun(jaxpr)
    out_tree = jax.tree_util.tree_structure(out)

    def update(grads, state, params):
        return out_tree.unflatten(
            run(*jax.tree_util.tree_leaves((grads, state, params))))

    return update, None


def sgd_momentum_update(m, g, lr: float, momentum: float):
    """One heavy-ball SGD step on host numpy: ``m' = momentum*m + g``,
    ``delta = -lr*m'`` (the parameter increment).  Returns ``(m', delta)``.

    This is the SINGLE update rule both the replicated baseline and the
    ZeRO-sharded path (training/zero.py) call: it is elementwise, so the
    owner of a parameter span computing it over just that span produces
    bytes bitwise-identical to a replicated client computing the full
    tensor and slicing — the bit-equality contract tests/test_zero.py
    pins.  Keep it numpy (not jnp): the eager PS data path is host-side,
    and both legs must share one arithmetic, not two lowerings of it."""
    m = momentum * m + g
    return m, (-lr) * m


def scoped_update(tx: optax.GradientTransformation
                  ) -> optax.GradientTransformation:
    """``tx`` with its ``update`` traced under ``bps.optimizer`` — the
    same state, the same program; the device ops of the update carry
    the scope in their ``op_name`` (common/tracing.py)."""
    tx = optax.with_extra_args_support(tx)

    def update_fn(updates, state, params=None, **extra_args):
        with jax.named_scope(SCOPE_OPTIMIZER):
            return tx.update(updates, state, params, **extra_args)

    return optax.GradientTransformationExtraArgs(tx.init, update_fn)


def push_pull_gradients(
    axis_name: Union[str, Sequence[str], None] = "dp",
    average: bool = True,
    compression: type = Compression.none,
    partition_bytes: Optional[int] = None,
    plan: Optional[BucketPlan] = None,
    local_axis: Optional[str] = None,
) -> optax.GradientTransformation:
    """An optax transformation that allreduces incoming gradients across the
    data axes via the bucketed reduce-scatter/all-gather path.

    Must run inside shard_map over a mesh containing ``axis_name`` (the
    innermost/ICI axis is the last element when a sequence is given; leading
    axes — e.g. ``"dcn"`` — are summed hierarchically on the scattered
    shard, reference SURVEY.md §2.4 3-level reduction).  ``local_axis``
    pins which axis hosts the local reduce-scatter stage explicitly
    (validated against the axes — see :func:`resolve_local_axis`).
    ``axis_name=None`` means single-worker: pass-through (the reference
    likewise short-circuits when size()==1).

    ``compression`` accepts cast specs only (class or ``"bf16"``/
    ``"fp16"``); a biased registry scheme needs error-feedback state,
    which this stateless transformation cannot hold — use
    ``DistributedOptimizer(compression="onebit")`` or chain
    ``compression.error_feedback_compress`` in front.
    """
    if isinstance(compression, str):
        cast, ef = resolve_compression(compression)
        if ef is not None:
            raise ValueError(
                f"compression={compression!r} is a biased scheme and needs "
                "error-feedback state; use DistributedOptimizer or chain "
                "byteps_tpu.compression.error_feedback_compress before "
                "push_pull_gradients")
        compression = cast
    pb = partition_bytes or get_config().effective_partition_bytes
    # compression class wins; else env BYTEPS_WIRE_DTYPE ("bf16"/"fp16")
    wire = resolve_wire_dtype(compression)

    def init_fn(params):
        del params
        return PushPullState()

    def update_fn(updates, state, params=None):
        del params
        if axis_name is None:
            return updates, state
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        scatter, sums = resolve_local_axis(axes, local_axis)
        # single-worker short-circuit (reference does the same when
        # size()==1): with |axes|==1 the collectives are no-ops but the
        # bucket gather/scatter copies are not — skip them entirely.
        world = 1
        for ax in axes:
            world *= jax.lax.psum(1, ax)
        if world == 1:
            return updates, state
        reduced = push_pull_tree(
            updates,
            plan=plan,
            scatter_axis=scatter,
            sum_axes=sums,
            average=average,
            wire_dtype=wire,
            partition_bytes=pb,
        )
        return reduced, state

    return optax.GradientTransformation(init_fn, update_fn)


def distributed_links(optimizer, compression, axis_name, average,
                      partition_bytes, plan, local_axis) -> list:
    """The links ``DistributedOptimizer`` chains, in order: an
    error-feedback compressor where the scheme is biased, the bucketed
    push_pull, the caller's optimizer under its scope.  The chain's state
    is the tuple of theirs; ``make_data_parallel_step`` walks the same
    links by hand where it updates a share (training/step.py)."""
    cast, ef_tx = resolve_compression(compression)
    # validate eagerly: a bad local_axis must fail at build time, not
    # from inside the traced update
    if axis_name is not None:
        axes = ((axis_name,) if isinstance(axis_name, str)
                else tuple(axis_name))
        resolve_local_axis(axes, local_axis)
    links = [] if ef_tx is None else [ef_tx]
    links.append(
        push_pull_gradients(
            axis_name=axis_name,
            average=average,
            compression=cast,
            partition_bytes=partition_bytes,
            plan=plan,
            local_axis=local_axis,
        ))
    links.append(scoped_update(optimizer))
    return links


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    named_parameters: Any = None,  # accepted for API parity; unused in JAX
    compression: Any = Compression.none,  # Compressor class or scheme name
    backward_passes_per_step: int = 1,
    axis_name: Union[str, Sequence[str], None] = "dp",
    average: bool = True,
    partition_bytes: Optional[int] = None,
    plan: Optional[BucketPlan] = None,
    local_axis: Optional[str] = None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so its gradients are push_pulled across
    workers first (reference torch/__init__.py:383-402 factory).

    ``compression`` takes a Compressor class or a registry scheme name
    (docs/compression.md): ``"bf16"``/``"fp16"`` cast the collective
    payload, while ``"onebit"``/``"topk"``/``"randomk"``/``"int8"``
    chain an error-feedback compressor in front of the allreduce (one
    extra chain level in the opt_state, holding the fp32 residual
    pytree).

    ``local_axis`` names the mesh axis hosting the local (ICI)
    reduce-scatter stage of the hierarchical reduction — the
    ``NcclManager`` group of the reference (docs/wire.md "Hierarchical
    reduction").  Default: the innermost of ``axis_name``; an axis not
    in ``axis_name`` raises at build time.

    Usage inside a shard_mapped train step::

        opt = bps.DistributedOptimizer(optax.sgd(0.1), axis_name="dp",
                                       compression="onebit")
        updates, opt_state = opt.update(grads, opt_state, params)
    """
    del named_parameters
    links = distributed_links(optimizer, compression, axis_name, average,
                              partition_bytes, plan, local_axis)
    tx = optax.chain(*links)
    if backward_passes_per_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)
    return tx
