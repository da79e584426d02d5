"""push_pull / broadcast collectives — the TPU-native communication core.

This replaces the reference's entire data path (SURVEY.md §1 control flow:
NCCL reduce-scatter -> D2H -> cross-PCIe CPU reduce -> ps-lite push -> server
sum -> pull -> H2D -> NCCL allgather, core_loops.cc) with XLA collectives on
a device mesh:

  * intra-slice (ICI) reduce-scatter  == the NCCL ReduceScatter stage
    (core_loops.cc:170-191);
  * cross-slice (DCN axis) psum on the scattered shard == the push/server-
    sum/pull stages (core_loops.cc:430-502) — each device only moves its
    1/|dp| shard across DCN, exactly the bandwidth optimality argument of
    BytePS's hierarchical design (docs/rationale.md);
  * intra-slice all-gather == the NCCL AllGather/broadcast return stage
    (core_loops.cc:192-206).

No D2H/H2D copies (buffers live in HBM), no unix-socket coordination (SPMD
programs are self-synchronizing), no CPU reducer (the scattered-shard psum
rides DCN directly).  Priority scheduling survives as the *issue order* of
per-bucket collectives inside the traced program (BucketPlan.schedule_order).

All ``*_shard`` functions must be called inside ``shard_map`` (they use named
axes); the ``push_pull_tree`` entry point is the one the training step uses.
Eager, handle-based wrappers live in byteps_tpu.engine.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with this repo's positional convention and
    replication checking off by default (the bucketed push_pull returns
    values it knows are replicated but the checker cannot prove)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma)


from ..common import partition as partition_mod
from ..common.partition import BucketPlan
from ..common.tracing import bucket_scope


def _axis_size(axes) -> int:
    """Static size of named axis/axes inside shard_map."""
    return lax.psum(1, axes)


def _pad_to(x: jax.Array, multiple: int) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    rem = (-n) % multiple
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem,), x.dtype)])
    return x, n


def push_pull_shard(
    x: jax.Array,
    scatter_axis: Optional[str] = "dp",
    sum_axes: Sequence[str] = (),
    average: bool = False,
    wire_dtype=None,
) -> jax.Array:
    """Allreduce one flat (1-D) buffer across mesh axes.  Call inside
    shard_map where ``x`` is replicated over the reduce axes.

    Hierarchy: reduce-scatter over ``scatter_axis`` (ICI), psum the shard
    over ``sum_axes`` (DCN), all-gather back over ``scatter_axis`` — the
    reference's 3-level reduction (SURVEY.md §2.4) in three XLA ops.

    ``wire_dtype`` casts the payload before communication (the fp16/bf16
    compression hook of reference torch/compression.py:21-75; bf16 is the
    natural TPU wire format).
    """
    orig_dtype = x.dtype
    if x.ndim != 1:
        x = x.reshape(-1)
    if wire_dtype is not None and x.dtype != wire_dtype:
        x = x.astype(wire_dtype)

    denom = 1
    if average:
        axes = (tuple(sum_axes) + ((scatter_axis,) if scatter_axis else ()))
        denom = _axis_size(axes) if axes else 1

    if scatter_axis is not None:
        nshards = _axis_size(scatter_axis)
        x, n = _pad_to(x, nshards)
        y = lax.psum_scatter(x, scatter_axis, scatter_dimension=0, tiled=True)
        if sum_axes:
            y = lax.psum(y, tuple(sum_axes))
        y = lax.all_gather(y, scatter_axis, axis=0, tiled=True)
        y = y[:n]
    else:
        y = lax.psum(x, tuple(sum_axes)) if sum_axes else x

    if average:
        y = y / denom
    return y.astype(orig_dtype)


def sparse_push_pull(
    indices: jax.Array,
    values: jax.Array,
    num_rows: int,
    axes: Sequence[str] = ("dp",),
    average: bool = False,
    wire_dtype=None,
) -> jax.Array:
    """Row-sparse allreduce — the operation the reference *reserves* as
    ``kRowSparsePushPull`` (common.h:212-216) and lists as future work
    (README.md:106-110) but never implements.

    Call inside shard_map.  Each worker contributes gradients for ``k``
    embedding rows: ``indices [k]`` (int row ids, duplicates allowed) and
    ``values [k, d]``; every worker receives the dense ``[num_rows, d]``
    sum (or mean over workers) of all contributions.

    Wire traffic is ``world * k * d`` (all_gather of the nonzero rows)
    instead of the dense allreduce's ``~2 * num_rows * d / world`` per
    link — the sparse win whenever ``k << num_rows / world²``-ish, i.e.
    the classic embedding-gradient regime the PS architecture was built
    for.  The scatter-add runs on-device per worker; XLA lowers it to an
    efficient sorted segment-sum.
    """
    axes = tuple(axes)
    if values.ndim != 2:
        raise ValueError(f"values must be [k, d]; got {values.shape}")
    if indices.shape[0] != values.shape[0]:
        raise ValueError("indices and values disagree on k")
    orig_dtype = values.dtype
    if wire_dtype is not None and values.dtype != wire_dtype:
        values = values.astype(wire_dtype)

    # gather every worker's (indices, values) — the only communication
    all_idx = indices
    all_val = values
    for ax in reversed(axes):
        all_idx = lax.all_gather(all_idx, ax, axis=0, tiled=True)
        all_val = lax.all_gather(all_val, ax, axis=0, tiled=True)

    dense = jnp.zeros((num_rows, values.shape[1]), all_val.dtype)
    dense = dense.at[all_idx].add(all_val, mode="drop")
    if average:
        dense = dense / _axis_size(axes)
    return dense.astype(orig_dtype)


def broadcast_shard(
    x: jax.Array,
    root_rank: int = 0,
    axes: Sequence[str] = ("dp",),
) -> jax.Array:
    """Broadcast ``root_rank``'s value to all members of ``axes``.

    Uses the reference's own trick — zero on non-root, then sum
    (tensorflow/ops.py:117,130-139) — which XLA lowers to an efficient
    collective without a dedicated broadcast primitive.
    """
    axes = tuple(axes)
    # linearized rank over the broadcast axes
    idx = 0
    for ax in axes:
        idx = idx * _axis_size(ax) + lax.axis_index(ax)
    mask = (idx == root_rank).astype(x.dtype)
    return lax.psum(x * mask, axes)


def push_pull_tree(
    grads,
    plan: Optional[BucketPlan] = None,
    scatter_axis: Optional[str] = "dp",
    sum_axes: Sequence[str] = (),
    average: bool = True,
    wire_dtype=None,
    partition_bytes: int = 4_096_000,
):
    """Bucketed allreduce of a gradient pytree.  Call inside shard_map.

    The pytree is packed into <=partition_bytes buckets (reference
    PartitionTensor semantics + TPU fusion, common/partition.py) and one
    collective is issued per bucket in priority order
    (BucketPlan.schedule_order == scheduled_queue.cc ordering).

    A bucket packed from several leaves stands between each leaf's
    gradient and its collective: on the v5e XLA ran this whole chain
    after the backward pass, 37.6 ms exposed a step = all of the
    collective time (PERF_LEDGER.jsonl, PR 22, ``gpt2m_train_dp4``).  A
    collective that is a leaf's own is hidden inside the backward pass
    (12.7 ms exposed, ledger PR 28): that is ``reduce_scatter_tree`` /
    ``all_gather_tree`` below, which the data-parallel step uses.  This
    flat bucketed form remains for the replicated path and the eager API
    and has no benchmark cell at world > 1.
    """
    if plan is None:
        plan = partition_mod.plan_buckets(grads, partition_bytes)
    buckets = partition_mod.gather_buckets(grads, plan)
    reduced: List[Optional[jax.Array]] = [None] * len(buckets)
    for i in plan.schedule_order():
        with jax.named_scope(bucket_scope("reduce", i)):
            reduced[i] = push_pull_shard(
                buckets[i],
                scatter_axis=scatter_axis,
                sum_axes=sum_axes,
                average=average,
                wire_dtype=wire_dtype,
            )
    return partition_mod.scatter_buckets(reduced, plan)


def reduce_scatter_tree(
    leaves: Sequence[jax.Array],
    plan: BucketPlan,
    scatter_axis: str = "dp",
    sum_axes: Sequence[str] = (),
    average: bool = True,
) -> List[jax.Array]:
    """The first half of ``push_pull_tree`` for leaves whose dim 0
    divides by the scatter axis: one ``psum_scatter`` per bucket of
    ``plan`` (``partition.plan_share_buckets``), in priority order, so
    what this worker is left with IS its contiguous 1/shards of every
    leaf's reduced value along dim 0 — returned as the list of those
    shares.  ``all_gather_tree`` is the other half; what runs between
    them (the optimizer, on a share) runs on 1/shards of the bytes.
    Call inside shard_map."""
    shards = _axis_size(scatter_axis)
    denom = _axis_size(tuple(sum_axes) + (scatter_axis,)) if average else 1
    payloads = [partition_mod.pack_share_bucket(leaves, b, shards)
                for b in plan.buckets]
    shares: List[Optional[jax.Array]] = [None] * len(leaves)
    rows: List[Optional[jax.Array]] = [None] * len(payloads)
    for i in plan.schedule_order():
        with jax.named_scope(
                bucket_scope("reduce", plan.buckets[i].bucket_id)):
            y = lax.psum_scatter(payloads[i], scatter_axis,
                                 scatter_dimension=0, tiled=True)
            if sum_axes:
                y = lax.psum(y, tuple(sum_axes))
            rows[i] = y / denom if average else y
    for b, row in zip(plan.buckets, rows):
        for s, x in zip(b.slices,
                        partition_mod.unpack_share_bucket(row, b, plan, 1)):
            shares[s.leaf_index] = x
    return shares


def all_gather_tree(
    shares: Sequence[jax.Array],
    plan: BucketPlan,
    scatter_axis: str = "dp",
) -> List[jax.Array]:
    """The whole leaves from every worker's dim-0 shares: one
    ``all_gather`` per bucket of the same ``plan``, in priority order
    and under the bucket's ``reduce`` scope.  The gather of a dim-0 share
    along dim 0 IS the leaf, so a leaf alone in its bucket is neither
    packed nor unpacked.  Call inside shard_map."""
    shards = _axis_size(scatter_axis)
    rows = [partition_mod.pack_share_bucket(shares, b, 1)
            for b in plan.buckets]
    whole: List[Optional[jax.Array]] = [None] * len(shares)
    payloads: List[Optional[jax.Array]] = [None] * len(rows)
    for i in plan.schedule_order():
        with jax.named_scope(
                bucket_scope("reduce", plan.buckets[i].bucket_id)):
            payloads[i] = lax.all_gather(rows[i], scatter_axis, axis=0,
                                         tiled=True)
    for b, payload in zip(plan.buckets, payloads):
        for s, x in zip(b.slices, partition_mod.unpack_share_bucket(
                payload, b, plan, shards)):
            whole[s.leaf_index] = x
    return whole


# ---------------------------------------------------------------------------
# Eager (outside-jit) entry points: one controller, workers == mesh devices.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _stacked_push_pull_fn(mesh: Mesh, axes: Tuple[str, ...], average: bool, wire: Optional[str]):
    wire_dtype = jnp.dtype(wire) if wire else None
    inner = axes[-1]
    outer = axes[:-1]

    def f(x):  # x: local slice [1, ...] of the stacked input
        flat = x.reshape(-1)
        y = push_pull_shard(
            flat, scatter_axis=inner, sum_axes=outer,
            average=average, wire_dtype=wire_dtype,
        )
        return y.reshape(x.shape[1:])

    return jax.jit(
        shard_map(f, mesh, in_specs=P(axes), out_specs=P())
    )


def push_pull_stacked(
    x_stacked: jax.Array, mesh: Mesh, axes: Sequence[str], average: bool = False,
    wire_dtype: Optional[str] = None,
) -> jax.Array:
    """Eager allreduce: ``x_stacked[w]`` is worker w's contribution
    (w enumerates the mesh's reduce axes, row-major); returns the
    sum/average, replicated.  This is the single-controller rendering of the
    reference's per-rank push_pull (SURVEY.md §4 test contract: result ==
    sum over ranks)."""
    n = int(np.prod([mesh.shape[a] for a in axes]))
    if x_stacked.shape[0] != n:
        raise ValueError(
            f"stacked push_pull expects leading axis == world size {n}, "
            f"got shape {x_stacked.shape}"
        )
    fn = _stacked_push_pull_fn(mesh, tuple(axes), average, wire_dtype)
    return fn(x_stacked)


@functools.lru_cache(maxsize=None)
def _stacked_broadcast_fn(mesh: Mesh, axes: Tuple[str, ...], root_rank: int):
    def f(x):
        return broadcast_shard(x.reshape(x.shape[1:]) if x.shape[0] == 1 else x[0],
                               root_rank=root_rank, axes=axes)

    return jax.jit(shard_map(f, mesh, in_specs=P(axes), out_specs=P()))


def broadcast_stacked(
    x_stacked: jax.Array, mesh: Mesh, axes: Sequence[str], root_rank: int = 0
) -> jax.Array:
    """Eager broadcast over stacked per-worker values: every worker receives
    worker ``root_rank``'s slice (reference broadcast contract,
    tests/test_mxnet.py:116-158)."""
    fn = _stacked_broadcast_fn(mesh, tuple(axes), root_rank)
    return fn(x_stacked)


def replicate(x, mesh: Mesh):
    """Place a host value on the mesh fully replicated."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(x, sharding)


# ---------------------------------------------------------------------------
# Eager local-mesh scatter/gather: the in-graph half of the hierarchical
# PS data path (engine/hierarchical.py; docs/wire.md "Hierarchical
# reduction").  ``local_reduce_scatter`` is the NcclManager reduce-scatter
# stage of the reference (core_loops.cc:170-191) — run BEFORE an eager PS
# push so each colocated worker ships only its 1/local_size slice —
# and ``local_all_gather`` is the AllGather/broadcast return stage
# (core_loops.cc:192-206) rebuilding the full tensor from pulled slices.
# One traced program per (mesh, axis, padded-length) shape bucket.
# ---------------------------------------------------------------------------


def _axes_tuple(axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


@functools.lru_cache(maxsize=None)
def _local_scatter_fn(mesh: Mesh, axes: Tuple[str, ...], npad: int,
                      dtype: str):
    del npad, dtype  # cache keys only: one traced program per shape bucket

    def f(x):  # x: [1, npad] — this member's row of the stacked input
        return lax.psum_scatter(
            x.reshape(-1), axes, scatter_dimension=0, tiled=True)

    # out_specs P(axes): member r of the (flattened) axes holds chunk r
    # of the reduced buffer — exactly the slice it pushes to the PS tier
    return jax.jit(shard_map(f, mesh, in_specs=P(axes), out_specs=P(axes)))


def local_reduce_scatter(stacked, mesh: Mesh, axis) -> jax.Array:
    """Reduce ``stacked[w]`` contributions over the local mesh ``axis``
    (a name or tuple of names — flattened row-major) and scatter the
    sum: returns a flat ``[npad]`` array (npad = input row length, padded
    by the caller to a multiple of the axis size) whose chunk ``r`` — as
    laid out by ``hierarchical.slice_spans`` — lives on axis member
    ``r``.  Call with ``stacked`` shaped ``[axis_size, npad]``."""
    axes = _axes_tuple(axis)
    n = _axes_size(mesh, axes)
    if stacked.ndim != 2 or stacked.shape[0] != n:
        raise ValueError(
            f"local_reduce_scatter expects [axis_size={n}, npad]; got "
            f"{stacked.shape}")
    if stacked.shape[1] % n:
        raise ValueError(
            f"row length {stacked.shape[1]} is not a multiple of the "
            f"local axis size {n} — pad first (engine/hierarchical.py "
            "owns the span math)")
    fn = _local_scatter_fn(mesh, axes, stacked.shape[1],
                           str(stacked.dtype))
    return fn(jnp.asarray(stacked))


def reduce_scatter_spans(stacked, mesh: Mesh, axis) -> List[np.ndarray]:
    """Sum per-worker rows on-mesh and hand back the per-rank OWNED
    spans: ``[rank r's span of sum(stacked, axis=0)]`` with the same
    ceil-chunk span layout as ``zero_spans``/``hierarchical.slice_spans``
    (span r = ``flat[r*ceil(n/world):(r+1)*ceil(n/world)]``, last span
    clipped).  Unlike :func:`local_reduce_scatter` this pads internally,
    so any row length works.

    This is the gradient-reduction front half of a ZeRO step
    (training/zero.py): after it, rank r holds exactly the summed
    gradient for the parameter span whose optimizer state it owns — at
    1/world of the allreduce's gather traffic, since no rank ever needs
    the other spans' gradients."""
    axes = _axes_tuple(axis)
    world = _axes_size(mesh, axes)
    stacked = np.asarray(stacked)
    if stacked.ndim != 2 or stacked.shape[0] != world:
        raise ValueError(
            f"reduce_scatter_spans expects [axis_size={world}, n]; got "
            f"{stacked.shape}")
    n = stacked.shape[1]
    chunk = -(-n // world) if n else 0
    pad = chunk * world - n
    if pad:
        stacked = np.concatenate(
            [stacked, np.zeros((world, pad), stacked.dtype)], axis=1)
    flat = np.asarray(local_reduce_scatter(stacked, mesh, axes))
    return [flat[r * chunk:min((r + 1) * chunk, n)] for r in range(world)]


@functools.lru_cache(maxsize=None)
def _local_gather_fn(mesh: Mesh, axes: Tuple[str, ...], npad: int,
                     dtype: str):
    del npad, dtype

    def f(x):  # x: [npad / axis_size] — this member's pulled slice
        return lax.all_gather(x, axes, axis=0, tiled=True)

    return jax.jit(shard_map(f, mesh, in_specs=P(axes), out_specs=P()))


def local_all_gather(flat_sharded, mesh: Mesh, axis) -> jax.Array:
    """Rebuild the full flat buffer from per-member slices: input is a
    flat ``[npad]`` value laid out (or shardable) as ``P(axis)`` — chunk
    ``r`` is member ``r``'s pulled slice — and the result is the full
    ``[npad]`` buffer replicated over the mesh."""
    axes = _axes_tuple(axis)
    n = _axes_size(mesh, axes)
    flat_sharded = jnp.asarray(flat_sharded)
    if flat_sharded.ndim != 1 or flat_sharded.shape[0] % n:
        raise ValueError(
            f"local_all_gather expects a flat buffer divisible by the "
            f"axis size {n}; got {flat_sharded.shape}")
    sharded = jax.device_put(flat_sharded, NamedSharding(mesh, P(axes)))
    fn = _local_gather_fn(mesh, axes, flat_sharded.shape[0],
                          str(flat_sharded.dtype))
    return fn(sharded)
