"""Tensor partitioning and gradient bucketization.

Counterpart of reference ``PartitionTensor`` (operations.cc:95-132): every
declared tensor is split into ``ceil(nbytes / BYTEPS_PARTITION_BYTES)``
partitions named ``name_i``, each with its own PS key, so partitions pipeline
independently through the communication stages.

TPU-native generalization: besides splitting *large* tensors, we also *fuse
small* tensors into fixed-size buckets (the way Horovod's fusion buffer and
modern DDP bucketing do).  On TPU the cost model demands it — each
reduce-scatter/all-gather pair has a fixed ICI latency, so thousands of tiny
collectives would be latency-bound, while a handful of multi-MB buckets ride
the ICI at full bandwidth.  The bucket plan is computed once per parameter
pytree at trace time (static shapes — XLA requirement) and drives both the
jitted push_pull (bucket order == collective issue order == priority order)
and the eager engine (one scheduler task per bucket, reference
scheduled_queue.cc semantics).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .tracing import SCOPE_UNPACK, bucket_scope


def partition_offsets(nbytes: int, bound: int) -> List[Tuple[int, int]]:
    """Split ``nbytes`` into (offset, length) parts each <= bound.

    Mirrors reference operations.cc:95-132 (the accumulated-size loop).
    """
    if nbytes <= 0:
        return [(0, 0)] if nbytes == 0 else []
    if bound <= 0:
        raise ValueError("partition bound must be positive")
    parts = []
    offset = 0
    while offset < nbytes:
        length = min(bound, nbytes - offset)
        parts.append((offset, length))
        offset += length
    return parts


@dataclass(frozen=True)
class LeafSpec:
    """Static description of one pytree leaf."""

    index: int  # position in the flattened pytree
    name: str
    shape: Tuple[int, ...]
    dtype: Any
    size: int  # elements
    nbytes: int


@dataclass(frozen=True)
class BucketSlice:
    """A contiguous run of one leaf's flat elements placed inside a bucket."""

    leaf_index: int
    leaf_start: int  # element offset within the (flattened) leaf
    bucket_start: int  # element offset within the bucket
    length: int  # elements


@dataclass
class Bucket:
    """One schedulable unit of communication.

    ``priority`` follows the reference convention ``-declared_key``
    (tensorflow/ops.cc:158): lower leaf index (earlier layer, needed first by
    the next forward pass) => higher priority value => scheduled earlier.
    """

    bucket_id: int
    dtype: Any
    size: int  # elements (unpadded)
    priority: int
    slices: List[BucketSlice] = field(default_factory=list)

    @property
    def nbytes(self) -> int:
        return self.size * np.dtype(self.dtype).itemsize if self.dtype != jnp.bfloat16 else self.size * 2


@dataclass
class BucketPlan:
    """Static plan mapping a parameter pytree to communication buckets."""

    leaves: List[LeafSpec]
    buckets: List[Bucket]
    treedef: Any = None

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def schedule_order(self) -> List[int]:
        """Bucket issue order: priority desc, then bucket id asc — the exact
        ordering rule of reference scheduled_queue.cc:78-98."""
        return sorted(
            range(len(self.buckets)),
            key=lambda i: (-self.buckets[i].priority, self.buckets[i].bucket_id),
        )


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return ".".join(parts) if parts else "param"


def leaf_specs_of_tree(tree) -> Tuple[List[LeafSpec], Any]:
    """Extract static leaf descriptions (works on arrays or ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for i, (path, leaf) in enumerate(flat):
        shape = tuple(leaf.shape)
        dtype = leaf.dtype
        size = int(np.prod(shape)) if shape else 1
        itemsize = 2 if dtype == jnp.bfloat16 else np.dtype(dtype).itemsize
        specs.append(
            LeafSpec(
                index=i,
                name=_leaf_name(path),
                shape=shape,
                dtype=dtype,
                size=size,
                nbytes=size * itemsize,
            )
        )
    return specs, treedef


def plan_buckets(
    tree,
    partition_bytes: int = 4_096_000,
    reverse: bool = True,
) -> BucketPlan:
    """Build the static bucket plan for a parameter/gradient pytree.

    * leaves are packed in ``reverse`` flattening order by default, because
      gradients materialize in reverse layer order during backprop — the
      bucket holding the *last* layer's grads fills first and its collective
      can overlap the rest of the backward pass (the scheduling insight of
      reference scheduled_queue.cc + bytescheduler).
    * a leaf larger than ``partition_bytes`` is split across several buckets
      (reference PartitionTensor, operations.cc:95-132);
    * consecutive small leaves of the same dtype share a bucket (TPU fusion).
    * ``priority`` is ``-min(leaf index in bucket)`` so earlier-layer buckets
      are *issued last but scheduled first* on the return path, matching the
      reference's ``-declared_key`` rule (tensorflow/ops.cc:158).
    """
    leaves, treedef = leaf_specs_of_tree(tree)
    order = list(range(len(leaves)))
    if reverse:
        order = order[::-1]

    buckets: List[Bucket] = []
    cur: Bucket | None = None

    def close():
        nonlocal cur
        if cur is not None and cur.size > 0:
            buckets.append(cur)
        cur = None

    for li in order:
        leaf = leaves[li]
        itemsize = 2 if leaf.dtype == jnp.bfloat16 else np.dtype(leaf.dtype).itemsize
        bound_elems = max(1, partition_bytes // itemsize)
        remaining = leaf.size
        leaf_off = 0
        while remaining > 0:
            if cur is not None and (cur.dtype != leaf.dtype or cur.size >= bound_elems):
                close()
            if cur is None:
                cur = Bucket(
                    bucket_id=len(buckets),
                    dtype=leaf.dtype,
                    size=0,
                    priority=0,
                    slices=[],
                )
            room = bound_elems - cur.size
            take = min(room, remaining)
            cur.slices.append(
                BucketSlice(
                    leaf_index=li,
                    leaf_start=leaf_off,
                    bucket_start=cur.size,
                    length=take,
                )
            )
            cur.size += take
            leaf_off += take
            remaining -= take
            if cur.size >= bound_elems:
                close()
    close()

    for b in buckets:
        b.priority = -min(s.leaf_index for s in b.slices)

    return BucketPlan(leaves=leaves, buckets=buckets, treedef=treedef)


def gather_buckets(tree, plan: BucketPlan) -> List[jax.Array]:
    """Materialize bucket payloads (1-D arrays) from a pytree.  Traceable."""
    flat = jax.tree_util.tree_leaves(tree)
    out = []
    for i, b in enumerate(plan.buckets):
        with jax.named_scope(bucket_scope("pack", i)):
            parts = []
            for s in b.slices:
                leaf = flat[s.leaf_index].reshape(-1)
                parts.append(jax.lax.dynamic_slice_in_dim(leaf, s.leaf_start, s.length))
            out.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
    return out


def scatter_buckets(bucket_arrays: Sequence[jax.Array], plan: BucketPlan):
    """Inverse of gather_buckets: rebuild the pytree from bucket payloads."""
    pieces: Dict[int, List[Tuple[int, jax.Array]]] = {}
    flat = []
    with jax.named_scope(SCOPE_UNPACK):
        for b, arr in zip(plan.buckets, bucket_arrays):
            for s in b.slices:
                chunk = jax.lax.dynamic_slice_in_dim(arr, s.bucket_start, s.length)
                pieces.setdefault(s.leaf_index, []).append((s.leaf_start, chunk))
        for leaf in plan.leaves:
            chunks = sorted(pieces[leaf.index], key=lambda t: t[0])
            vec = chunks[0][1] if len(chunks) == 1 else jnp.concatenate([c for _, c in chunks])
            flat.append(vec.reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(plan.treedef, flat)


# ---------------------------------------------------------------------------
# Buckets of dim-0 shares: what lets a reduce-scatter leave every worker the
# contiguous 1/shards of each leaf along dim 0, and an all-gather of such
# shares rebuild the leaves (the sharded update, training/step.py).  A
# share's bytes are not contiguous in a flat bucket of whole leaves, so
# the flat plan above cannot say it; this one never cuts a leaf:
#
# * a leaf whose share fills a bucket is a bucket by itself, whatever its
#   size — the collective runs on the leaf as it lies (``psum_scatter`` /
#   ``all_gather`` over dim 0), nothing is packed and nothing unpacked;
# * consecutive smaller leaves of one dtype share a bucket whose payload is
#   shard-major: flat ``[shards * size]``, its r-th ``size`` elements
#   ("row" r) worker r's shares end to end.
# ---------------------------------------------------------------------------


def dim0_share(leaf, shards: int) -> jax.ShapeDtypeStruct:
    """The shape of one worker's contiguous 1/shards of ``leaf`` along
    dim 0 (the caller has checked that it divides)."""
    return jax.ShapeDtypeStruct(
        (leaf.shape[0] // shards,) + tuple(leaf.shape[1:]), leaf.dtype)


def plan_share_buckets(leaves: Sequence[Any], shards: int,
                       partition_bytes: int = 4_096_000,
                       first_id: int = 0) -> BucketPlan:
    """The bucket plan of a list of leaves whose dim 0 divides by
    ``shards``, in share coordinates (``plan.leaves``, every slice and
    every ``size`` describe ONE worker's share): ``plan_buckets``' order
    (reverse), dtype rule and priorities at ``partition_bytes / shards``
    a worker, except that no leaf is cut: a leaf that fills a bucket
    stands alone (and closes nothing: the small leaves on either side of
    it still meet in one bucket), and a bucket of small leaves closes
    before the leaf that would overflow it.
    ``first_id`` numbers the buckets after another plan's (the leaves
    whose dim 0 does not divide), so no two collectives of a step share
    a ``bps.push_pull/*/b<iii>`` scope."""
    specs, treedef = leaf_specs_of_tree(
        [dim0_share(x, shards) for x in leaves])
    bound = max(1, partition_bytes // shards)
    buckets: List[Bucket] = []
    shared: Bucket | None = None      # the bucket small leaves still join

    def open_bucket(leaf):
        buckets.append(Bucket(bucket_id=first_id + len(buckets),
                              dtype=leaf.dtype, size=0, priority=0,
                              slices=[]))
        return buckets[-1]

    for leaf in reversed(specs):
        if leaf.nbytes >= bound:
            into = open_bucket(leaf)            # alone, and closed
        else:
            if (shared is None or shared.dtype != leaf.dtype
                    or shared.nbytes + leaf.nbytes > bound):
                shared = open_bucket(leaf)
            into = shared
        into.slices.append(BucketSlice(leaf.index, 0, into.size, leaf.size))
        into.size += leaf.size
        into.priority = -leaf.index             # the smallest index so far
    return BucketPlan(leaves=specs, buckets=buckets, treedef=treedef)


def pack_share_bucket(leaves: Sequence[jax.Array], bucket: Bucket,
                      rows: int) -> jax.Array:
    """The flat payload of ``bucket`` from its leaves (indexed as the
    plan's): ``rows`` = shards packs whole leaves shard-major, ``rows``
    = 1 one worker's shares into its row.  A leaf alone in its bucket is
    its own payload, as it lies.  Traceable."""
    if len(bucket.slices) == 1:
        return leaves[bucket.slices[0].leaf_index]
    with jax.named_scope(bucket_scope("pack", bucket.bucket_id)):
        return jnp.concatenate(
            [leaves[s.leaf_index].reshape(rows, s.length)
             for s in bucket.slices], axis=1).reshape(-1)


def unpack_share_bucket(payload: jax.Array, bucket: Bucket,
                        plan: BucketPlan, rows: int) -> List[jax.Array]:
    """Inverse of ``pack_share_bucket``: the bucket's leaves, in the
    order of its slices, dim 0 ``rows`` x the share's."""
    if len(bucket.slices) == 1:
        return [payload]
    with jax.named_scope(SCOPE_UNPACK):
        table = payload.reshape(rows, bucket.size)
        out = []
        for s in bucket.slices:
            shape = plan.leaves[s.leaf_index].shape
            out.append(
                table[:, s.bucket_start:s.bucket_start + s.length].reshape(
                    (rows * shape[0],) + shape[1:]))
        return out
