"""Unit tests for the partitioner/bucketizer (reference operations.cc:95-132
behavioral contract + TPU fusion-bucket extension)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import partition as P


class TestPartitionOffsets:
    def test_exact_multiple(self):
        assert P.partition_offsets(100, 25) == [(0, 25), (25, 25), (50, 25), (75, 25)]

    def test_remainder(self):
        assert P.partition_offsets(10, 4) == [(0, 4), (4, 4), (8, 2)]

    def test_single(self):
        assert P.partition_offsets(3, 100) == [(0, 3)]

    def test_zero(self):
        assert P.partition_offsets(0, 4) == [(0, 0)]

    def test_bad_bound(self):
        with pytest.raises(ValueError):
            P.partition_offsets(10, 0)


def _tree():
    return {
        "layer0": {"w": jnp.zeros((8, 16), jnp.float32), "b": jnp.zeros((16,), jnp.float32)},
        "layer1": {"w": jnp.zeros((16, 4), jnp.float32)},
    }


class TestBucketPlan:
    def test_all_elements_covered_once(self):
        plan = P.plan_buckets(_tree(), partition_bytes=200)
        covered = {}
        for b in plan.buckets:
            for s in b.slices:
                for e in range(s.leaf_start, s.leaf_start + s.length):
                    key = (s.leaf_index, e)
                    assert key not in covered, "element covered twice"
                    covered[key] = True
        total = sum(l.size for l in plan.leaves)
        assert len(covered) == total

    def test_bucket_size_bound(self):
        plan = P.plan_buckets(_tree(), partition_bytes=100)
        bound_elems = 100 // 4
        for b in plan.buckets:
            assert b.size <= bound_elems

    def test_large_leaf_split(self):
        tree = {"big": jnp.zeros((1000,), jnp.float32)}
        plan = P.plan_buckets(tree, partition_bytes=1024)  # 256 elems/bucket
        assert plan.num_buckets == 4
        assert [b.size for b in plan.buckets] == [256, 256, 256, 232]

    def test_small_leaves_fused(self):
        tree = {f"p{i}": jnp.zeros((10,), jnp.float32) for i in range(10)}
        plan = P.plan_buckets(tree, partition_bytes=4_096_000)
        assert plan.num_buckets == 1
        assert plan.buckets[0].size == 100

    def test_priority_rule(self):
        # priority = -min(leaf_index): earlier-declared params get higher
        # priority (reference tensorflow/ops.cc:158).
        plan = P.plan_buckets(_tree(), partition_bytes=64 * 4)
        prios = {}
        for b in plan.buckets:
            prios[b.bucket_id] = b.priority
        order = plan.schedule_order()
        sorted_prios = [plan.buckets[i].priority for i in order]
        assert sorted_prios == sorted(sorted_prios, reverse=True)

    def test_roundtrip(self):
        tree = {
            "a": jnp.arange(37, dtype=jnp.float32).reshape(37),
            "b": jnp.arange(24, dtype=jnp.float32).reshape(4, 6) * 2,
            "c": jnp.arange(5, dtype=jnp.float32) - 3,
        }
        plan = P.plan_buckets(tree, partition_bytes=64)
        arrs = P.gather_buckets(tree, plan)
        out = P.scatter_buckets(arrs, plan)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(out[k]))

    def test_roundtrip_under_jit(self):
        tree = {"w": jnp.arange(100, dtype=jnp.float32), "b": jnp.ones((7,), jnp.float32)}
        plan = P.plan_buckets(tree, partition_bytes=128)

        @jax.jit
        def f(t):
            return P.scatter_buckets([a * 2 for a in P.gather_buckets(t, plan)], plan)

        out = f(tree)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(100) * 2.0)

    def test_mixed_dtypes_not_fused(self):
        tree = {"f": jnp.zeros((10,), jnp.float32), "i": jnp.zeros((10,), jnp.int32),
                "h": jnp.zeros((10,), jnp.bfloat16)}
        plan = P.plan_buckets(tree, partition_bytes=4_096_000)
        for b in plan.buckets:
            dts = {plan.leaves[s.leaf_index].dtype for s in b.slices}
            assert len(dts) == 1

    def test_reverse_packing_order(self):
        # last leaf should land in the first bucket (backward-pass overlap).
        tree = {"a": jnp.zeros((10,)), "z": jnp.zeros((10,))}
        plan = P.plan_buckets(tree, partition_bytes=10 * 4)
        first_bucket_leaves = {s.leaf_index for s in plan.buckets[0].slices}
        assert first_bucket_leaves == {len(plan.leaves) - 1}


def _share_tree():
    """A leaf spanning several buckets, two leaves sharing one, and (kept
    apart by the caller) a leaf whose dim 0 does not divide by 4."""
    return {
        "big": jnp.arange(8 * 40, dtype=jnp.float32).reshape(8, 40),
        "s0": jnp.arange(12, dtype=jnp.float32) + 1000,
        "s1": jnp.arange(4 * 3, dtype=jnp.float32).reshape(4, 3) + 2000,
    }


class TestShareBuckets:
    """The buckets of dim-0 shares of the sharded update (a leaf that
    fills a bucket stands alone and is its own payload; a shared bucket's
    row r is worker r's shares), on the host and through the
    collectives."""

    @staticmethod
    def _round_trip(leaves, plan, rows):
        out = [None] * len(leaves)
        for b in plan.buckets:
            payload = P.pack_share_bucket(leaves, b, rows)
            for s, x in zip(b.slices,
                            P.unpack_share_bucket(payload, b, plan, rows)):
                out[s.leaf_index] = x
        return out

    @pytest.mark.parametrize("shards,partition_bytes", [
        (4, 256), (4, 64), (2, 256), (8, 4096), (4, 4)])
    def test_pack_unpack_is_the_identity(self, shards, partition_bytes):
        tree = _share_tree()
        tree["big"] = jnp.arange(16 * 20, dtype=jnp.float32).reshape(16, 20)
        tree["s0"] = jnp.arange(16, dtype=jnp.float32) + 1000
        tree["s1"] = jnp.arange(24, dtype=jnp.float32).reshape(8, 3) + 2000
        leaves = jax.tree_util.tree_leaves(tree)
        plan = P.plan_share_buckets(leaves, shards, partition_bytes)
        for b in plan.buckets:
            payload = P.pack_share_bucket(leaves, b, shards)
            alone = len(b.slices) == 1
            assert payload.shape == (
                leaves[b.slices[0].leaf_index].shape if alone
                else (shards * b.size,))
        for a, b in zip(leaves, self._round_trip(leaves, plan, shards)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("partition_bytes", [64, 256, 4096])
    def test_row_r_is_worker_rs_payload(self, partition_bytes):
        """Row r of a bucket's payload is what the same bucket packs
        from worker r's dim-0 shares — which is why a reduce-scatter
        leaves exactly those shares, and an all-gather of the rows is
        the payload again."""
        shards = 4
        leaves = jax.tree_util.tree_leaves(_share_tree())
        plan = P.plan_share_buckets(leaves, shards, partition_bytes)
        for r in range(shards):
            shares = [x[r * (x.shape[0] // shards):
                        (r + 1) * (x.shape[0] // shards)] for x in leaves]
            for b in plan.buckets:
                payload = np.asarray(
                    P.pack_share_bucket(leaves, b, shards)).reshape(-1)
                row = np.asarray(
                    P.pack_share_bucket(shares, b, 1)).reshape(-1)
                assert row.shape == (b.size,)
                np.testing.assert_array_equal(
                    row, payload[r * b.size:(r + 1) * b.size])
            for a, b in zip(shares, self._round_trip(shares, plan, 1)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_the_share_plan_never_cuts_a_leaf(self):
        """``plan_buckets``' order, dtype rule and priorities in share
        coordinates, but a leaf that fills a bucket stands alone (the
        flat plan cuts it into several), the smaller ones share buckets
        up to ``partition_bytes / shards`` a worker, and the ids follow
        ``first_id``."""
        leaves = [jnp.zeros((8, 40)), jnp.zeros((16,)), jnp.zeros((4, 3)),
                  jnp.zeros((8,), jnp.int32), jnp.zeros((4,))]
        flat = P.plan_buckets(leaves, 256)
        share = P.plan_share_buckets(leaves, 4, 256, first_id=7)
        assert [[s.leaf_index for s in b.slices] for b in share.buckets] == [
            [4], [3], [2, 1], [0]]
        assert sum(1 for b in flat.buckets
                   if {s.leaf_index for s in b.slices} == {0}) > 1
        assert [b.bucket_id for b in share.buckets] == [7, 8, 9, 10]
        assert [b.priority for b in share.buckets] == [-4, -3, -1, 0]
        assert share.schedule_order() == [3, 2, 1, 0]
        assert [l.shape for l in share.leaves] == [
            (2, 40), (4,), (1, 3), (2,), (1,)]
        for b in share.buckets:
            assert b.size == sum(s.length for s in b.slices)
            assert all(s.leaf_start == 0
                       and s.length == share.leaves[s.leaf_index].size
                       for s in b.slices)
            assert len(b.slices) == 1 or b.nbytes <= 256 // 4

    @pytest.mark.parametrize("partition_bytes", [64, 256, 1 << 20])
    def test_scatter_then_gather_is_the_mean_over_workers(
            self, partition_bytes):
        """pack -> psum_scatter -> (this worker's dim-0 shares) ->
        all_gather -> unpack on the CPU mesh: every worker ends with the
        mean of the workers' trees, and in between holds rows
        ``[r * n/4, (r + 1) * n/4)`` of each leaf's mean."""
        from jax.sharding import Mesh, PartitionSpec

        from byteps_tpu.parallel import collectives as C

        shards = 4
        mesh = Mesh(np.array(jax.devices()[:shards]), ("dp",))
        tree = _share_tree()
        leaves = jax.tree_util.tree_leaves(tree)
        plan = P.plan_share_buckets(leaves, shards, partition_bytes)

        def f(scale):
            mine = [x * scale[0] for x in leaves]
            shares = C.reduce_scatter_tree(mine, plan, "dp")
            return shares, C.all_gather_tree(shares, plan, "dp")

        scale = jnp.arange(1.0, shards + 1)          # mean 2.5
        shares, whole = jax.jit(C.shard_map(
            f, mesh, in_specs=PartitionSpec("dp"),
            out_specs=(PartitionSpec("dp"), PartitionSpec())))(scale)
        for x, s, w in zip(leaves, shares, whole):
            # out_specs P("dp") lays the workers' shares end to end:
            # dim-0 shares make the leaf again
            np.testing.assert_allclose(np.asarray(s), 2.5 * np.asarray(x),
                                       rtol=1e-6)
            np.testing.assert_allclose(np.asarray(w), 2.5 * np.asarray(x),
                                       rtol=1e-6)
            assert w.shape == x.shape
