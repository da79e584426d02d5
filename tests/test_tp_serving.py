"""Tensor-parallel paged serving (ISSUE 20 tentpole a).

``tp > 1`` shards the paged block pool into per-KV-head-slice sub-pools
(``[tp, n_blocks, block, (KV/tp)*D]``, serving/blocks.py) and serves
them through either the head-sliced fused kernel
(``paged_decode_attention_sharded``) or the gather fallback, which
reassembles the unsharded flat row byte-for-byte and rides the grouped
dense path.  Attention is exactly partitioned by KV head, so the parity
bar is the same one every serving feature pins: token-identical streams
to sequential ``generate()`` (greedy AND seeded), across prefix hits,
chunked prefill, preempt/resume, int8 pools, and the disagg ship seam.

The refusal-message satellite lives here too: tp NOT dividing
``kv_heads`` keeps a typed refusal naming the grouped-layout fallback
and the padding option (the init_cache twin is pinned in
tests/test_resilience.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import generate
from byteps_tpu.models.transformer import Transformer, TransformerConfig
from byteps_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_sharded,
)
from byteps_tpu.serving import (
    PagedSlotPool,
    ServeMetrics,
    ServingEngine,
)
from byteps_tpu.serving import metrics as sm

M = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, 61)
    variables = model.init(jax.random.PRNGKey(1), toks)
    return cfg, model, variables


@pytest.fixture(scope="module")
def prompts():
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (5 + i,), 0, 61), np.int32)
        for i in range(3)]


@pytest.fixture(scope="module")
def greedy_base(tiny, prompts):
    _, model, variables = tiny
    return [np.asarray(generate(model, variables, p[None], M,
                                temperature=0.0)["tokens"])[0]
            for p in prompts]


# --------------------------------------------------- pool shapes + refusals


def test_tp_pool_shapes_and_total_bytes(tiny):
    """tp=2 pools carry a leading shard axis with the per-shard head
    slice on the minor axis; ``block_bytes`` stays the TOTAL across
    shards so byte-budget sizing is tp-independent."""
    cfg, _, _ = tiny
    base = PagedSlotPool(cfg, 2, 64, block=8, layout="flat")
    pool = PagedSlotPool(cfg, 2, 64, block=8, tp=2, layout="flat")
    KVs_D = (cfg.kv_heads // 2) * cfg.d_head
    assert pool.caches[0]["k"].shape == (2, pool.alloc.n_blocks, 8, KVs_D)
    assert pool.layout == "flat"
    assert pool.block_bytes == base.block_bytes
    assert pool.alloc.n_blocks == base.alloc.n_blocks
    # int8: s8 values + f32 scales, both per-shard
    q = PagedSlotPool(cfg, 2, 64, block=8, kv_dtype="int8", tp=2)
    assert q.caches[0]["k"].dtype == jnp.int8
    assert q.caches[0]["k_scale"].shape == (2, q.alloc.n_blocks, 8, 1)


def test_tp_refusal_messages(tiny):
    """Satellite: tp not dividing kv_heads keeps a typed refusal whose
    message names the padding option; the engine refuses tp on dense
    engines and tp not dividing num_heads."""
    cfg, model, variables = tiny  # kv_heads == 2
    with pytest.raises(ValueError, match="divide kv_heads") as ei:
        PagedSlotPool(cfg, 2, 64, block=8, tp=3)
    assert "pad kv_heads" in str(ei.value)
    with pytest.raises(ValueError, match="tp must be >= 1"):
        PagedSlotPool(cfg, 2, 64, block=8, tp=0)
    # grouped layout cannot carry per-shard sub-pools (fp pools)
    with pytest.raises(ValueError, match="flat"):
        PagedSlotPool(cfg, 2, 64, block=8, tp=2, layout="grouped")
    with pytest.raises(ValueError, match="paged=True"):
        ServingEngine(model, variables, n_slots=1, max_seq=64, tp=2,
                      metrics=ServeMetrics())
    # the engine checks query-head alignment before pool construction
    with pytest.raises(ValueError, match="divide num_heads"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      paged=True, block=8, tp=3, metrics=ServeMetrics())


# ------------------------------------------------ op-level bit-exactness


def test_sharded_kernel_matches_unsharded():
    """The head-slice exactness argument, pinned at the op: per-shard
    kernel calls over the per-shard pools, concatenated over heads,
    equal the unsharded kernel on the unsharded pool — attention is
    exactly partitioned by KV head (docs/parallel.md).

    Compared at a few f32 ulps, not bit-for-bit: the two are different
    programs.  The unsharded block-diagonal dot contracts KV*D columns
    (the other heads' columns are exact zeros), the per-shard dot
    contracts KV*D/tp, and the backend may sum a 32-long and a 16-long
    contraction in a different order (jaxlib 0.9.0 does: 36 of 96
    outputs differ by half an ulp).  Token-level parity across tp
    widths is pinned separately, through the engine."""
    rng = np.random.RandomState(0)
    B, H, D, KV, blk, mb, nb, tp = 3, 4, 8, 4, 4, 6, 16, 2
    pos = np.array([3, 9, 17], np.int32)
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    pk = jnp.asarray(rng.randn(nb, blk, KV * D), jnp.float32)
    pv = jnp.asarray(rng.randn(nb, blk, KV * D), jnp.float32)
    tables = np.zeros((B, mb), np.int32)
    nxt = iter(range(1, nb))
    for b in range(B):
        for j in range((int(pos[b]) + 1 + blk - 1) // blk + 1):
            tables[b, j] = next(nxt)
    tables = jnp.asarray(tables)
    base = paged_decode_attention(q, pk, pv, tables, jnp.asarray(pos),
                                  interpret=True)
    # per-shard pools: contiguous minor-axis slices ARE the head slices
    X = (KV // tp) * D
    spk = jnp.stack([pk[..., s * X:(s + 1) * X] for s in range(tp)])
    spv = jnp.stack([pv[..., s * X:(s + 1) * X] for s in range(tp)])
    out = paged_decode_attention_sharded(q, spk, spv, tables,
                                         jnp.asarray(pos),
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                               rtol=4 * np.finfo(np.float32).eps,
                               atol=4 * np.finfo(np.float32).eps)


def test_sharded_kernel_int8_bit_identical():
    """Same pin for the int8 pools: per-(position, head) scales are
    head-independent, so the per-shard dequant is an exact slice."""
    rng = np.random.RandomState(1)
    B, H, D, KV, blk, mb, nb, tp = 2, 4, 8, 2, 4, 4, 8, 2
    pos = np.array([2, 11], np.int32)
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    pk = jnp.asarray(rng.randint(-127, 127, (nb, blk, KV * D)), jnp.int8)
    pv = jnp.asarray(rng.randint(-127, 127, (nb, blk, KV * D)), jnp.int8)
    ks = jnp.asarray(rng.rand(nb, blk, KV), jnp.float32)
    vs = jnp.asarray(rng.rand(nb, blk, KV), jnp.float32)
    tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 6]], jnp.int32)
    base = paged_decode_attention(q, pk, pv, tables, jnp.asarray(pos),
                                  k_scale=ks, v_scale=vs, interpret=True)
    X, KVs = (KV // tp) * D, KV // tp
    spk = jnp.stack([pk[..., s * X:(s + 1) * X] for s in range(tp)])
    spv = jnp.stack([pv[..., s * X:(s + 1) * X] for s in range(tp)])
    sks = jnp.stack([ks[..., s * KVs:(s + 1) * KVs] for s in range(tp)])
    svs = jnp.stack([vs[..., s * KVs:(s + 1) * KVs] for s in range(tp)])
    out = paged_decode_attention_sharded(
        q, spk, spv, tables, jnp.asarray(pos), k_scale=sks, v_scale=svs,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


# ------------------------------------------------------- engine parity


def test_tp_gather_greedy_parity(tiny, prompts, greedy_base):
    _, model, variables = tiny
    eng = ServingEngine(model, variables, n_slots=4, max_seq=64,
                        temperature=0.0, paged=True, block=8, tp=2,
                        metrics=ServeMetrics())
    assert eng.pool.caches[0]["k"].ndim == 4  # [tp, nb, blk, X]
    reqs = [eng.submit(p, M) for p in prompts]
    eng.drain(timeout=120)
    for r, b in zip(reqs, greedy_base):
        np.testing.assert_array_equal(r.result(), b)
    assert eng.pool.alloc.used_count == 1  # reclaimed down to null


def test_tp_gather_seeded_parity(tiny, prompts):
    _, model, variables = tiny
    p = prompts[0]
    base = np.asarray(generate(
        model, variables, p[None], M, temperature=0.8, top_k=20,
        rng=jax.random.PRNGKey(100))["tokens"])[0]
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.8, top_k=20, paged=True, block=8,
                        tp=2, metrics=ServeMetrics())
    req = eng.submit(p, M, seed=100)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(req.result(), base)


@pytest.mark.slow  # ~5s; tp greedy parity stays fast and test_paged_attention covers prefix zero-copy fast
def test_tp_prefix_hit_zero_copy_parity(tiny):
    """Prefix sharing under tp: block ids name the same token span on
    every shard, so hits stay refcount bumps (zero-copy) and chunked
    prefill resumes at the shared boundary — streams bit-identical to
    generate()."""
    _, model, variables = tiny
    shared = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (16,), 0, 61), np.int32)
    pA = np.concatenate([shared, np.asarray([3, 9, 4], np.int32)])
    pB = np.concatenate([shared, np.asarray([11, 2], np.int32)])
    base = [np.asarray(generate(model, variables, p[None], M,
                                temperature=0.0)["tokens"])[0]
            for p in (pA, pB)]
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        temperature=0.0, paged=True, block=8, chunk=8,
                        tp=2, prefix_cache=True, metrics=ServeMetrics())
    rA = eng.submit(pA, M)
    eng.drain(timeout=120)
    rB = eng.submit(pB, M)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(rA.result(), base[0])
    np.testing.assert_array_equal(rB.result(), base[1])
    counts = eng.compile_counts()
    assert counts["prefix_copy"] == 0 and counts["prefix_extract"] == 0
    assert eng.metrics.get(sm.PREFIX_HITS) == 1
    assert eng.metrics.get(sm.PREFIX_HIT_TOKENS) == 16


@pytest.mark.slow  # ~6s; tp gather greedy/seeded parity stays fast and test_serving_paged covers preemption fast
def test_tp_preempt_resume_parity(tiny):
    """Preemption under block pressure with tp=2: the victim re-prefills
    per-shard pools and both streams stay bit-identical to generate()."""
    _, model, variables = tiny
    pA = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (19,), 0, 61), np.int32)
    pB = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (18,), 0, 61), np.int32)
    m = 30
    base = [np.asarray(generate(model, variables, p[None], m,
                                temperature=0.0)["tokens"])[0]
            for p in (pA, pB)]
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        temperature=0.0, paged=True, block=8, tp=2,
                        kv_blocks=9, metrics=ServeMetrics())
    r0 = eng.submit(pA, m)
    r1 = eng.submit(pB, m)
    eng.drain(timeout=180)
    np.testing.assert_array_equal(r0.result(), base[0])
    np.testing.assert_array_equal(r1.result(), base[1])
    assert eng.metrics.get(sm.PREEMPTIONS) == 1
    assert eng.pool.alloc.used_count == 1


@pytest.mark.slow  # ~6s; test_sharded_kernel_int8_bit_identical keeps the int8 head-slice math fast
def test_tp_int8_pool_token_parity(tiny, prompts):
    """int8 per-shard pools: quantize-at-write is per-(position, head),
    so the sharded pool's bytes are an exact slice of the unsharded
    pool's — token streams identical between tp=1 and tp=2."""
    _, model, variables = tiny

    def run(tp):
        eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                            temperature=0.0, paged=True, block=8, tp=tp,
                            kv_dtype="int8", metrics=ServeMetrics())
        r = eng.submit(prompts[0], M)
        eng.drain(timeout=120)
        return r.result()

    np.testing.assert_array_equal(run(1), run(2))


def test_tp_disagg_wire_format_is_tp_independent(tiny, prompts):
    """extract_kv_blocks reassembles per-shard slices head-major into
    the unsharded flat row bytes: a tp=2 extract equals a tp=1 extract
    row-major, and write/extract round-trips byte-exact — ships work
    across tiers with different tp counts."""
    _, model, variables = tiny

    def park(tp):
        eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                            temperature=0.0, paged=True, block=8, tp=tp,
                            metrics=ServeMetrics())
        r = eng.submit(prompts[0], 4, keep_kv=True)
        eng.drain(timeout=120)
        return eng, eng.take_parked_kv(r.id)

    e1, kv1 = park(1)
    e2, kv2 = park(2)
    b1 = e1.extract_kv_blocks(kv1["ids"])
    b2 = e2.extract_kv_blocks(kv2["ids"])
    for l1, l2 in zip(b1, b2):
        for n in l1:
            np.testing.assert_array_equal(
                l1[n].reshape(l1[n].shape[0], -1),
                l2[n].reshape(l2[n].shape[0], -1))
    # round-trip through the tp=2 pool
    ids2 = e2.stage_alloc(len(kv2["ids"]))
    for j, bid in enumerate(ids2):
        e2.write_kv_block(bid, [{n: l[n][j] for n in l} for l in b2])
    b2rt = e2.extract_kv_blocks(ids2)
    for l1, l2 in zip(b2, b2rt):
        for n in l1:
            np.testing.assert_array_equal(l1[n], l2[n])
    e1.release_kv_ids(kv1["ids"])
    e2.release_kv_ids(kv2["ids"])
    e2.release_kv_ids(ids2)


@pytest.mark.slow
def test_tp_fused_kernel_engine_parity(tiny, prompts):
    """Slow sibling of test_sharded_kernel_bit_identical_to_unsharded:
    the whole engine on the fused kernel path (interpret mode), tp=2 vs
    tp=1, token-identical streams."""
    _, model, variables = tiny

    def run(tp):
        eng = ServingEngine(model, variables, n_slots=2, max_seq=32,
                            temperature=0.0, paged=True, block=8, tp=tp,
                            paged_kernel="on", metrics=ServeMetrics())
        reqs = [eng.submit(p[:5], 6) for p in prompts[:2]]
        eng.drain(timeout=240)
        return [r.result() for r in reqs]

    for a, b in zip(run(1), run(2)):
        np.testing.assert_array_equal(a, b)
