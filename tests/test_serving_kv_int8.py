"""int8 paged KV blocks (kv_dtype="int8") + block-granular radix
prefix sharing (PR 19).

Three layers, all on CPU:

* **kernel** — the quantized-pool variant of ops/paged_attention.py
  dequantizes s8 blocks + per-(position, head) scale rows in-register
  (interpret mode) and matches a dense dequantize-after-gather
  reference at every query width, GQA included.
* **engine** — kv_dtype="int8" halves (better: ~3x at this geometry)
  block bytes at fixed budget, keeps kernel-vs-gather token parity and
  run-to-run bit-exactness (quantize-at-write determinism: COW forks,
  re-feed rewrites, and preempt/resume re-prefill all reproduce
  identical s8 bytes), and refuses the legacy dense kv_quant knob in
  one clear error.
* **radix store** — serving/prefix.py stores one node per block
  boundary, so two requests sharing a prefix NEVER inserted as a
  single entry still share physical blocks; partial insert under
  budget and leaf-only LRU eviction keep the chain invariant.

The int8-vs-fp32 token streams are NOT asserted equal — divergence is
bounded by the documented per-element quantization error (scale/2 =
absmax/254, docs/serving.md "int8 paged KV"); what IS pinned exact is
every int8-vs-int8 comparison: kernel vs gather, preempt vs
unpressured, COW-forked vs fresh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    _quantize_kv,
)
from byteps_tpu.ops.paged_attention import paged_decode_attention
from byteps_tpu.serving import PagedSlotPool, ServeMetrics, ServingEngine
from byteps_tpu.serving import metrics as sm
from byteps_tpu.serving.blocks import BlockAllocator, init_paged_cache
from byteps_tpu.serving.prefix import PagedPrefixCache

TOL = 2e-5  # same dense-vs-online-softmax pin as test_paged_attention

M = 8


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))
    return cfg, model, variables


@pytest.fixture(scope="module")
def prompts():
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (5 + i,), 0, 61), np.int32)
        for i in range(3)]


def _int8_engine(model, variables, *, paged_kernel="off", n_slots=2,
                 **kw):
    return ServingEngine(model, variables, n_slots=n_slots, max_seq=64,
                         temperature=kw.pop("temperature", 0.0),
                         paged=True, block=8, kv_dtype="int8",
                         paged_kernel=paged_kernel,
                         metrics=ServeMetrics(), **kw)


# --------------------------------------------------- quantize roundtrip


def test_quantize_roundtrip_error_bound_and_determinism():
    """Per-(position, head) symmetric int8: |x - s8*scale| <= scale/2
    elementwise (absmax maps to ±127 exactly), zero rows stay exactly
    zero with scale 1, and requantizing is bit-deterministic — the
    property every resume/COW/disagg parity claim stands on."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 16)), jnp.float32)
    q, s = _quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 5, 3)
    deq = q.astype(jnp.float32) * s[..., None]
    err = np.abs(np.asarray(deq - x))
    bound = np.asarray(s)[..., None] / 2 + 1e-7
    assert (err <= bound).all()
    # absmax element hits ±127 exactly -> roundtrips exactly
    amax = np.abs(np.asarray(x)).max(-1)
    np.testing.assert_allclose(np.asarray(s), amax / 127.0, rtol=1e-6)
    # zero rows: scale 1, values 0
    q0, s0 = _quantize_kv(jnp.zeros((1, 2, 2, 8)))
    assert not np.asarray(q0).any() and (np.asarray(s0) == 1.0).all()
    # write-time determinism, bit for bit
    q2, s2 = _quantize_kv(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))


# ------------------------------------------------- kernel vs gather ref


@pytest.mark.parametrize("tq", [1, 2, 5])
def test_kernel_matches_dequantized_gather_int8(tq):
    """Quantized-pool kernel (interpret) vs a dense softmax over the
    DEQUANTIZED gathered rows — decode (tq=1) and the spec-verify
    widths, under GQA, with unwritten positions' scale rows poisoned
    (NaN) to prove the in-kernel mask runs before the scale fold."""
    rng = np.random.default_rng(1)
    B, H, KV, D, bs, nblog = 2, 4, 2, 16, 8, 4
    KVD = KV * D
    pos = np.array([11, 7], np.int32)
    table = np.arange(1, 1 + B * nblog, dtype=np.int32).reshape(B, nblog)
    S = nblog * bs
    k = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    v = rng.standard_normal((B, S, KV, D)).astype(np.float32)
    q = rng.standard_normal((B, tq, H, D)).astype(np.float32)

    k8, ks = _quantize_kv(jnp.asarray(k))
    v8, vs = _quantize_kv(jnp.asarray(v))
    k8, ks = np.asarray(k8), np.asarray(ks)
    v8, vs = np.asarray(v8), np.asarray(vs)
    npool = 1 + B * nblog
    pool_k = np.zeros((npool, bs, KVD), np.int8)
    pool_v = np.zeros((npool, bs, KVD), np.int8)
    pool_ks = np.full((npool, bs, KV), np.nan, np.float32)
    pool_vs = np.full((npool, bs, KV), np.nan, np.float32)
    for b in range(B):
        for j in range(nblog):
            pid = table[b, j]
            sl = slice(j * bs, (j + 1) * bs)
            pool_k[pid] = k8[b, sl].reshape(bs, KVD)
            pool_v[pid] = v8[b, sl].reshape(bs, KVD)
            pool_ks[pid] = ks[b, sl]
            pool_vs[pid] = vs[b, sl]
    out = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(table), jnp.asarray(pos),
        k_scale=jnp.asarray(pool_ks), v_scale=jnp.asarray(pool_vs),
        interpret=True)
    kd = k8.astype(np.float32) * ks[..., None]
    vd = v8.astype(np.float32) * vs[..., None]
    G = H // KV
    ref = np.zeros_like(q)
    for b in range(B):
        for i in range(tq):
            p = int(pos[b]) + i
            for h in range(H):
                g = h // G
                s = (q[b, i, h] @ kd[b, :p + 1, g].T) * D ** -0.5
                w = np.exp(s - s.max())
                w /= w.sum()
                ref[b, i, h] = w @ vd[b, :p + 1, g]
    np.testing.assert_allclose(np.asarray(out), ref, atol=TOL, rtol=0)


def test_kernel_int8_requires_both_scales():
    pk8 = jnp.zeros((4, 8, 32), jnp.int8)
    scl = jnp.ones((4, 8, 2), jnp.float32)
    q = jnp.zeros((1, 1, 4, 16), jnp.float32)
    tab = jnp.zeros((1, 2), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="k_scale"):
        paged_decode_attention(q, pk8, pk8, tab, pos, interpret=True)
    with pytest.raises(ValueError, match="BOTH"):
        paged_decode_attention(q, pk8, pk8, tab, pos, k_scale=scl,
                               interpret=True)


# -------------------------------------------------- pool sizing + knobs


def test_int8_pool_sizing_and_leaves(tiny):
    cfg, _, _ = tiny
    # int8 forces flat storage with scale-row leaves, any layout arg
    caches = init_paged_cache(cfg, 3, 8, layout="grouped",
                              kv_dtype="int8")
    c0 = caches[0]
    KV, D = cfg.kv_heads, cfg.d_head
    assert c0["k"].dtype == jnp.int8 and c0["k"].shape == (3, 8, KV * D)
    assert c0["k_scale"].dtype == jnp.float32
    assert c0["k_scale"].shape == (3, 8, KV)
    assert set(c0) == {"k", "v", "k_scale", "v_scale"}

    fp = PagedSlotPool(cfg, 2, 64, block=8)
    q8 = PagedSlotPool(cfg, 2, 64, block=8, kv_dtype="int8")
    # per-block bytes: L * 2 sides * block * (s8 values + f32 scales)
    L = cfg.num_layers
    assert q8.block_bytes == L * 2 * 8 * (KV * D + 4 * KV)
    assert fp.block_bytes == L * 2 * 8 * KV * D * 4
    # the capacity acceptance: >= 1.8x blocks at a FIXED byte budget
    budget = 12 * fp.block_bytes
    nf = PagedSlotPool(cfg, 2, 64, block=8, kv_bytes=budget)
    n8 = PagedSlotPool(cfg, 2, 64, block=8, kv_bytes=budget,
                       kv_dtype="int8")
    assert n8.alloc.n_blocks >= 1.8 * nf.alloc.n_blocks, (
        n8.alloc.n_blocks, nf.alloc.n_blocks)
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedSlotPool(cfg, 2, 64, block=8, kv_dtype="int4")


def test_kv_quant_and_kv_dtype_are_mutually_exclusive(tiny):
    _, model, variables = tiny
    with pytest.raises(ValueError, match="mutually exclusive"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      paged=True, block=8, kv_quant=True,
                      kv_dtype="int8", metrics=ServeMetrics())
    with pytest.raises(ValueError, match="requires paged"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      kv_dtype="int8", metrics=ServeMetrics())
    # the legacy knob's paged refusal now names the replacement
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      paged=True, block=8, kv_quant=True,
                      metrics=ServeMetrics())
    # int8 paged engines are NOT resume-unsafe (write-time determinism)
    eng = _int8_engine(model, variables, n_slots=1)
    assert eng._resume_unsafe == ""


# ------------------------------------------------ engine parity anchors


def _run(model, variables, prompts, m, *, kv_dtype="int8", seed0=3,
         temperature=0.0, **kw):
    eng = ServingEngine(model, variables,
                        n_slots=kw.pop("n_slots", len(prompts)),
                        max_seq=64, temperature=temperature,
                        top_k=20 if temperature else None,
                        paged=True, block=8, kv_dtype=kv_dtype,
                        metrics=ServeMetrics(), **kw)
    reqs = [eng.submit(p, m, seed=seed0 + i)
            for i, p in enumerate(prompts)]
    eng.drain(timeout=300)
    return [np.asarray(r.result()) for r in reqs], eng


@pytest.mark.slow  # ~9s; kernel_matches_dequantized_gather_int8[1/2/5] keeps kernel-vs-gather parity fast
def test_engine_int8_kernel_vs_gather_parity_and_rerun(tiny, prompts):
    """The int8 acceptance anchor: fused-kernel (interpret) and
    gather-fallback engines emit IDENTICAL token streams from an int8
    pool, and a re-run is bit-exact — deterministic quantize-at-write
    leaves nothing path- or run-dependent.  The gather path dequantizes
    after gather (dense q8 attention), so CPU tests exercise the same
    numerics contract the kernel implements."""
    _, model, variables = tiny
    g_out, eng_g = _run(model, variables, prompts, M,
                        paged_kernel="off")
    k_out, eng_k = _run(model, variables, prompts, M,
                        paged_kernel="on")
    for a, b in zip(g_out, k_out):
        np.testing.assert_array_equal(a, b)
    counts = eng_k.compile_counts()
    assert counts["decode"] == counts["decode_buckets"] == 1, counts
    assert eng_k.metrics.get(sm.GATHERED_BLOCKS) == 0
    # run-to-run bit-exactness, both paths
    g2, _ = _run(model, variables, prompts, M, paged_kernel="off")
    for a, b in zip(g_out, g2):
        np.testing.assert_array_equal(a, b)
    # int8 engines actually report the shrunken pool
    assert eng_g.pool.kv_dtype == "int8"


@pytest.mark.slow  # ~8s; int8 pool sizing stays fast and test_serving_paged covers preemption fast
def test_engine_int8_preempt_resume_parity(tiny):
    """Preempt/resume on quantized shared storage: under block
    pressure the victim re-prefills and must reproduce the ORIGINAL
    run's int8 blocks byte-for-byte — streams stay identical to
    unpressured int8 runs (the resume acceptance anchor)."""
    _, model, variables = tiny
    pA = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (19,), 0, 61), np.int32)
    pB = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (18,), 0, 61), np.int32)
    m = 30
    baseA, _ = _run(model, variables, [pA], m, n_slots=1)
    baseB, _ = _run(model, variables, [pB], m, n_slots=1)
    outs, eng = _run(model, variables, [pA, pB], m, n_slots=2,
                     kv_blocks=9)
    np.testing.assert_array_equal(outs[0], baseA[0])
    np.testing.assert_array_equal(outs[1], baseB[0])
    assert eng.metrics.get(sm.PREEMPTIONS) >= 1
    assert eng.pool.alloc.used_count == 1


@pytest.mark.slow  # ~10s; test_serve_blocks COW-fork tests keep the fork semantics fast
def test_engine_int8_cow_on_quantized_shared_blocks(tiny):
    """COW forks quantized shared blocks whole — s8 values AND scale
    rows ride in one generic fork program.  With min_prefill_bucket=16
    a 56-token prefix hit leaves a 2-token tail whose covering bucket
    overruns the row; the boundary guard can't split below the minimum
    bucket, so the chunk shifts left to start=48 and RE-FEEDS positions
    48..56 — which live in a SHARED prefix block.  make_writable must
    fork it (block_cow == 1) and the requantized rewrite must land the
    identical s8 bytes: the stream matches a solo int8 run that never
    shared (and never shifted) at all."""
    _, model, variables = tiny
    m = 4
    X = _toks(56, seed=7)
    pA = np.concatenate([X, _toks(3, seed=8)])   # inserts 7 blocks
    pB = np.concatenate([X, _toks(2, seed=9)])   # hits all 56 tokens
    base, _ = _run(model, variables, [pB], m, n_slots=1)
    # kv_blocks=20 keeps the pool pressure-free so the store RETAINS
    # its refs — otherwise eviction drops them and no fork is needed
    eng = _int8_engine(model, variables, n_slots=1, prefix_cache=True,
                       min_prefill_bucket=16, kv_blocks=20)
    rA = eng.submit(pA, m)
    eng.drain(timeout=300)
    rA.result()
    assert eng.metrics.get(sm.PREFIX_INSERTIONS) == 1
    rB = eng.submit(pB, m)
    eng.drain(timeout=300)
    assert eng.metrics.get(sm.PREFIX_HIT_TOKENS) == 56
    counts = eng.compile_counts()
    assert counts["block_cow"] == 1, counts  # the fork program ran
    assert counts["prefix_copy"] == 0 and counts["prefix_extract"] == 0
    np.testing.assert_array_equal(np.asarray(rB.result()), base[0])


# ----------------------------------------------------- radix block index


def _toks(n, seed=0):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 61), np.int32)


def test_radix_store_chains_never_inserted_as_one_entry():
    """Two inserts along one token chain meet at shared nodes: a later
    match over the COMBINED prefix — never inserted as a single entry —
    returns the full canonical block chain."""
    alloc = BlockAllocator(32, 8)
    store = PagedPrefixCache(alloc, block=8, block_bytes=100)
    toks = _toks(32, seed=5)
    a = alloc.alloc(2)
    assert store.insert_blocks(toks[:16], a)
    # second request prefilled its own copies of blocks 0-1 (ids b[:2])
    # then extended: canonical dedup keeps a[:2], adopts b[2:]
    b = alloc.alloc(4)
    assert store.insert_blocks(toks, b)
    assert store.entry_count == 1          # one leaf = one chain
    assert len(store._entries) == 4        # four boundary nodes
    # b's duplicated prefix blocks took no store refs
    assert alloc.refs(b[0]) == 1 and alloc.refs(b[1]) == 1
    assert alloc.refs(a[0]) == 2 and alloc.refs(a[1]) == 2
    # a 4-block-prefix prompt matches the deepest boundary (capped at
    # len-1) and gets the canonical chain a[:2] + b[2:]
    probe = np.concatenate([toks, _toks(3, seed=6)])
    entry, blen = store.match(probe)
    assert blen == 32
    assert list(entry.buffer) == a + b[2:]
    assert store.hits == 1


def test_radix_store_partial_insert_and_leaf_only_eviction():
    """Budget holds 2 nodes: a 4-block insert stores its affordable
    2-block prefix (partial, not refused), and eviction drains chains
    leaf-first so every surviving boundary still has its ancestors —
    insertable_len's last-boundary probe stays exact."""
    alloc = BlockAllocator(32, 8)
    store = PagedPrefixCache(alloc, block=8, block_bytes=100,
                             max_bytes=200)
    toks = _toks(32, seed=9)
    ids = alloc.alloc(4)
    assert store.insert_blocks(toks, ids)
    assert len(store._entries) == 2        # partial: first 2 boundaries
    assert store.total_bytes == 200
    assert alloc.refs(ids[2]) == 1         # tail took no store refs
    # the stored prefix is still matchable...
    entry, blen = store.match(np.concatenate([toks, _toks(1)]))
    assert blen == 16 and list(entry.buffer) == ids[:2]
    # ...and a DIFFERENT chain evicts the old one leaf-first to fit
    other = _toks(16, seed=11)
    ids2 = alloc.alloc(2)
    assert store.insert_blocks(other, ids2)
    assert store.evictions >= 1
    # chain invariant: any indexed boundary's parent is indexed
    for e in store._entries:
        dig = e.keys[0][0]
        parent = store._node_parent[dig]
        assert parent is None or parent in store._index
    # full drain via evict_for frees every store ref
    store.evict_for(32)
    assert len(store._entries) == 0
    assert alloc.used_count == 6  # only the callers' own alloc refs


@pytest.mark.slow  # ~8s; the radix-store chain tests keep block-boundary sharing fast
def test_engine_radix_share_without_single_entry_insert(tiny):
    """The acceptance pin: C shares a 4-block prefix assembled from TWO
    different requests' inserts (never one entry) — its admit hit
    covers >= k-1 blocks, zero copy programs exist, and its stream
    matches an unshared int8 run bit-for-bit."""
    _, model, variables = tiny
    X = _toks(32, seed=21)
    pA = np.concatenate([X[:16], _toks(3, seed=22)])    # inserts blocks 0-1
    pB = np.concatenate([X, _toks(3, seed=23)])         # extends to 0-3
    pC = np.concatenate([X, _toks(2, seed=24)])         # shares all 4
    baseC, _ = _run(model, variables, [pC], M, n_slots=1)
    eng = _int8_engine(model, variables, n_slots=1, prefix_cache=True,
                       chunk=8)
    for p in (pA, pB):
        r = eng.submit(p, M)
        eng.drain(timeout=300)
        r.result()
    assert eng.prefix.entry_count == 1      # ONE chain, two insertions
    assert eng.prefix.insertions == 2
    rC = eng.submit(pC, M)
    eng.step()
    # k=4 block prefix, hit capped at len-1 -> shares k-1=3.. here the
    # 34-token prompt admits the full 4-block boundary (32 <= 33)
    assert eng.metrics.get(sm.PREFIX_HIT_TOKENS) >= 3 * 8
    assert eng.pool.alloc.shared_count() >= 3
    eng.drain(timeout=300)
    np.testing.assert_array_equal(np.asarray(rC.result()), baseC[0])
    counts = eng.compile_counts()
    assert counts["prefix_copy"] == 0 and counts["prefix_extract"] == 0
