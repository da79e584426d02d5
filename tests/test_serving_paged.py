"""Paged KV cache (serving/blocks.py + the engine's paged mode).

THE parity anchor: a paged engine — block-granular slot memory, lazy
block grants, zero-copy prefix sharing, preemption under pressure —
must emit token-identical streams to sequential ``generate()`` (and so
to the dense engine, which pins the same baselines in
tests/test_serving.py), greedy AND seeded, including prefix-share and
chunked-prefill interleavings and across a preempt/resume cycle.  The
gather moves bytes and computes nothing, so parity is by construction;
these tests pin it bit-for-bit.

Zero-copy acceptance: on a paged engine prefix hits bump refcounts —
the ``prefix_copy``/``prefix_extract`` compile counters must stay 0
(no copy program even exists to run).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import generate
from byteps_tpu.models.transformer import Transformer, TransformerConfig
from byteps_tpu.serving import (
    PagedSlotPool,
    ServeMetrics,
    ServingEngine,
)
from byteps_tpu.serving import metrics as sm

M = 8  # tokens per request, shared so generate() compiles once per mode


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
        for i in range(4)]


@pytest.fixture(scope="module")
def greedy_base(tiny, prompts):
    _, model, variables = tiny
    return [np.asarray(generate(model, variables, p[None], M,
                                temperature=0.0)["tokens"])[0]
            for p in prompts]


@pytest.fixture(scope="module")
def paged_eng(tiny):
    _, model, variables = tiny
    return ServingEngine(model, variables, n_slots=4, max_seq=64,
                         temperature=0.0, paged=True, block=8,
                         metrics=ServeMetrics())


# ------------------------------------------------------------- pool wiring


def test_paged_pool_validation_and_sizing(tiny):
    cfg, _, _ = tiny
    # max_seq must be block-aligned (gathered row == dense row shape)
    with pytest.raises(ValueError, match="multiple of"):
        PagedSlotPool(cfg, 2, 60, block=8)
    # the pool must fit one max-length request + the null block
    with pytest.raises(ValueError, match="too small"):
        PagedSlotPool(cfg, 2, 64, block=8, n_blocks=8)
    # kv_quant has no paged path (traced-position int8 reads)
    with pytest.raises(ValueError, match="dense"):
        PagedSlotPool(cfg, 2, 64, block=8, kv_quant=True)
    # byte budget -> block count, dense-equivalent default
    pool = PagedSlotPool(cfg, 2, 64, block=8)
    assert pool.max_blocks == 8
    assert pool.alloc.n_blocks == 2 * 8 + 1  # dense-equivalent + null
    assert pool.caches[0]["k"].shape == (17, 8, cfg.kv_heads, cfg.d_head)
    budget = PagedSlotPool(cfg, 2, 64, block=8,
                           kv_bytes=12 * pool.block_bytes)
    assert budget.alloc.n_blocks == 12
    assert budget.null_block == 0 and budget.alloc.refs(0) == 1
    st = budget.block_stats()
    assert st["free"] == 11 and st["used"] == 1 and st["shared"] == 0


# ------------------------------------------------------------------ parity


def test_paged_greedy_parity_and_lazy_block_growth(tiny, prompts,
                                                   greedy_base, paged_eng):
    """4 concurrent requests on the paged engine are bit-identical to
    sequential generate(), and blocks are granted lazily: the pool's
    usage peaks at actual usage, never n_slots * max_blocks."""
    eng = paged_eng
    reqs = [eng.submit(p, M) for p in prompts]
    peak = 0
    for _ in range(64):
        eng.step()
        peak = max(peak, eng.pool.alloc.used_count)
        if all(r.done for r in reqs):
            break
    for r, b in zip(reqs, greedy_base):
        np.testing.assert_array_equal(r.result(), b)
    # lazy grants: prompts are 5-8 tokens + M=8 new -> 2-3 blocks each
    # of 8 logical (a dense-equivalent pool would hold 32 + null)
    assert peak <= 1 + 4 * 3, peak
    assert eng.pool.alloc.used_count == 1  # everything reclaimed (null)


def test_paged_staggered_arrivals_and_compile_stability(tiny, prompts,
                                                        greedy_base,
                                                        paged_eng):
    eng = paged_eng
    r0 = eng.submit(prompts[0], M)
    eng.step()
    r1 = eng.submit(prompts[1], M)
    eng.step()
    r2 = eng.submit(prompts[2], M)
    eng.drain(timeout=120)
    for r, b in zip([r0, r1, r2], greedy_base):
        np.testing.assert_array_equal(r.result(), b)
    counts = eng.compile_counts()
    # the pos-capped gather compiles one decode program per block
    # high-water bucket touched (never more than O(log max_blocks));
    # these prompts grow through buckets {1, 2} of the 8-block table
    assert counts["decode"] == counts["decode_buckets"], counts
    assert 1 <= counts["decode_buckets"] <= 2, counts
    assert counts["prefix_copy"] == 0 and counts["prefix_extract"] == 0
    # steady state: a second wave over the same depths compiles NOTHING
    # new — decode, chunk, or gather-width buckets
    r3 = eng.submit(prompts[0], M)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(r3.result(), greedy_base[0])
    assert eng.compile_counts() == counts


def test_paged_seeded_parity(tiny, prompts):
    """Seeded sampling through the paged engine replays generate()'s
    exact key chain — the same anchor the dense engine pins."""
    _, model, variables = tiny
    p = prompts[0]
    base = np.asarray(generate(
        model, variables, p[None], M, temperature=0.8, top_k=20,
        rng=jax.random.PRNGKey(100))["tokens"])[0]
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.8, top_k=20, paged=True, block=8,
                        metrics=ServeMetrics())
    req = eng.submit(p, M, seed=100)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(req.result(), base)


# ------------------------------------------------- zero-copy prefix share


def test_prefix_hit_shares_blocks_zero_copy(tiny):
    """A prefix hit on the paged engine is refcount bumps: the admitted
    slot's table adopts the store's blocks, no device-side K/V copy
    happens for whole shared blocks (prefix_copy/prefix_extract compile
    counters pinned at 0), and the token streams stay bit-identical to
    generate() — chunked prefill resuming at the shared boundary."""
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
                        prefix_cache=True, metrics=ServeMetrics())
    rA = eng.submit(pA, M)
    eng.drain(timeout=120)
    # A's own blocks are now store-referenced (insert = refcount bumps)
    assert eng.prefix.entry_count == 1
    assert eng.metrics.get(sm.PREFIX_INSERTIONS) == 1
    rB = eng.submit(pB, M)
    eng.step()  # admission: B's table adopts the shared blocks
    assert eng.pool.alloc.shared_count() >= 2  # 16 tokens / 8 block
    eng.drain(timeout=120)
    np.testing.assert_array_equal(rA.result(), base[0])
    np.testing.assert_array_equal(rB.result(), base[1])
    counts = eng.compile_counts()
    assert counts["prefix_copy"] == 0, counts      # zero-copy: no copy
    assert counts["prefix_extract"] == 0, counts   # program ever ran
    assert counts["block_cow"] == 0, counts        # aligned: no forks
    assert eng.metrics.get(sm.PREFIX_HITS) == 1
    assert eng.metrics.get(sm.PREFIX_HIT_TOKENS) == 16
    # a paged engine refuses a foreign store (block ids are pool-local)
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      paged=True, block=8, prefix_cache=eng.prefix,
                      metrics=ServeMetrics())
    # ...and a DENSE engine refuses a paged store (its entries are
    # block ids, not row buffers — it would die on first insert/hit)
    with pytest.raises(ValueError, match="dense"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      prefix_cache=eng.prefix, metrics=ServeMetrics())


# ------------------------------------------------------------- preemption


def _preempt_prompts():
    pA = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (19,), 0, 61), np.int32)
    pB = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (18,), 0, 61), np.int32)
    return pA, pB


def test_preemption_under_block_pressure_greedy(tiny):
    """Two requests whose combined K/V exceeds the block pool: the
    newest is preempted back to QUEUED (never deadlocked), waits out
    the pressure, resumes by re-prefill, and BOTH streams stay
    bit-identical to generate().  Tokens emitted before the preemption
    are kept — consumers see a stall, never a replay."""
    _, model, variables = tiny
    pA, pB = _preempt_prompts()
    m = 30  # each needs ~7 of the pool's 8 usable blocks
    base = [np.asarray(generate(model, variables, p[None], m,
                                temperature=0.0)["tokens"])[0]
            for p in (pA, pB)]
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        temperature=0.0, paged=True, block=8,
                        kv_blocks=9, metrics=ServeMetrics())
    r0 = eng.submit(pA, m)
    r1 = eng.submit(pB, m)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(r0.result(), base[0])
    np.testing.assert_array_equal(r1.result(), base[1])
    # preempted exactly once: the re-admission watermark keeps the
    # victim QUEUED until its need fits (no preempt/re-prefill thrash)
    assert eng.metrics.get(sm.PREEMPTIONS) == 1
    assert eng.pool.alloc.used_count == 1  # all blocks reclaimed


@pytest.mark.slow
def test_preemption_under_block_pressure_seeded(tiny):
    """Slow sibling of the greedy preemption test above (sampling-path
    compile).
    The preempt/resume cycle preserves the per-request sampling key
    chain: the resume prefill's sampled token and key split are
    discarded, the parked token + carried key continue the stream —
    seeded output identical to an unpreempted generate()."""
    _, model, variables = tiny
    pA, pB = _preempt_prompts()
    m = 30
    base = [np.asarray(generate(
        model, variables, p[None], m, temperature=0.8, top_k=20,
        rng=jax.random.PRNGKey(40 + i))["tokens"])[0]
        for i, p in enumerate((pA, pB))]
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        temperature=0.8, top_k=20, paged=True, block=8,
                        kv_blocks=9, metrics=ServeMetrics())
    r0 = eng.submit(pA, m, seed=40)
    r1 = eng.submit(pB, m, seed=41)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(r0.result(), base[0])
    np.testing.assert_array_equal(r1.result(), base[1])
    assert eng.metrics.get(sm.PREEMPTIONS) >= 1


def test_pressure_evicts_prefix_store_before_preempting(tiny):
    """Cached-but-unreferenced prefixes are the cheapest memory under
    block pressure: a request whose need exceeds the free pool evicts
    the store's LRU entries (bumping serve.block_evictions) and
    completes — preemption and failure are later resorts.  (A lone
    max-length request can ALWAYS complete: the pool floor at
    construction guarantees max_blocks + null, and the store is
    evictable; the typed-failure branch is defense-in-depth.)"""
    _, model, variables = tiny
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.0, paged=True, block=8,
                        kv_blocks=9, prefix_cache=True,
                        metrics=ServeMetrics())
    # fill the store so its entries pin blocks, then retire the slot:
    # the pressure path must evict the store BEFORE failing anything
    warm = eng.submit(np.arange(16, dtype=np.int32) % 61, 2)
    eng.drain(timeout=60)
    assert len(warm.result()) == 2
    assert eng.prefix.entry_count == 1
    # 20 + 44 = 64 positions = all 8 usable blocks: fits only after
    # the store's 2 blocks are pressure-evicted (a DISJOINT prompt —
    # sharing the warm prefix would sidestep the pressure)
    big = eng.submit((np.arange(20, dtype=np.int32) + 23) % 61, 44)
    eng.drain(timeout=120)
    assert len(big.result()) == 44
    assert eng.metrics.get(sm.BLOCK_EVICTIONS) >= 1
    # the warm chain was pressure-evicted NODE BY NODE (the radix store
    # drains a cold chain leaf-first: 2 blocks = 2 node evictions); the
    # one remaining entry (= chain leaf) is big's OWN post-prefill
    # insertion (refcount bumps on its blocks)
    assert eng.prefix.evictions == 2 and eng.prefix.entry_count == 1
    assert eng.prefix.blocks_released == 2


def test_held_request_is_not_overtaken_by_newer_arrivals(tiny):
    """FCFS under pressure: while a preempted request waits on its
    re-admission watermark, requests submitted after it must NOT slip
    past and consume each tick's freed blocks (sustained arrivals
    would starve it forever)."""
    _, model, variables = tiny
    pA, pB = _preempt_prompts()
    eng = ServingEngine(model, variables, n_slots=3, max_seq=64,
                        temperature=0.0, paged=True, block=8,
                        kv_blocks=9, metrics=ServeMetrics())
    a = eng.submit(pA, 30)   # oldest, ~7 blocks
    b = eng.submit(pB, 30)   # collides with a -> preempted, held
    for _ in range(30):
        eng.step()
        if eng.metrics.get(sm.PREEMPTIONS):
            break
    assert eng.metrics.get(sm.PREEMPTIONS) == 1
    assert b.state.value == "queued"
    c = eng.submit(pB[:8], 2)  # newer short request: blocks would fit
    stats = eng.step()
    # ...but it must wait behind the held request b
    assert stats["admitted"] == 0, stats
    assert c.state.value == "queued"
    eng.drain(timeout=120)
    # b resumed first; c completed after — both fully served
    assert b.state.value == "done" and len(b.result()) == 30
    assert len(c.result()) == 2
    assert b.t_first < c.t_first


def test_padded_bucket_tail_holds_no_ghost_blocks(tiny, prompts):
    """Block grants cover the chunk's REAL tokens only: the padded
    bucket tail writes route to the null block instead of pinning
    pad-only blocks for the slot's whole lifetime."""
    _, model, variables = tiny
    pA, _ = _preempt_prompts()  # 19 tokens
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.0, paged=True, block=8,
                        metrics=ServeMetrics())
    r = eng.submit(pA, 4)
    eng.step()  # whole-prompt chunk pads 19 -> bucket 32
    # 19 real tokens -> 3 blocks of 8; blocks for positions [24, 32)
    # of the padded bucket must NOT be held
    assert len(eng.pool.tables[r.slot]) == 3
    eng.drain(timeout=60)
    assert len(r.result()) == 4


# --------------------------------------------- eager cancel + observability


def test_cancel_reclaims_blocks_same_tick(tiny):
    """Satellite: cancel() of an in-flight request returns its
    non-shared blocks at cancel time (eager, engine-lock serialized),
    and a full pool admits a queued request on the very next tick."""
    _, model, variables = tiny
    pA, pB = _preempt_prompts()
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        temperature=0.0, paged=True, block=8,
                        kv_blocks=9, metrics=ServeMetrics())
    a = eng.submit(pA, 30)
    b = eng.submit(pB, 30)
    eng.step()
    eng.step()  # both in flight, pool saturating
    c = eng.submit(pB[:8], 4)  # no free slot -> queued
    assert eng.scheduler.depth == 1
    free_before = eng.pool.alloc.free_count
    eng.cancel(a)  # eager: slot AND blocks return NOW, no tick needed
    assert a.done and a.state.value == "cancelled"
    assert eng.pool.alloc.free_count > free_before
    stats = eng.step()  # the very next tick admits c
    assert stats["admitted"] == 1, stats
    eng.cancel(b)
    eng.drain(timeout=120)
    assert len(c.result()) == 4
    assert eng.pool.alloc.used_count == 1  # only the null block


def test_block_gauges_metrics_and_tcp_stats(tiny, prompts, paged_eng):
    """Block-pool observability: kv_blocks_{free,used,shared} gauges on
    the registry after a tick, and the TCP STATS reply carries the pool
    accounting next to prefix_cache."""
    from byteps_tpu.serving.frontend import RemoteServeClient, serve

    eng = paged_eng
    req = eng.submit(prompts[0], M)
    eng.step()
    gauges = eng.metrics.registry.snapshot()["gauges"]
    assert {sm.KV_BLOCKS_FREE, sm.KV_BLOCKS_USED,
            sm.KV_BLOCKS_SHARED} <= set(gauges), gauges
    assert gauges[sm.KV_BLOCKS_USED] >= 2  # null + the first block
    eng.drain(timeout=120)
    assert len(req.result()) == M
    srv, _ = serve(eng, port=0, host="127.0.0.1", in_thread=True)
    try:
        c = RemoteServeClient("127.0.0.1:%d" % srv.server_address[1])
        stats = c.stats()
        kv = stats["kv_blocks"]
        assert kv["block"] == 8 and kv["n_blocks"] == 33
        assert kv["free"] + kv["used"] == kv["n_blocks"]
        c.close()
    finally:
        srv.shutdown()
        srv.server_close()
