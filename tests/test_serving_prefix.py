"""Chunked prefill + prefix-reuse KV cache (byteps_tpu/serving/).

The correctness anchor extends PR 2's: with chunked prefill and the
prefix cache enabled, the engine must stay token-identical to
sequential ``inference.generate()`` — bit-exact by construction, since
a prefix hit COPIES the K/V bytes whole prefill would recompute and a
chunk recomputes exactly the positions whole prefill would.  The rest:
per-tick prefill bounded by the chunk budget while decoders keep
emitting, compile-count pinning of the new programs (chunk traces
bounded by distinct chunk buckets; prefix copy/extract trace once),
and the PrefixCache store's hash/LRU/refcount/byte-budget mechanics.

Engines and generate() baselines are module-scoped where possible (jit
compiles dominate this file's cost).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import generate
from byteps_tpu.models.transformer import Transformer, TransformerConfig
from byteps_tpu.serving import (
    PrefixCache,
    RequestState,
    ServeMetrics,
    ServingEngine,
)
from byteps_tpu.serving import metrics as sm

M = 6  # tokens per request, shared so generate() compiles once per mode


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
def shared_prompts():
    """Prompts sharing a 32-token prefix, plus one unrelated prompt."""
    shared = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (32,), 0, 61), np.int32)
    tails = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(50 + i), (3 + i,), 0, 61), np.int32)
        for i in range(2)]
    other = np.asarray(jax.random.randint(
        jax.random.PRNGKey(60), (20,), 0, 61), np.int32)
    return ([np.concatenate([shared, t]) for t in tails]
            + [shared.copy(), other])


def _gen(model, variables, prompt, temperature=0.0, **kw):
    return np.asarray(generate(model, variables, prompt[None], M,
                               temperature=temperature, **kw)["tokens"])[0]


# -------------------------------------------------------- prefix store unit


def test_prefix_cache_store_mechanics():
    buf = lambda v: {"k": jnp.full((1, 8, 2), v, jnp.float32)}  # noqa: E731
    pc = PrefixCache(block=4, max_bytes=3 * 64)  # budget = 3 entries
    t = np.arange(16, dtype=np.int32)
    # nothing cached -> miss, and short prompts can never match
    assert pc.match(t) is None
    assert pc.insertable_len(t[:3]) == 0
    # insert 2 blocks; every boundary of the entry is indexed
    assert pc.insertable_len(t[:11]) == 8
    assert pc.insert(t[:8], buf(1.0))
    e1, L = pc.match(t)             # longest boundary wins
    assert L == 8 and np.array_equal(e1.tokens, t[:8])
    _, L1 = pc.match(t[:6])         # shorter prompt hits block 1
    assert L1 == 4
    # usable match is capped at len(prompt) - 1
    _, L2 = pc.match(t[:8])
    assert L2 == 4
    # re-inserting the same prefix stores nothing new
    assert pc.insertable_len(t[:8]) == 0
    assert not pc.insert(t[:8], buf(9.0))
    # a diverging prompt misses even at a colliding length
    t2 = t.copy()
    t2[1] = 60
    assert pc.match(t2) is None
    # LRU eviction under the byte budget: touch e1, add two more
    # entries, then overflow — the least-recently-matched dies first
    assert pc.insert(t2[:8], buf(2.0))
    pc.match(t)                     # e1 most recent
    e3 = np.full((8,), 7, np.int32)
    assert pc.insert(e3, buf(3.0))  # 3 entries = at budget
    e4 = np.full((8,), 9, np.int32)
    assert pc.insert(e4, buf(4.0))  # overflow -> evict t2 (LRU)
    assert pc.evictions == 1 and pc.match(t2) is None
    assert pc.match(t) is not None
    # refcount pins against eviction
    pinned, _ = pc.match(e3)
    pc.acquire(pinned)
    e5 = np.full((8,), 11, np.int32)
    assert pc.insert(e5, buf(5.0))
    assert pc.match(e3) is not None, "pinned entry must survive eviction"
    pc.release(pinned)
    with pytest.raises(ValueError):
        pc.release(pinned)
    # an entry bigger than the whole budget is refused
    tiny_pc = PrefixCache(block=4, max_bytes=8)
    assert not tiny_pc.insert(t[:4], buf(1.0))
    assert tiny_pc.entry_count == 0


def test_prefix_cache_eviction_repoints_shared_boundaries():
    """Boundaries first registered by an evicted entry re-point to a
    surviving entry sharing those blocks: evicting the short prefix
    must not blind lookups to K/V a longer superset entry still
    holds."""
    buf = lambda v: {"k": jnp.full((1, 8, 2), v, jnp.float32)}  # noqa: E731
    pc = PrefixCache(block=4, max_bytes=2 * 64)
    t = np.arange(12, dtype=np.int32)
    assert pc.insert(t[:8], buf(1.0))       # A owns boundaries 4, 8
    assert pc.insert(t[:12], buf(2.0))      # B registers only boundary 12
    unrelated = np.full((8,), 50, np.int32)
    assert pc.insert(unrelated, buf(3.0))   # overflow -> evicts A (LRU)
    assert pc.evictions == 1
    entry, L = pc.match(t)                  # boundaries 4/8 survived via B
    assert L == 8 and entry.length == 12
    _, L1 = pc.match(t[:6])
    assert L1 == 4


# ------------------------------------------------- chunked prefill parity


@pytest.mark.slow
def test_chunked_prefill_greedy_parity_and_trace_counts(tiny):
    """Prompts spanning several chunks (and the sub-chunk short case)
    match generate() bit-for-bit; chunk-prefill traces are bounded by
    distinct chunk buckets (one here: everything pads to the 8 bucket)
    and nothing retraces on repeats.  Slow: multi-chunk prefill
    compile + trace assertions;
    test_chunk_budget_bounds_tick_prefill and the prefix-reuse parity
    tests keep fast chunked-prefill coverage."""
    _, model, variables = tiny
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(20 + i), (L,), 0, 61), np.int32)
        for i, L in enumerate([5, 20, 33])]
    base = [_gen(model, variables, p) for p in prompts]
    eng = ServingEngine(model, variables, n_slots=3, max_seq=64,
                        temperature=0.0, chunk=8, min_prefill_bucket=8,
                        metrics=ServeMetrics())
    reqs = [eng.submit(p, M) for p in prompts]
    eng.drain(timeout=120)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(r.result(), b)
    counts = eng.compile_counts()
    assert counts["decode"] == 1
    assert counts["chunk"] == counts["chunk_buckets"] == 1
    assert counts["prefill"] == 0  # chunked engines never take the
    # whole-prompt path
    # steady state: same shapes -> zero new traces
    r = eng.submit(prompts[2], M)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(r.result(), base[2])
    assert eng.compile_counts() == counts


def test_chunk_budget_bounds_tick_prefill(tiny):
    """The acceptance bound: with chunking on, no tick's prefill work
    exceeds the credit budget — a max-length prompt spreads over ticks
    while an already-decoding request keeps emitting every tick."""
    _, model, variables = tiny
    short = np.asarray(jax.random.randint(
        jax.random.PRNGKey(30), (5,), 0, 61), np.int32)
    longp = np.asarray(jax.random.randint(
        jax.random.PRNGKey(31), (62,), 0, 61), np.int32)  # max_seq - 2
    b_short = _gen(model, variables, short, )
    base_long = np.asarray(generate(model, variables, longp[None], 2,
                                    temperature=0.0)["tokens"])[0]
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        temperature=0.0, chunk=8, min_prefill_bucket=8,
                        metrics=ServeMetrics())
    r0 = eng.submit(short, M)
    s = eng.step()
    assert s["prefill_tokens"] <= 8
    r1 = eng.submit(longp, 2)
    ticks = 0
    while not r1.done:
        st = eng.step()
        ticks += 1
        assert st["prefill_tokens"] <= 8, st
        if not r0.done:
            # decode never stalls behind the long prefill
            assert st["emitted"] >= 1, st
        assert ticks < 64, "long prompt failed to finish prefilling"
    assert ticks >= 62 // 8  # the prefill really was spread out
    eng.drain(timeout=120)
    np.testing.assert_array_equal(r0.result(), b_short)
    np.testing.assert_array_equal(r1.result(), base_long)


# ------------------------------------------------------ prefix cache reuse


def test_prefix_reuse_bit_exact_greedy(tiny, shared_prompts):
    """Requests sharing a cached prefix reproduce generate() exactly
    (cache-on == cache-off == generate, the acceptance criterion), the
    hit skips the shared tokens' prefill, and the copy/extract
    programs trace exactly once."""
    _, model, variables = tiny
    base = [_gen(model, variables, p) for p in shared_prompts]
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        temperature=0.0, chunk=8, prefix_cache=True,
                        prefix_block=8, metrics=ServeMetrics())
    results = []
    for p in shared_prompts:  # sequential: later submits see the cache
        r = eng.submit(p, M)
        eng.drain(timeout=120)
        results.append(r)
    for r, b in zip(results, base):
        np.testing.assert_array_equal(r.result(), b)
    # prompt 0 missed+inserted; 1 hit 32 shared tokens; 2 (the exact
    # prefix) hit capped at T-1 -> 24; 3 missed (unrelated)
    assert eng.metrics.get(sm.PREFIX_HITS) == 2
    assert eng.metrics.get(sm.PREFIX_HIT_TOKENS) == 32 + 24
    assert eng.metrics.get(sm.PREFIX_MISSES) == 2
    assert eng.prefix.stats()["insertions"] >= 1
    counts = eng.compile_counts()
    assert counts["decode"] == 1
    assert counts["prefix_copy"] == 1 and counts["prefix_extract"] == 1
    assert counts["chunk"] == counts["chunk_buckets"]
    # prefill work actually skipped: the hit requests computed fewer
    # padded prefill tokens than their prompts
    assert eng.metrics.get(sm.PREFILL_TOKENS) < sum(
        len(p) + 8 for p in shared_prompts)


def test_prefix_reuse_bit_exact_seeded_sampling(tiny, shared_prompts):
    """The key-chain replay survives prefix reuse: the final chunk (and
    only it) splits the request's PRNGKey, so a cache hit cannot shift
    the sampled trajectory."""
    _, model, variables = tiny
    p0, p1 = shared_prompts[0], shared_prompts[1]
    base = _gen(model, variables, p1, temperature=0.8, top_k=20,
                rng=jax.random.PRNGKey(142))
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.8, top_k=20, chunk=8,
                        prefix_cache=True, prefix_block=8,
                        metrics=ServeMetrics())
    eng.submit(p0, M, seed=7)
    eng.drain(timeout=120)  # seeds the cache
    r = eng.submit(p1, M, seed=142)
    eng.drain(timeout=120)
    assert eng.metrics.get(sm.PREFIX_HITS) == 1
    np.testing.assert_array_equal(r.result(), base)


def test_prefix_cache_budget_zero_disables_reuse_correctly(tiny,
                                                           shared_prompts):
    """A byte budget too small for one entry refuses every insert: all
    lookups miss, nothing breaks, outputs stay exact."""
    _, model, variables = tiny
    p = shared_prompts[0]
    base = _gen(model, variables, p)
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.0, chunk=8, prefix_cache=True,
                        prefix_block=8, prefix_bytes=64,
                        metrics=ServeMetrics())
    for _ in range(2):
        r = eng.submit(p, M)
        eng.drain(timeout=120)
        np.testing.assert_array_equal(r.result(), base)
    assert eng.metrics.get(sm.PREFIX_HITS) == 0
    assert eng.prefix.stats()["entries"] == 0


def test_prefix_hit_without_chunking_splits_instead_of_refeeding(tiny):
    """chunk=0 + a hit whose covering bucket would overrun the row:
    the continuation must SPLIT into fitting buckets at the boundary,
    not shift left over the copied prefix — otherwise the hit costs as
    much prefill as a miss.  Geometry: S=64, p0=16, T=50 -> covering
    bucket 64 overruns; split = 32 at p0 + 8 tail = 40 padded tokens
    (vs 64 for the miss), still token-identical to generate()."""
    _, model, variables = tiny
    shared = np.asarray(jax.random.randint(
        jax.random.PRNGKey(90), (16,), 0, 61), np.int32)
    warm = np.concatenate([shared, np.asarray(jax.random.randint(
        jax.random.PRNGKey(91), (34,), 0, 61), np.int32)])
    probe = np.concatenate([shared, np.asarray(jax.random.randint(
        jax.random.PRNGKey(92), (34,), 0, 61), np.int32)])
    base = _gen(model, variables, probe)
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.0, chunk=0, prefix_cache=True,
                        prefix_block=8, metrics=ServeMetrics())
    eng.submit(warm, M)
    eng.drain(timeout=120)  # miss: whole-prompt 64-bucket, seeds cache
    before = eng.metrics.get(sm.PREFILL_TOKENS)
    r = eng.submit(probe, M)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(r.result(), base)
    assert eng.metrics.get(sm.PREFIX_HITS) == 1
    assert eng.metrics.get(sm.PREFIX_HIT_TOKENS) == 16
    # the split keeps the reuse real: 32 + 8 padded tokens, not a
    # full-row 64-token refeed
    assert eng.metrics.get(sm.PREFILL_TOKENS) - before == 40


def test_tiny_credit_budget_cannot_stall_prefix_resume(tiny):
    """A continuation bucket larger than the WHOLE per-tick credit
    budget must clamp its debit (the admission-grant rule) rather than
    wait for credits that can never accrue — regression for a permanent
    PREFILLING hang with chunk=0 + a prefix hit + prefill_credits
    smaller than the minimum bucket."""
    _, model, variables = tiny
    shared = np.asarray(jax.random.randint(
        jax.random.PRNGKey(93), (16,), 0, 61), np.int32)
    warm = np.concatenate([shared, np.asarray(jax.random.randint(
        jax.random.PRNGKey(94), (34,), 0, 61), np.int32)])
    probe = np.concatenate([shared, np.asarray(jax.random.randint(
        jax.random.PRNGKey(95), (34,), 0, 61), np.int32)])
    base = _gen(model, variables, probe)
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.0, chunk=0, prefix_cache=True,
                        prefix_block=8, prefill_credits=4,
                        metrics=ServeMetrics())
    eng.submit(warm, M)
    eng.drain(timeout=120)
    r = eng.submit(probe, M)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(r.result(), base)
    assert eng.metrics.get(sm.PREFIX_HITS) == 1


@pytest.mark.slow
def test_shared_store_isolates_different_weights(tiny, shared_prompts):
    """Slow: a second model init + its prefill compiles;
    test_prefix_cache_store_mechanics keeps the fast
    store-keying coverage.
    Two engines serving DIFFERENT weights through one shared
    PrefixCache must never exchange K/V: the weights-fingerprint salt
    keys their prefixes apart, so engine B misses on the prompt engine
    A cached (and still matches its own generate() exactly), while a
    same-weights engine C does hit A's entry."""
    _, model, variables = tiny
    variables_b = model.init(jax.random.PRNGKey(99),
                             jnp.zeros((1, 8), jnp.int32))
    p = shared_prompts[0]
    store = PrefixCache(block=8)
    eng_a = ServingEngine(model, variables, n_slots=1, max_seq=64,
                          temperature=0.0, chunk=8, prefix_cache=store,
                          metrics=ServeMetrics())
    eng_a.submit(p, M)
    eng_a.drain(timeout=120)
    assert store.stats()["entries"] == 1
    base_b = _gen(model, variables_b, p)
    eng_b = ServingEngine(model, variables_b, n_slots=1, max_seq=64,
                          temperature=0.0, chunk=8, prefix_cache=store,
                          metrics=ServeMetrics())
    r = eng_b.submit(p, M)
    eng_b.drain(timeout=120)
    np.testing.assert_array_equal(r.result(), base_b)
    assert eng_b.metrics.get(sm.PREFIX_HITS) == 0
    assert eng_b.metrics.get(sm.PREFIX_MISSES) == 1
    # B's own prefill lands as a second, salt-separate entry
    assert store.stats()["entries"] == 2
    eng_c = ServingEngine(model, variables, n_slots=1, max_seq=64,
                          temperature=0.0, chunk=8, prefix_cache=store,
                          metrics=ServeMetrics())
    r = eng_c.submit(p, M)
    eng_c.drain(timeout=120)
    np.testing.assert_array_equal(r.result(),
                                  _gen(model, variables, p))
    assert eng_c.metrics.get(sm.PREFIX_HITS) == 1
    # same weights but different row geometry (max_seq): the salt's
    # geometry digest turns what would be an incompatible-shape copy
    # (an engine-fatal tick crash) into a harmless miss
    eng_d = ServingEngine(model, variables, n_slots=1, max_seq=48,
                          temperature=0.0, chunk=8, prefix_cache=store,
                          metrics=ServeMetrics())
    r = eng_d.submit(p, M)
    eng_d.drain(timeout=120)
    np.testing.assert_array_equal(r.result(),
                                  _gen(model, variables, p))
    assert eng_d.metrics.get(sm.PREFIX_HITS) == 0


# ---------------------------------------------------- cancellation paths


def test_cancel_mid_prefill_frees_slot(tiny):
    _, model, variables = tiny
    longp = np.asarray(jax.random.randint(
        jax.random.PRNGKey(33), (40,), 0, 61), np.int32)
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.0, chunk=8,
                        metrics=ServeMetrics())
    r = eng.submit(longp, 4)
    eng.step()
    assert r.state is RequestState.PREFILLING
    eng.cancel(r)
    eng.step()
    assert r.done and r.state is RequestState.CANCELLED
    assert not r.tokens  # never reached its first token
    assert eng.pool.free_count == 1
    assert eng.scheduler.credits == eng.scheduler.credit_budget


def test_kv_quant_refuses_chunking_and_prefix_cache(tiny):
    """A chunk (or a prefix-resumed prefill) attends at a traced
    position and reads already-quantized int8 K/V, where whole-prompt
    prefill at static pos=0 reads the pre-quantization values — the
    combination would silently break the parity contract, so the
    engine must refuse it loudly.  Plain kv_quant (chunk=0, no prefix
    store) stays constructible."""
    _, model, variables = tiny
    with pytest.raises(ValueError, match="dense KV cache"):
        ServingEngine(model, variables, n_slots=2, max_seq=32,
                      kv_quant=True, chunk=8)
    with pytest.raises(ValueError, match="dense KV cache"):
        ServingEngine(model, variables, n_slots=2, max_seq=32,
                      kv_quant=True, prefix_cache=True)
    eng = ServingEngine(model, variables, n_slots=2, max_seq=32,
                        kv_quant=True)
    assert eng.chunk == 0 and eng.prefix is None


def test_flash_prefill_refuses_chunking_when_bucket_can_go_flash(tiny):
    """Same hazard class via the attention implementation: a flash
    model's whole-prompt prefill can take the Pallas kernel (bucket
    gcd gate needs >= 128) while chunks always take dense cached
    attention — different accumulation order, silent ulp divergence.
    Refused only when a flash-eligible bucket is reachable
    (max_seq >= 128); tiny flash configs stay constructible."""
    cfg = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=256,
                            attn_impl="flash", dtype=jnp.float32)
    model = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, 61)
    variables = model.init(jax.random.PRNGKey(1), toks)
    with pytest.raises(ValueError, match="dense "):
        ServingEngine(model, variables, n_slots=2, max_seq=256, chunk=8)
    with pytest.raises(ValueError, match="dense "):
        ServingEngine(model, variables, n_slots=2, max_seq=256,
                      prefix_cache=True)
    # no bucket below 128 can pass the gcd gate: allowed
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64, chunk=8)
    assert eng.chunk == 8
