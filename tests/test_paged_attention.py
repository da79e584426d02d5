"""Fused paged-attention decode kernel (ops/paged_attention.py) and its
engine wiring (``ServingEngine(paged_kernel=...)``).

Two parity layers, both in interpret mode on CPU:

* **kernel vs gather** — the kernel consumes the block pool through the
  block table; the reference gathers the same table into a dense row
  and runs ``_cached_attention``.  The two compute the same softmax
  with different accumulation order (online chunked vs one dense pass),
  so values agree to float tolerance — pinned at 2e-5 absolute on f32 —
  and token decisions (greedy argmax, seeded sampling) are identical on
  every tested workload.  Dense-equivalent, ragged, and null-padded
  tables, GQA, windows, and every spec depth bucket are covered.
* **engine kernel-on vs kernel-off** — whole token streams must match,
  greedy AND seeded, including speculative verify and preempt/resume
  mid-stream, with ``compile_counts()`` pinned: the kernel path traces
  the decode program ONCE and one verify program per depth bucket.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    _cached_attention,
)
from byteps_tpu.ops.paged_attention import paged_decode_attention
from byteps_tpu.serving import ServeMetrics, ServingEngine
from byteps_tpu.serving import metrics as sm

TOL = 2e-5  # f32 dense-vs-online-softmax accumulation divergence


def _pool_and_tables(rng, B, pos, blk, mb, n_blocks, KVD,
                     dense_equivalent=False):
    """Random flat block pools + per-slot tables covering each slot's
    ``pos + spare`` span; remaining entries stay on the null block 0."""
    pk = jnp.asarray(rng.randn(n_blocks, blk, KVD), jnp.float32)
    pv = jnp.asarray(rng.randn(n_blocks, blk, KVD), jnp.float32)
    tables = np.zeros((B, mb), np.int32)
    nxt = iter(range(1, n_blocks))
    for b in range(B):
        need = mb if dense_equivalent else min(
            (int(pos[b]) + 2 + blk - 1) // blk + 1, mb)
        for j in range(need):
            tables[b, j] = next(nxt)
    return pk, pv, tables


def _reference(q, pk, pv, tables, pos, window=None):
    """Gather-path reference: dense row per slot + ``_cached_attention``
    (the ONE implementation the paged gather engine delegates to)."""
    B = q.shape[0]
    blk, KVD = pk.shape[1], pk.shape[2]
    D = q.shape[3]
    KV = KVD // D
    S = tables.shape[1] * blk
    outs = []
    for b in range(B):
        rk = pk[tables[b]].reshape(1, S, KV, D)
        rv = pv[tables[b]].reshape(1, S, KV, D)
        outs.append(_cached_attention(q[b:b + 1], rk, rv, int(pos[b]),
                                      window=window))
    return jnp.concatenate(outs, 0)


@pytest.mark.parametrize("tq", [1, 2, 5])
def test_kernel_matches_gather_ragged_and_null_tables(tq):
    """Ragged tables (each slot holds only its covering blocks, the
    tail null-padded), one slot at pos 0 with an ALL-null table (a
    masked/free slot's view), positions straddling block boundaries —
    kernel output matches the gathered dense reference at every query
    width, within the documented tolerance."""
    rng = np.random.RandomState(0)
    B, H, D, KV, blk, mb = 4, 4, 8, 2, 4, 8
    pos = np.array([0, 5, 12, 26], np.int32)
    pk, pv, tables = _pool_and_tables(rng, B, pos, blk, mb, 40, KV * D)
    tables[0, :] = 0  # slot 0: free/masked — reads only the null block
    q = jnp.asarray(rng.randn(B, tq, H, D), jnp.float32)
    out = paged_decode_attention(q, pk, pv, jnp.asarray(tables),
                                 jnp.asarray(pos), interpret=True)
    ref = _reference(q, pk, pv, tables, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=0)


def test_kernel_matches_gather_dense_equivalent_mha_and_window():
    """Fully-allocated (dense-equivalent) tables — the paged layout's
    degenerate case — under MHA and a sliding window."""
    rng = np.random.RandomState(1)
    B, H, D, KV, blk, mb = 2, 4, 8, 4, 4, 6
    pos = np.array([9, 21], np.int32)
    pk, pv, tables = _pool_and_tables(rng, B, pos, blk, mb, 32, KV * D,
                                      dense_equivalent=True)
    for tq in (1, 3):
        q = jnp.asarray(rng.randn(B, tq, H, D), jnp.float32)
        for window in (None, 6):
            out = paged_decode_attention(
                q, pk, pv, jnp.asarray(tables), jnp.asarray(pos),
                window=window, interpret=True)
            ref = _reference(q, pk, pv, tables, pos, window=window)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(ref),
                                       atol=TOL, rtol=0)


def test_kernel_rejects_bad_shapes():
    rng = np.random.RandomState(2)
    pk = jnp.asarray(rng.randn(4, 4, 16), jnp.float32)
    q = jnp.asarray(rng.randn(1, 1, 3, 8), jnp.float32)  # 16/8=2 kv, 3%2
    with pytest.raises(ValueError, match="dividing"):
        paged_decode_attention(q, pk, pk,
                               jnp.zeros((1, 2), jnp.int32),
                               jnp.zeros((1,), jnp.int32),
                               interpret=True)


# --------------------------------------------------------- engine wiring


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


def _run(model, variables, prompts, M, *, paged_kernel, temperature=0.0,
         seed0=0, **kw):
    eng = ServingEngine(model, variables,
                        n_slots=kw.pop("n_slots", len(prompts)),
                        max_seq=64, temperature=temperature,
                        top_k=20 if temperature else None,
                        paged=True, block=8, paged_kernel=paged_kernel,
                        metrics=ServeMetrics(), **kw)
    reqs = [eng.submit(p, M, seed=seed0 + i)
            for i, p in enumerate(prompts)]
    eng.drain(timeout=300)
    return [np.asarray(r.result()) for r in reqs], eng


def test_engine_kernel_on_vs_gather_token_parity(tiny, prompts):
    """The acceptance anchor: kernel-on decode emits token-identical
    streams to the gather path (greedy; seeded sibling below), and the
    kernel decode program traces exactly once (no gather-width buckets
    — the pos clamp lives inside the kernel)."""
    _, model, variables = tiny
    M = 8
    g_out, _ = _run(model, variables, prompts, M,
                    paged_kernel="off", seed0=3)
    k_out, eng = _run(model, variables, prompts, M,
                      paged_kernel="on", seed0=3)
    for a, b in zip(g_out, k_out):
        np.testing.assert_array_equal(a, b)
    counts = eng.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["decode_buckets"] == 1, counts
    # the fused path never gathers
    assert eng.metrics.get(sm.GATHERED_BLOCKS) == 0


@pytest.mark.slow
def test_engine_kernel_on_vs_gather_token_parity_seeded(tiny, prompts):
    """Seeded sibling of the kernel-vs-gather anchor: per-request key
    chains replay identically through the fused path."""
    _, model, variables = tiny
    M = 8
    g_out, _ = _run(model, variables, prompts, M,
                    paged_kernel="off", temperature=0.8, seed0=3)
    k_out, _ = _run(model, variables, prompts, M,
                    paged_kernel="on", temperature=0.8, seed0=3)
    for a, b in zip(g_out, k_out):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow  # ~19s: two engines + verify-bucket compiles; engine_kernel_on_vs_gather_token_parity keeps fast fused-kernel coverage, test_speculative keeps fast spec coverage
def test_engine_kernel_spec_verify_parity(tiny):
    """Speculative decoding rides the SAME kernel at k+1 query
    positions: spec-on kernel streams match spec-off kernel streams
    (and the gather engine's), with one verify program per depth
    bucket and proposals actually accepted."""
    _, model, variables = tiny
    # periodic prompts so the n-gram proposer fires
    props = [np.asarray(([1, 2, 3] * 4)[:10], np.int32),
             np.asarray(([7, 8] * 4)[:7], np.int32)]
    M = 12
    base, _ = _run(model, variables, props, M, paged_kernel="on")
    spec_out, eng = _run(model, variables, props, M,
                         paged_kernel="on", spec_k=4)
    for a, b in zip(base, spec_out):
        np.testing.assert_array_equal(a, b)
    counts = eng.compile_counts()
    assert counts["verify"] == counts["verify_buckets"] >= 1, counts
    assert counts["decode"] == counts["decode_buckets"] == 1, counts
    assert eng.metrics.get(sm.SPEC_ACCEPTED) > 0


@pytest.mark.slow
def test_engine_kernel_spec_verify_parity_seeded(tiny):
    """Seeded sibling of the spec parity test: kernel spec-on vs the
    gather spec engine under sampling (fast greedy coverage above)."""
    _, model, variables = tiny
    props = [np.asarray(([1, 2, 3] * 4)[:10], np.int32),
             np.asarray(([7, 8] * 4)[:7], np.int32)]
    M = 12
    g_out, _ = _run(model, variables, props, M, paged_kernel="off",
                    temperature=0.8, seed0=9, spec_k=4)
    k_out, _ = _run(model, variables, props, M, paged_kernel="on",
                    temperature=0.8, seed0=9, spec_k=4)
    for a, b in zip(g_out, k_out):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow  # ~19s: three engine runs; test_serving_paged preemption_under_block_pressure_greedy keeps fast preempt-resume coverage
def test_engine_kernel_preempt_resume_mid_stream(tiny):
    """Block pressure preempting a kernel-path request back to QUEUED
    and resuming it by re-prefill keeps the stream token-identical to
    an unpressured kernel run — the PR 9 resume argument holds on the
    fused path (prefill rebuilds the same K/V bytes; decode re-reads
    them through the same kernel)."""
    _, model, variables = tiny
    pA = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (19,), 0, 61), np.int32)
    pB = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (18,), 0, 61), np.int32)
    m = 30
    base, _ = _run(model, variables, [pA], m, paged_kernel="on",
                   n_slots=1)
    base_b, _ = _run(model, variables, [pB], m, paged_kernel="on",
                     n_slots=1)
    outs, eng = _run(model, variables, [pA, pB], m, paged_kernel="on",
                     n_slots=2, kv_blocks=9)
    np.testing.assert_array_equal(outs[0], base[0])
    np.testing.assert_array_equal(outs[1], base_b[0])
    assert eng.metrics.get(sm.PREEMPTIONS) >= 1


def test_engine_kernel_prefix_share_zero_copy(tiny):
    """Zero-copy prefix sharing composes with the kernel: a hit
    attaches the store's blocks to the new slot's table (refcount
    bumps) and the kernel reads the SHARED blocks in place — token
    streams match the gather engine's, no copy program exists, and
    nothing ever gathers."""
    _, model, variables = tiny
    shared = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (16,), 0, 61), np.int32)
    pA = np.concatenate([shared, np.asarray([3, 9, 4], np.int32)])
    pB = np.concatenate([shared, np.asarray([11, 2], np.int32)])
    M = 8
    outs = {}
    for mode in ("off", "on"):
        eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                            temperature=0.0, paged=True, block=8,
                            chunk=8, prefix_cache=True,
                            paged_kernel=mode, metrics=ServeMetrics())
        rA = eng.submit(pA, M)
        eng.drain(timeout=300)
        rB = eng.submit(pB, M)
        eng.step()
        assert eng.pool.alloc.shared_count() >= 2  # B adopted A's blocks
        eng.drain(timeout=300)
        outs[mode] = (np.asarray(rA.result()), np.asarray(rB.result()))
        counts = eng.compile_counts()
        assert counts["prefix_copy"] == 0 and counts["prefix_extract"] == 0
        assert eng.metrics.get(sm.PREFIX_HITS) == 1
        if mode == "on":
            assert eng.metrics.get(sm.GATHERED_BLOCKS) == 0
    np.testing.assert_array_equal(outs["off"][0], outs["on"][0])
    np.testing.assert_array_equal(outs["off"][1], outs["on"][1])


def test_engine_paged_kernel_validation(tiny):
    _, model, variables = tiny
    with pytest.raises(ValueError, match="paged_kernel"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      paged=True, block=8, paged_kernel="maybe",
                      metrics=ServeMetrics())
    # flat pool layout without the kernel would route flat rows into
    # the dense decode kernel under vmap — refused loudly
    with pytest.raises(ValueError, match="flat"):
        ServingEngine(model, variables, n_slots=1, max_seq=64,
                      paged=True, block=8, cache_layout="flat",
                      paged_kernel="off", metrics=ServeMetrics())
    # a dense engine ignores the knob entirely
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        paged_kernel="on", metrics=ServeMetrics())
    assert not eng.paged_kernel
