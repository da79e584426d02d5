"""CI wiring for scripts/serve_smoke.py: randomized-arrival continuous
batching must be token-identical to sequential ``generate()`` (greedy
and seeded sampling), with a retrace-free decode program.

Marked ``slow`` so tier-1 (-m 'not slow') stays fast; run explicitly
with ``pytest -m slow tests/test_serve_smoke.py``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "scripts"))


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_serve_smoke_randomized_arrival_parity(temperature):
    import serve_smoke

    stats = serve_smoke.run(requests=10, seed=0, n_slots=4,
                            temperature=temperature, verbose=False)
    assert stats["mismatches"] == 0
    # steady-state compile stability: one decode program, bounded
    # prefill buckets (power-of-two padding)
    assert stats["decode_traces"] == stats["decode_buckets"]
    assert stats["prefill_buckets"] <= 4
    assert stats["serve.requests_completed"] == 10


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_serve_smoke_prefix_share_parity(temperature):
    """Shared-prefix workload under randomized threaded arrivals with
    chunked prefill + the prefix cache on: token-identical to BOTH the
    sequential generate() baselines and a cache-off engine run (the
    bit-exactness acceptance criterion), with the cache actually
    hitting and the compiled-program counts pinned."""
    import serve_smoke

    stats = serve_smoke.run(requests=10, seed=0, n_slots=4,
                            temperature=temperature, verbose=False,
                            prefix_share=True)
    assert stats["mismatches"] == 0
    assert stats["decode_traces"] == stats["decode_buckets"]
    assert stats["chunk_buckets"] <= 1  # every chunk pads to one bucket
    assert stats["prefix_copy_traces"] <= 1
    assert stats["serve.prefix_hits"] > 0
    assert stats["serve.prefix_hit_tokens"] >= 8 * stats["serve.prefix_hits"]
    assert stats["serve.requests_completed"] == 10


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_serve_smoke_paged_parity(temperature):
    """Paged KV cache under randomized threaded arrivals on a
    deliberately tight block pool: lazy block grants, pressure
    eviction, and preempt/resume must all keep every request
    token-identical to sequential generate() — greedy and seeded."""
    import serve_smoke

    stats = serve_smoke.run(requests=10, seed=0, n_slots=4,
                            temperature=temperature, verbose=False,
                            paged=True)
    assert stats["mismatches"] == 0
    assert stats["decode_traces"] == stats["decode_buckets"]
    assert stats["serve.requests_completed"] == 10
    # zero-copy contract: no prefix copy/extract program exists
    assert stats["prefix_copy_traces"] == 0
    assert stats["prefix_extract_traces"] == 0
    # every block reclaimed at drain (only the null block is held)
    assert stats["block_stats"]["used"] == 1


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_serve_smoke_paged_prefix_share_parity(temperature):
    """Zero-copy prefix sharing on the paged engine under threaded
    arrivals: hits are refcount bumps (no copy program ever compiles),
    outputs token-identical to BOTH generate() and a dense cache-off
    engine run of the same jobs."""
    import serve_smoke

    stats = serve_smoke.run(requests=10, seed=0, n_slots=4,
                            temperature=temperature, verbose=False,
                            prefix_share=True, paged=True)
    assert stats["mismatches"] == 0
    assert stats["decode_traces"] == stats["decode_buckets"]
    assert stats["serve.prefix_hits"] > 0
    assert stats["prefix_copy_traces"] == 0
    assert stats["prefix_extract_traces"] == 0
    assert stats["serve.requests_completed"] == 10


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_serve_smoke_spec_parity(temperature):
    """Speculative decoding under randomized threaded arrivals: n-gram
    proposals + batched verify must keep every request token-identical
    to sequential generate() — greedy and seeded — with exactly one
    verify program per speculation-depth bucket (the compile-
    discipline acceptance criterion)."""
    import serve_smoke

    stats = serve_smoke.run(requests=10, seed=0, n_slots=4,
                            temperature=temperature, verbose=False,
                            spec=4)
    assert stats["mismatches"] == 0
    assert stats["decode_traces"] == stats["decode_buckets"]
    assert stats["verify_traces"] == stats["verify_buckets"]
    assert stats["serve.requests_completed"] == 10


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_serve_smoke_spec_paged_parity(temperature):
    """Speculation on the paged engine over a deliberately tight block
    pool: lazy span grants, per-position scatter, and preempt/resume
    firing between verify ticks must all keep bit-exact parity."""
    import serve_smoke

    stats = serve_smoke.run(requests=10, seed=0, n_slots=4,
                            temperature=temperature, verbose=False,
                            paged=True, spec=4)
    assert stats["mismatches"] == 0
    assert stats["decode_traces"] == stats["decode_buckets"]
    assert stats["verify_traces"] == stats["verify_buckets"]
    assert stats["serve.requests_completed"] == 10
    assert stats["block_stats"]["used"] == 1  # every block reclaimed


@pytest.mark.slow
def test_tcp_frontend_roundtrip_and_backpressure():
    """The launcher-facing TCP tier: concurrent RemoteServeClient
    connections batch into one engine and return exact generate()
    parity; a full admission queue surfaces the typed rejection as a
    status=1 reply without killing the connection."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.inference import generate
    from byteps_tpu.models.transformer import (Transformer,
                                               TransformerConfig)
    from byteps_tpu.serving import ServeMetrics, ServingEngine
    from byteps_tpu.serving.frontend import RemoteServeClient, serve

    cfg = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (5 + i,), 0, 61), np.int32)
        for i in range(3)]
    M = 6
    base = [np.asarray(generate(model, variables, p[None], M,
                                temperature=0.0)["tokens"])[0]
            for p in prompts]
    engine = ServingEngine(model, variables, n_slots=2, max_seq=64,
                           metrics=ServeMetrics())
    srv, _ = serve(engine, port=0, host="127.0.0.1", in_thread=True)
    addr = "127.0.0.1:%d" % srv.server_address[1]
    try:
        outs = [None] * 3

        def call(i):
            c = RemoteServeClient(addr)
            try:
                outs[i] = c.generate(prompts[i], M)
            finally:
                c.close()

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for got, want in zip(outs, base):
            np.testing.assert_array_equal(got, want)
        c = RemoteServeClient(addr)
        stats = c.stats()
        assert stats["serve.requests_completed"] == 3
        assert stats["compile_counts"]["decode"] == 1
        # the frontend advertises the colocated fast path (docs/wire.md
        # "Transports"): an auto-resolved client rides UDS into the
        # SAME engine with exact parity
        cu = RemoteServeClient(addr, transport="unix")
        assert cu.transport == "unix"
        np.testing.assert_array_equal(cu.generate(prompts[0], M), base[0])
        cu.close()
        # typed backpressure over the wire: stall admissions (stop the
        # tick thread), fill the queue, and the reply is a status=1
        # QueueFullError message on a connection that stays usable
        engine.stop()
        engine.scheduler.max_queue = 1
        c2 = RemoteServeClient(addr)
        done = threading.Event()

        def first():  # occupies the single queue slot (blocks)
            try:
                c2.generate(prompts[0], 2)
            except RuntimeError:
                pass
            finally:
                done.set()

        t = threading.Thread(target=first, daemon=True)
        t.start()
        import time

        for _ in range(100):  # wait for the first submit to enqueue
            if engine.scheduler.depth == 1:
                break
            time.sleep(0.02)
        try:
            c.generate(prompts[1], 2)
            assert False, "expected QueueFullError over the wire"
        except RuntimeError as e:
            assert "QueueFullError" in str(e)
        assert c.ping()  # connection survived the rejection
        engine.start()  # let the stalled request finish
        done.wait(60)
        c.close()
        c2.close()
    finally:
        srv.shutdown()
        srv.server_close()
