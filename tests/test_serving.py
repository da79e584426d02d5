"""Continuous-batching serving engine (byteps_tpu/serving/).

The correctness anchor is deterministic parity: the engine serving N
concurrent requests must emit token-identical sequences to running the
same prompts sequentially through ``inference.generate()`` — greedy and
seeded-sampling both (docs/serving.md explains why the numerics are
bit-exact, not merely close).  The rest: slot-pool bookkeeping, credit
scheduling, typed backpressure, metrics on the Tracer timeline, and
compile-count stability (steady-state serving never retraces).

Engines and generate() baselines are module-scoped: jit compiles
dominate this file's cost, so tests share one greedy engine (built with
a one-bucket credit budget — admissions interleave one per tick, which
the credit test asserts and every other test simply rides through).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common.tracing import Tracer
from byteps_tpu.inference import generate
from byteps_tpu.models.transformer import Transformer, TransformerConfig
from byteps_tpu.serving import (
    QueueFullError,
    ServeClient,
    ServeMetrics,
    ServeScheduler,
    ServingEngine,
    SlotPool,
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
def greedy_eng(tiny):
    _, model, variables = tiny
    return ServingEngine(model, variables, n_slots=4, max_seq=64,
                         temperature=0.0, prefill_credits=8,
                         min_prefill_bucket=8, metrics=ServeMetrics())


# ----------------------------------------------------------------- slot pool


def test_slot_pool_assign_free_reset(tiny):
    cfg, _, _ = tiny
    pool = SlotPool(cfg, 3, 32)
    a = pool.assign(1, prompt_len=4)
    b = pool.assign(2, prompt_len=6)
    assert (a, b) == (0, 1)  # lowest-free-index, deterministic
    assert pool.pos[a] == 4 and pool.pos[b] == 6
    assert pool.active_count == 2 and pool.free_count == 1
    assert pool.advance(a) == 5
    pool.free(a)
    assert pool.request_ids[a] is None and pool.pos[a] == 0
    # freed slot is reused first (lowest index)
    assert pool.assign(3, prompt_len=2) == 0
    with pytest.raises(ValueError):
        pool.free(2)  # never assigned
    with pytest.raises(ValueError):
        pool.assign(4, prompt_len=32)  # prompt_len >= max_seq
    pool.pos[1] = 32
    with pytest.raises(RuntimeError):
        pool.advance(1)  # cursor overrun must raise, not clamp
    # cache pytree: [slots, max_seq, ...] per layer
    assert pool.caches[0]["k"].shape[:2] == (3, 32)
    assert len(pool.caches) == cfg.num_layers


# ----------------------------------------------------------------- scheduler


class _FakeReq:
    def __init__(self, rid, priority=0):
        self.id = rid
        self.priority = priority
        self.cancelled = False


def test_scheduler_credits_bound_admissions_per_tick():
    sched = ServeScheduler(max_queue=10, credit_budget=16)
    for i in range(3):
        sched.submit(_FakeReq(i), padded_len=8)
    granted = sched.admit(10)  # 16 credits / 8 tokens -> 2 grants
    assert [t.request.id for t in granted] == [0, 1]
    assert sched.admit(10) == []  # credits exhausted until finish
    for t in granted:
        sched.finish(t)
    assert [t.request.id for t in sched.admit(10)] == [2]


def test_scheduler_fifo_within_priority_and_priority_order():
    sched = ServeScheduler(max_queue=10, credit_budget=100)
    sched.submit(_FakeReq(0, priority=0), 4)
    sched.submit(_FakeReq(1, priority=5), 4)
    sched.submit(_FakeReq(2, priority=5), 4)
    sched.submit(_FakeReq(3, priority=0), 4)
    order = [t.request.id for t in sched.admit(10)]
    assert order == [1, 2, 0, 3]  # priority desc, FIFO within


def test_scheduler_bounded_queue_rejects_typed():
    sched = ServeScheduler(max_queue=2, credit_budget=64)
    sched.submit(_FakeReq(0), 4)
    sched.submit(_FakeReq(1), 4)
    with pytest.raises(QueueFullError) as ei:
        sched.submit(_FakeReq(2), 4)
    assert ei.value.depth == 2 and ei.value.bound == 2


def test_scheduler_oversized_task_clamped_to_budget():
    # a prompt longer than the whole budget must still be admittable:
    # its accounted length clamps to the budget (it then owns the tick)
    sched = ServeScheduler(max_queue=4, credit_budget=8)
    sched.submit(_FakeReq(0), 32)
    sched.submit(_FakeReq(1), 4)
    granted = sched.admit(10)
    assert [t.request.id for t in granted] == [0]  # big one owns the tick
    for t in granted:
        sched.finish(t)
    assert [t.request.id for t in sched.admit(10)] == [1]


def test_scheduler_grants_cancelled_for_engine_retirement():
    # cancellation is retired by the ENGINE (stream sentinel, metrics);
    # the queue hands the task out like any other grant
    sched = ServeScheduler(max_queue=4, credit_budget=64)
    r0, r1 = _FakeReq(0), _FakeReq(1)
    sched.submit(r0, 8)
    sched.submit(r1, 8)
    r0.cancelled = True
    granted = sched.admit(10)
    assert [t.request.id for t in granted] == [0, 1]
    for t in granted:
        sched.finish(t)
    assert sched.credits == 64


# ------------------------------------------------------------ engine parity


def test_credit_interleave_then_greedy_parity(tiny, prompts, greedy_base,
                                              greedy_eng):
    """One tick admits one bucket's worth of prefill (credit budget),
    decode interleaves every tick — and the final output of 4 concurrent
    requests is bit-identical to sequential generate() (the
    deterministic-mode acceptance criterion)."""
    eng = greedy_eng
    reqs = [eng.submit(p, M) for p in prompts]
    s1 = eng.step()
    assert s1["admitted"] == 1 and s1["active"] == 1
    s2 = eng.step()
    assert s2["admitted"] == 1 and s2["active"] == 2
    eng.drain(timeout=120)
    for r, b in zip(reqs, greedy_base):
        np.testing.assert_array_equal(r.result(), b)


def test_staggered_arrivals_and_compile_stability(tiny, prompts,
                                                  greedy_base, greedy_eng):
    """Requests admitted mid-flight (others already decoding) still match
    their sequential baselines — batch composition cannot leak — and the
    decode program never retraces after warmup.  The test warms the
    module-scoped engine itself: under ``--dist load`` no earlier test of
    this file need have run on this worker."""
    eng = greedy_eng
    warm = eng.submit(prompts[0], M)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(warm.result(), greedy_base[0])
    counts = eng.compile_counts()
    assert counts["decode"] == 1, counts
    r0 = eng.submit(prompts[0], M)
    eng.step()
    r1 = eng.submit(prompts[1], M)
    eng.step()
    r2 = eng.submit(prompts[2], M)
    eng.drain(timeout=120)
    for r, b in zip([r0, r1, r2], greedy_base):
        np.testing.assert_array_equal(r.result(), b)
    # same shapes -> zero new traces for decode OR prefill
    assert eng.compile_counts() == counts


@pytest.mark.slow
def test_sampled_parity_seeded(tiny, prompts):
    """Seeded sampling replays generate()'s exact key chain — identical
    draws even batched with other requests.  Slow-marked (PR 4 tier-1
    budget): it compiles its own sampled decode programs for a 3-slot
    pool; the fast 1-slot variant below keeps the key-chain replay
    pinned in tier-1."""
    _, model, variables = tiny
    base = [np.asarray(generate(
        model, variables, p[None], M, temperature=0.8, top_k=20,
        rng=jax.random.PRNGKey(100 + i))["tokens"])[0]
        for i, p in enumerate(prompts[:3])]
    eng = ServingEngine(model, variables, n_slots=3, max_seq=64,
                        temperature=0.8, top_k=20, metrics=ServeMetrics())
    reqs = [eng.submit(p, M, seed=100 + i)
            for i, p in enumerate(prompts[:3])]
    eng.drain(timeout=120)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(r.result(), b)


def test_sampled_parity_seeded_fast(tiny, prompts):
    """Fast tier-1 pin of the seeded key-chain replay: one slot, one
    request (the batched-with-other-requests case rides the slow
    3-slot variant above)."""
    _, model, variables = tiny
    p = prompts[0]
    base = np.asarray(generate(
        model, variables, p[None], M, temperature=0.8, top_k=20,
        rng=jax.random.PRNGKey(100))["tokens"])[0]
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.8, top_k=20, metrics=ServeMetrics())
    req = eng.submit(p, M, seed=100)
    eng.drain(timeout=120)
    np.testing.assert_array_equal(req.result(), base)


def test_eos_stops_early_and_frees_slot(tiny, prompts, greedy_base,
                                        greedy_eng):
    """A request whose sequence hits eos retires at the eos token and its
    slot frees.  Greedy trajectories are prefix-stable, so the expected
    output is the no-eos baseline truncated at the first eos."""
    _, model, variables = tiny
    full = greedy_base[0]
    eos = int(full[3])  # force an eos 4 tokens in
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        temperature=0.0, eos_id=eos,
                        metrics=ServeMetrics())
    req = eng.submit(prompts[0], M)
    eng.drain(timeout=120)
    got = req.result()
    np.testing.assert_array_equal(got, full[:4])
    assert got[-1] == eos and len(got) == 4
    assert eng.pool.free_count == 1
    # a 1-token budget retires at admission (prefill-only request)
    r1 = eng.submit(prompts[1], 1)
    eng.drain(timeout=60)
    assert len(r1.result()) == 1


# ------------------------------------------- backpressure, cancel, streaming


def test_admission_queue_full_typed_rejection(tiny, prompts):
    _, model, variables = tiny
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        max_queue=1, metrics=ServeMetrics())
    eng.submit(prompts[0], 2)  # queued; engine never stepped, no compile
    with pytest.raises(QueueFullError) as ei:
        eng.submit(prompts[0], 2)
    assert "queue full" in str(ei.value)
    assert eng.metrics.get(sm.REJECTED) == 1
    # infeasible requests are typed too
    with pytest.raises(ValueError):
        eng.submit(prompts[0], 100)  # prompt + budget > max_seq
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0,), np.int32), 2)
    # an engine whose max_seq exceeds the model's position table is
    # rejected at construction (init_cache's bound), never built
    with pytest.raises(ValueError, match="max_seq_len"):
        ServingEngine(model, variables, n_slots=1, max_seq=128)


def test_cancel_queued_and_active(tiny, prompts, greedy_eng):
    eng = greedy_eng
    cancelled_before = eng.metrics.get(sm.CANCELLED)
    r0 = eng.submit(prompts[0], 32)
    eng.step()  # r0 active
    r1 = eng.submit(prompts[1], 32)  # still queued (credits spent? no -
    # fresh tick) — cancel both before the next tick
    eng.cancel(r0)
    eng.cancel(r1)
    eng.drain(timeout=60)
    assert r0.state.value == "cancelled" and r1.state.value == "cancelled"
    assert eng.pool.free_count == eng.pool.n_slots
    assert eng.metrics.get(sm.CANCELLED) == cancelled_before + 2
    assert r0.tokens and not r1.tokens  # r0 got its prefill token, r1 none


def test_cancel_queued_drops_eagerly_without_a_tick(tiny, prompts):
    """Cancelling a still-QUEUED request removes it from the admission
    queue at cancel() time: queue depth frees immediately (no tick
    thread involved) and no grant is ever consumed by the corpse."""
    _, model, variables = tiny
    eng = ServingEngine(model, variables, n_slots=1, max_seq=64,
                        max_queue=2, metrics=ServeMetrics())
    r0 = eng.submit(prompts[0], 16)
    eng.step()  # r0 occupies the only slot
    r1 = eng.submit(prompts[1], 4)
    assert eng.scheduler.depth == 1
    eng.cancel(r1)
    # retired synchronously: done before any further tick runs
    assert r1.done and r1.state.value == "cancelled"
    assert eng.scheduler.depth == 0
    assert r1.result().size == 0
    assert eng.metrics.get(sm.CANCELLED) == 1
    # the freed depth is usable again, and granting skips nothing
    r2 = eng.submit(prompts[2], 2)
    eng.cancel(r0)
    eng.drain(timeout=120)
    assert r2.state.value == "done" and len(r2.result()) == 2
    # double-cancel of an already-finished request is a no-op
    eng.cancel(r1)
    assert eng.metrics.get(sm.CANCELLED) == 2  # r0 + r1, not r1 twice


def test_tick_failure_fails_requests_loudly(tiny, prompts):
    """A tick-thread exception must not look like a hang: the in-flight
    request, queued requests beyond the credit budget (which a
    credit-bounded drain would skip), and new submissions all surface
    the error instead of blocking forever."""
    _, model, variables = tiny
    eng = ServingEngine(model, variables, n_slots=2, max_seq=64,
                        prefill_credits=8, min_prefill_bucket=8,
                        metrics=ServeMetrics())

    def boom(bucket):
        raise RuntimeError("injected tick failure")

    eng._prefill_fn = boom  # fires inside the first admission
    reqs = [eng.submit(p, 4) for p in prompts[:3]]  # 1 admits, 2 queue
    eng.start()
    for req in reqs:
        with pytest.raises(RuntimeError, match="injected tick failure"):
            req.result(timeout=30)
        assert req.state.value == "failed"
        # streaming consumers see the failure too, not a clean short end
        with pytest.raises(RuntimeError, match="injected tick failure"):
            list(req)
    assert eng.metrics.get(sm.FAILED) == 3
    assert eng.scheduler.depth == 0
    with pytest.raises(RuntimeError, match="engine is dead"):
        eng.submit(prompts[0], 4)
    eng.drain(timeout=10)  # outstanding counter fully reconciled
    eng.stop()


def test_streaming_iterator_and_concurrent_submitters(tiny, prompts,
                                                      greedy_base,
                                                      greedy_eng):
    """Background tick thread + racing submitters: streams deliver
    tokens incrementally and every request matches its baseline."""
    client = ServeClient(greedy_eng)  # starts the tick thread
    try:
        got = list(client.stream(prompts[0], M))
        np.testing.assert_array_equal(np.asarray(got, np.int32),
                                      greedy_base[0])
        out = [None] * len(prompts)

        def worker(i):
            out[i] = client.submit(prompts[i], M)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        client.drain(timeout=120)
        for i, r in enumerate(out):
            np.testing.assert_array_equal(r.result(), greedy_base[i])
    finally:
        greedy_eng.stop()


# ----------------------------------------------------------------- metrics


def test_metrics_on_tracer_timeline(tiny, prompts, tmp_path, greedy_eng):
    """Occupancy / queue-wait / TTFT / TPOT / token counters land as
    chrome-trace counter events on the Tracer (acceptance criterion)."""
    tracer = Tracer(path=str(tmp_path / "trace.json"))
    eng = greedy_eng
    old_metrics = eng.metrics
    eng.metrics = ServeMetrics(tracer=tracer)
    try:
        reqs = [eng.submit(p, M) for p in prompts[:2]]
        eng.drain(timeout=120)
        for r in reqs:
            r.result()
        counters = {e["name"] for e in tracer.events() if e["ph"] == "C"}
        for want in (sm.OCCUPANCY, sm.QUEUE_DEPTH, sm.TTFT_MS, sm.TPOT_MS,
                     sm.QUEUE_WAIT_MS, sm.TOKENS, sm.COMPLETED):
            assert want in counters, f"missing counter track {want}"
        summ = eng.metrics.summary()
        assert summ["ttft_n"] == 2
        assert summ["serve.tokens_generated"] == 2 * M
        assert summ["ttft_p50_s"] >= 0 and summ["tpot_p50_s"] >= 0
        # and the file is a loadable chrome trace
        tracer.flush()
        import json

        with open(tracer.path) as f:
            assert json.load(f)["traceEvents"]
    finally:
        eng.metrics = old_metrics
