"""Resilience subsystem tests: retry policy, fault-injecting proxy,
version-guarded idempotence, heartbeat failure detection, degraded-mode
failover and bit-for-bit recovery.

Every network fault here is injected deterministically through
``FaultInjectingProxy`` (resilience/chaos.py) — no real network failures,
no sleeps hoping a race resolves.
"""

import struct
import threading
import time

import numpy as np
import pytest

from byteps_tpu.common.config import Config, reset_config, set_config
from byteps_tpu.engine import ps_server
from byteps_tpu.engine.ps_server import OP_PING, RemoteStore, _decode, _encode
from byteps_tpu.resilience import (DegradedModeRouter, FailureDetector,
                                   FaultInjectingProxy, ResilienceCounters,
                                   RetryPolicy, reset_counters)
from byteps_tpu.resilience import counters as cn


@pytest.fixture(autouse=True)
def _fresh_resilience_state():
    reset_config()
    reset_counters()
    yield
    reset_config()
    reset_counters()


def _spawn_shard():
    srv, thread = ps_server.serve(0, host="127.0.0.1", use_native=False,
                                  in_thread=True)
    return srv, thread, f"127.0.0.1:{srv.server_address[1]}"


def _fast_policy(**kw):
    kw.setdefault("max_attempts", 4)
    kw.setdefault("backoff_base", 0.01)
    kw.setdefault("jitter", 0.0)
    kw.setdefault("deadline", 10.0)
    return RetryPolicy(**kw)


# ---------------------------------------------------------------- RetryPolicy


def test_retry_policy_backoff_schedule():
    p = RetryPolicy(max_attempts=4, backoff_base=0.1, backoff_mult=2.0,
                    jitter=0.0, backoff_cap=10.0, deadline=0.0)
    assert p.backoff(1) == 0.0
    assert p.backoff(2) == pytest.approx(0.1)
    assert p.backoff(3) == pytest.approx(0.2)
    assert p.backoff(4) == pytest.approx(0.4)
    # deadline 0 = unbounded; attempts still bound
    assert p.should_retry(3, p.start())
    assert not p.should_retry(4, p.start())


def test_retry_policy_jitter_bounded_and_seeded():
    import random

    p = RetryPolicy(backoff_base=1.0, backoff_mult=1.0, jitter=0.25,
                    backoff_cap=10.0)
    rng = random.Random(7)
    vals = [p.backoff(2, rng) for _ in range(50)]
    assert all(0.75 <= v <= 1.25 for v in vals)
    assert len(set(vals)) > 1  # actually randomized
    # same seed -> same schedule (determinism for chaos tests)
    rng2 = random.Random(7)
    assert vals == [p.backoff(2, rng2) for _ in range(50)]


def test_retry_policy_deadline_stops_retries():
    p = RetryPolicy(max_attempts=100, backoff_base=10.0, jitter=0.0,
                    deadline=0.5)
    # next backoff (10s) would overshoot the 0.5s deadline
    assert not p.should_retry(1, p.start())


def test_retry_policy_from_config():
    cfg = Config(retry_max_attempts=7, retry_backoff_ms=5.0,
                 retry_backoff_mult=3.0, retry_jitter=0.0,
                 retry_deadline_ms=1000.0)
    p = RetryPolicy.from_config(cfg)
    assert p.max_attempts == 7
    assert p.backoff_base == pytest.approx(0.005)
    assert p.backoff_mult == 3.0
    assert p.deadline == pytest.approx(1.0)


# ------------------------------------------------------------------- sharder


def test_sharder_remap_deterministic_next_alive():
    from byteps_tpu.common.context import ServerSharder

    assert ServerSharder.remap(1, {1}, 4) == 2
    assert ServerSharder.remap(3, {3, 0}, 4) == 1
    assert ServerSharder.remap(2, set(), 4) == 2
    with pytest.raises(RuntimeError):
        ServerSharder.remap(0, {0, 1}, 2)


def test_router_routes_around_down_shard_and_keeps_ledger():
    r = DegradedModeRouter(3, counters=ResilienceCounters())
    assert r.route(1) == 1
    assert r.mark_down(1)
    assert r.is_degraded()
    assert r.route(1) == 2
    assert r.route(0) == 0  # healthy shards unaffected
    r.note_failover("w", 1, 2)
    assert r.fallback_for("w") == 2
    assert r.failed_over_names(1) == [("w", 2)]
    assert r.mark_up(1)
    assert r.route(1) == 1
    # never excludes the last alive shard
    r2 = DegradedModeRouter(2, counters=ResilienceCounters())
    assert r2.mark_down(0)
    assert not r2.mark_down(1)
    assert r2.route(1) == 1


# ------------------------------------------------------------ chaos proxy


def test_proxy_passthrough_and_request_count():
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    try:
        store = RemoteStore([proxy.addr], retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(4, np.float32))
        out = store.push_pull("w", np.ones(4, np.float32))
        np.testing.assert_allclose(out, 1.0)
        np.testing.assert_allclose(store.pull("w"), 1.0)
        assert proxy.requests_seen >= 3
        assert proxy.faults_injected == 0
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_reconnect_after_poisoned_socket_drop():
    """The seed's only recovery behavior — drop the poisoned cached
    socket so the next RPC reconnects — exercised deterministically: a
    scripted connection reset kills the cached socket mid-RPC; with
    retries disabled the op raises, and the *next* op transparently
    reconnects and succeeds."""
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    counters = ResilienceCounters()
    try:
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy(max_attempts=1))
        store.init_tensor("w", np.zeros(2, np.float32))
        proxy.script("drop_before")
        with pytest.raises(OSError):
            store.pull("w")
        # poisoned socket was dropped -> this op opens a fresh connection
        np.testing.assert_allclose(store.pull("w"), 0.0)
        assert counters.get(cn.RECONNECT) >= 1
        assert counters.get(cn.GIVE_UP) == 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_retry_recovers_from_transient_resets():
    """drop_before faults are retried transparently: the op succeeds and
    is applied exactly once (the request never reached the server)."""
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    counters = ResilienceCounters()
    try:
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(4, np.float32))
        proxy.script("drop_before", "drop_before")  # two resets, then ok
        out = store.push_pull("w", np.ones(4, np.float32))
        np.testing.assert_allclose(out, 1.0)  # applied exactly once
        # (the version-guard probe between attempts consumes one of the
        # scripted faults, so the exact retry count varies — >=1 holds)
        assert counters.get(cn.RETRY) >= 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_garbled_reply_poisons_socket_and_retries():
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    counters = ResilienceCounters()
    try:
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(4, np.float32))
        proxy.script("garble_reply")
        np.testing.assert_allclose(store.pull("w"), 0.0)
        assert counters.get(cn.RETRY) >= 1
        assert counters.get(cn.RECONNECT) >= 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_delay_fault_passes_through():
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    try:
        store = RemoteStore([proxy.addr], retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(2, np.float32))
        proxy.script(("delay", 0.2))
        t0 = time.monotonic()
        np.testing.assert_allclose(store.pull("w"), 0.0)
        assert time.monotonic() - t0 >= 0.2
        assert proxy.faults_injected == 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


# ------------------------------------------------- version-guard idempotence


def test_retried_push_applied_exactly_once_under_connection_reset():
    """ISSUE acceptance: OP_PUSH whose reply is lost (applied server-side,
    connection reset before the status came back) must NOT be re-applied
    by the retry — the version guard (OP_VERSION vs the last acknowledged
    version) detects the landed mutation and suppresses the resend."""
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    counters = ResilienceCounters()
    try:
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(4, np.float32))
        # the ambiguous fault: push IS applied, reply discarded, reset
        proxy.script("drop_after")
        store.push_delta("w", np.ones(4, np.float32))
        np.testing.assert_allclose(store.pull("w"), 1.0)  # once, not twice
        assert counters.get(cn.DEDUP) == 1
        assert srv.store.version("w") == 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_retried_push_resent_when_request_was_lost():
    """The complementary case: reset BEFORE the server saw the push — the
    version did not advance, so the retry must resend (otherwise the
    update is lost)."""
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    counters = ResilienceCounters()
    try:
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(4, np.float32))
        proxy.script("drop_before")
        store.push_delta("w", np.ones(4, np.float32))
        np.testing.assert_allclose(store.pull("w"), 1.0)
        assert counters.get(cn.DEDUP) == 0  # guard saw v unchanged
        assert srv.store.version("w") == 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_retried_push_pull_exactly_once_with_result_recovery():
    """push_pull under drop_after: the add landed but its reply (the
    global tensor) was lost — the guard suppresses the resend and
    recovers the result with an idempotent pull."""
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    counters = ResilienceCounters()
    try:
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy())
        store.init_tensor("w", np.full(4, 10.0, np.float32))
        proxy.script("drop_after")
        out = store.push_pull("w", np.ones(4, np.float32))
        np.testing.assert_allclose(out, 11.0)  # 10 + 1, not 10 + 2
        assert counters.get(cn.DEDUP) == 1
        assert srv.store.version("w") == 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_version_guard_auto_disabled_for_multi_worker(monkeypatch):
    """With DMLC_NUM_WORKER > 1 the version counter cannot attribute an
    advance to OUR lost push, so the guard auto-disables: retries fall
    back to at-least-once resend (double-apply beats a silent drop);
    BYTEPS_RETRY_VERSION_GUARD=1 forces it back on."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "4")
    reset_config()
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    counters = ResilienceCounters()
    try:
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(4, np.float32))
        proxy.script("drop_after")
        store.push_delta("w", np.ones(4, np.float32))
        # applied + resent = at-least-once double-apply, no dedup
        np.testing.assert_allclose(store.pull("w"), 2.0)
        assert counters.get(cn.DEDUP) == 0
        store.close()

        # explicit override re-enables exactly-once on a fresh store
        monkeypatch.setenv("BYTEPS_RETRY_VERSION_GUARD", "1")
        reset_config()
        store = RemoteStore([proxy.addr], counters=counters,
                            retry_policy=_fast_policy())
        store.init_tensor("w2", np.zeros(4, np.float32))
        proxy.script("drop_after")
        store.push_delta("w2", np.ones(4, np.float32))
        np.testing.assert_allclose(store.pull("w2"), 1.0)
        assert counters.get(cn.DEDUP) == 1
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


# ----------------------------------------------------------- failure detector


def test_failure_detector_transitions_and_callbacks():
    health = {0: True, 1: True}
    downs, ups = [], []
    det = FailureDetector(
        2, lambda s: health[s], interval=0.02, miss_threshold=2,
        on_down=downs.append, on_up=ups.append,
        counters=ResilienceCounters())
    det.start()
    try:
        time.sleep(0.1)
        assert det.is_up(0) and det.is_up(1)
        health[1] = False
        # poll the CALLBACK list, not is_up(): the state flips inside
        # the lock before the callback fires outside it
        deadline = time.monotonic() + 10.0
        while not downs and time.monotonic() < deadline:
            time.sleep(0.01)
        assert downs == [1] and ups == []
        assert not det.is_up(1)
        health[1] = True
        deadline = time.monotonic() + 10.0
        while not ups and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ups == [1]
        assert det.is_up(1)
    finally:
        det.stop()


def test_report_failure_accelerates_detection():
    det = FailureDetector(1, lambda s: True, interval=60.0,
                          miss_threshold=3, counters=ResilienceCounters())
    # never started: report_failure alone trips the threshold
    det.report_failure(0)
    det.report_failure(0)
    assert det.is_up(0)
    det.report_failure(0)
    assert not det.is_up(0)
    det.report_success(0)
    assert det.is_up(0)


def test_deadline_bounds_op_against_hung_shard():
    """BYTEPS_RETRY_DEADLINE_MS must bound the whole op even when the
    shard HANGS (accepts, never answers): each attempt's socket timeout
    is clamped to the remaining deadline, so a 30s connection timeout
    cannot stall a 1s-deadline op for minutes."""
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    try:
        store = RemoteStore([proxy.addr],
                            retry_policy=_fast_policy(max_attempts=10,
                                                      backoff_base=0.01,
                                                      deadline=1.0),
                            timeout=30.0, counters=ResilienceCounters())
        store.init_tensor("w", np.zeros(4, np.float32))
        proxy.blackhole(True)
        t0 = time.monotonic()
        with pytest.raises(OSError):
            store.pull("w")
        assert time.monotonic() - t0 < 5.0  # not 30s-per-attempt
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


def test_heartbeat_detects_blackholed_shard():
    """A hung (blackholed) shard times out pings and is declared down."""
    cfg = Config(heartbeat_timeout_ms=200.0)
    set_config(cfg)
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    try:
        store = RemoteStore([proxy.addr], retry_policy=_fast_policy(),
                            counters=ResilienceCounters())
        assert store.ping_shard(0)
        proxy.blackhole(True)
        assert not store.ping_shard(0)
        proxy.blackhole(False)
        assert store.ping_shard(0)
        store.close()
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()


# ------------------------------------------------------- failover + recovery


def _targets(dim, names):
    return {n: (np.arange(dim, dtype=np.float32) if n in ("w", "c0")
                else np.full(dim, -3.0, np.float32)) for n in names}


def _train(store, steps, lr=0.1, dim=4, names=("w", "b")):
    """Deterministic single-worker SGD-ish loop over the PS store:
    every step push_pulls a fixed-form delta per tensor.  Returns the
    final pulled values."""
    target = _targets(dim, names)
    state = {n: np.zeros(dim, np.float32) for n in names}
    for n in names:
        store.init_tensor(n, state[n])
    for _ in range(steps):
        for n in names:
            delta = lr * (target[n] - state[n])
            state[n] = store.push_pull(n, delta.astype(np.float32))
    return state


@pytest.mark.slow
def test_shard_death_failover_restart_bitwise_recovery():
    """ISSUE acceptance: kill one of two shards mid-training; training
    continues in degraded mode (keys re-homed + re-initialized from
    worker state); the shard restarts (fresh store, same port); the
    heartbeat sees it, state migrates back; final pulled parameters are
    bit-for-bit identical to the no-fault run.

    Slow-marked: the full 30-step
    kill/degrade/restart/migrate cycle with heartbeat waits; the fast
    failover coverage stays in tier-1 via
    test_degraded_mode_routes_and_reinits_without_heartbeat,
    test_repeat_failover_overwrites_stale_fallback_copy,
    test_partition_recovery_overwrites_survivor_state and the wire
    pipeline's failover-seed fold tests."""
    dim, steps, kill_at, restart_at = 8, 30, 10, 20
    names = ("w", "b", "c0", "c1")

    target = _targets(dim, names)

    # --- reference run: two shards, no faults --------------------------
    s1, t1, a1 = _spawn_shard()
    s2, t2, a2 = _spawn_shard()
    ref_store = RemoteStore([a1, a2], retry_policy=_fast_policy())
    # sanity: the keyspace actually spans both shards (else the test
    # proves nothing about failover)
    assert {ref_store._shard_of(n) for n in names} == {0, 1}
    ref = _train(ref_store, steps, dim=dim, names=names)
    ref_store.close()
    s1.shutdown(); s1.server_close()
    s2.shutdown(); s2.server_close()

    # --- faulted run ---------------------------------------------------
    s1, t1, a1 = _spawn_shard()
    s2, t2, a2 = _spawn_shard()
    servers, addrs = [s1, s2], [a1, a2]
    counters = ResilienceCounters()
    store = RemoteStore(addrs, counters=counters,
                        retry_policy=_fast_policy(
                            max_attempts=2, backoff_base=0.01, deadline=5.0),
                        heartbeat=0.05)
    victim = store._shard_of("b")  # the shard serving "b" will die
    victim_port = int(addrs[victim].rsplit(":", 1)[1])

    state = {n: np.zeros(dim, np.float32) for n in names}
    for n in names:
        store.init_tensor(n, state[n])

    for step in range(steps):
        if step == kill_at:
            servers[victim].kill()  # crash: accept loop AND live conns die
        if step == restart_at:
            # fresh store on the SAME port (the launcher restart hook's
            # behavior): the client must re-init state on recovery
            servers[victim], _ = ps_server.serve(
                victim_port, host="127.0.0.1", use_native=False,
                in_thread=True)
            # wait for the heartbeat to notice and migrate back
            deadline = time.monotonic() + 10.0
            while store._router.is_down(victim) and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert not store._router.is_down(victim), \
                "heartbeat never saw the shard recover"
        for n in names:
            delta = 0.1 * (target[n] - state[n])
            state[n] = store.push_pull(n, delta.astype(np.float32))

    # degraded mode really happened and was repaired
    assert counters.get(cn.FAILOVER) >= 1
    assert counters.get(cn.REINIT) >= 1
    assert counters.get(cn.FAILBACK) >= 1

    # final pulled parameters: bit-for-bit vs the no-fault run
    for n in names:
        final = store.pull(n)
        np.testing.assert_array_equal(final, ref[n])
        assert final.tobytes() == ref[n].tobytes()

    store.close()
    for srv in servers:
        try:
            srv.shutdown(); srv.server_close()
        except Exception:
            pass


def test_degraded_mode_routes_and_reinits_without_heartbeat():
    """Failover driven purely by RPC failure (no heartbeat configured up
    front): the dead shard's key is re-homed to the surviving shard and
    re-initialized from the client's last seen global state."""
    s1, t1, a1 = _spawn_shard()
    s2, t2, a2 = _spawn_shard()
    counters = ResilienceCounters()
    store = RemoteStore([a1, a2], counters=counters,
                        retry_policy=_fast_policy(max_attempts=2,
                                                  deadline=5.0))
    try:
        names = ["w", "b", "c0", "c1"]
        for n in names:
            store.init_tensor(n, np.zeros(4, np.float32))
            store.push_pull(n, np.ones(4, np.float32))
        shards = {n: store._shard_of(n) for n in names}
        assert set(shards.values()) == {0, 1}
        victim = shards[names[0]]
        ((s1, s2)[victim]).kill()
        # ops on the dead shard's keys keep working, now on the fallback
        for n in names:
            out = store.push_pull(n, np.ones(4, np.float32))
            np.testing.assert_allclose(out, 2.0)  # state survived failover
        assert counters.get(cn.FAILOVER) >= 1
        assert counters.get(cn.REINIT) >= 1
        surviving = (s1, s2)[1 - victim]
        # the surviving server now hosts every name
        assert set(surviving.store.names()) == set(names)
        # client-side names(): down shard skipped, no duplicates
        assert sorted(store.names()) == sorted(names)
    finally:
        store.close()
        for srv in (s1, s2):
            try:
                srv.shutdown(); srv.server_close()
            except Exception:
                pass


def test_repeat_failover_overwrites_stale_fallback_copy():
    """A second failover episode must not be shadowed by the fallback's
    leftover copy from the first episode: the re-seed is a force-SET,
    not a first-push-wins INIT.  Updates made between failback and the
    second failure survive."""
    s1, t1, a1 = _spawn_shard()
    s2, t2, a2 = _spawn_shard()
    servers, addrs = [s1, s2], [a1, a2]
    store = RemoteStore(addrs, counters=ResilienceCounters(),
                        retry_policy=_fast_policy(max_attempts=2,
                                                  deadline=5.0),
                        heartbeat=0.05)
    victim = store._shard_of("b")
    victim_port = int(addrs[victim].rsplit(":", 1)[1])
    try:
        store.init_tensor("b", np.zeros(4, np.float32))

        # episode 1: kill, push +1 on the fallback (value 1 there)
        servers[victim].kill()
        np.testing.assert_allclose(
            store.push_pull("b", np.ones(4, np.float32)), 1.0)
        # restart -> failback seeds the fresh shard with 1
        servers[victim], _ = ps_server.serve(victim_port, host="127.0.0.1",
                                             use_native=False,
                                             in_thread=True)
        deadline = time.monotonic() + 10.0
        while store._router.is_down(victim) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not store._router.is_down(victim)
        # post-failback progress on the primary: 1 -> 3
        np.testing.assert_allclose(
            store.push_pull("b", np.full(4, 2.0, np.float32)), 3.0)

        # episode 2: kill again; the fallback still holds its stale 1 —
        # a first-push-wins seed would resume from 1 and lose the +2
        servers[victim].kill()
        out = store.push_pull("b", np.ones(4, np.float32))
        np.testing.assert_allclose(out, 4.0)  # 3 (re-seeded) + 1
    finally:
        store.close()
        for srv in servers:
            try:
                srv.shutdown(); srv.server_close()
            except Exception:
                pass


def test_single_shard_restart_reseeds_without_failover():
    """A 1-shard cluster (failover impossible) whose shard is restarted
    with a fresh store (launcher supervision) must keep training: the
    restarted shard's KeyError triggers a one-shot re-seed from the
    client's last-seen global state instead of killing the job."""
    srv, thread, addr = _spawn_shard()
    port = srv.server_address[1]
    counters = ResilienceCounters()
    store = RemoteStore([addr], counters=counters,
                        retry_policy=_fast_policy(max_attempts=3,
                                                  deadline=5.0))
    try:
        store.init_tensor("w", np.zeros(4, np.float32))
        np.testing.assert_allclose(
            store.push_pull("w", np.ones(4, np.float32)), 1.0)
        srv.kill()  # crash...
        srv, _ = ps_server.serve(port, host="127.0.0.1", use_native=False,
                                 in_thread=True)  # ...supervised restart
        # next op reconnects, hits the fresh store's KeyError, re-seeds
        # with the last-seen value (1.0) and applies the delta
        out = store.push_pull("w", np.full(4, 2.0, np.float32))
        np.testing.assert_allclose(out, 3.0)
        assert counters.get(cn.REINIT) >= 1
        # a genuinely never-declared name still errors loudly
        with pytest.raises(RuntimeError, match="ps_server error"):
            store.pull("never_declared")
    finally:
        store.close()
        try:
            srv.shutdown(); srv.server_close()
        except Exception:
            pass


def test_partition_recovery_overwrites_survivor_state():
    """A shard that was only unreachable (network partition — process
    alive, state intact) must not resume with its pre-partition values:
    failback force-SETs the fallback's newer state over the survivor's."""
    cfg = Config(heartbeat_timeout_ms=150.0)
    set_config(cfg)
    s1, t1, a1 = _spawn_shard()
    s2, t2, a2 = _spawn_shard()
    # front the would-be victim with a proxy so we can partition it
    # without killing it
    name = "b"
    proxies = [FaultInjectingProxy(a) for a in (a1, a2)]
    addrs = [p.addr for p in proxies]
    store = RemoteStore(addrs, counters=ResilienceCounters(),
                        retry_policy=_fast_policy(max_attempts=2,
                                                  backoff_base=0.01,
                                                  deadline=3.0),
                        timeout=0.5, heartbeat=0.05)
    victim = store._shard_of(name)
    victim_srv = (s1, s2)[victim]
    try:
        store.init_tensor(name, np.zeros(4, np.float32))
        np.testing.assert_allclose(
            store.push_pull(name, np.ones(4, np.float32)), 1.0)

        proxies[victim].blackhole(True)  # partition: alive but silent
        # degraded-mode progress on the fallback: 1 -> 4
        np.testing.assert_allclose(
            store.push_pull(name, np.full(4, 3.0, np.float32)), 4.0)
        assert store._router.is_down(victim)

        proxies[victim].blackhole(False)  # partition heals
        deadline = time.monotonic() + 10.0
        while store._router.is_down(victim) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not store._router.is_down(victim)
        # the survivor held 1; failback must have overwritten it with 4
        np.testing.assert_allclose(victim_srv.store.pull(name), 4.0)
        np.testing.assert_allclose(store.pull(name), 4.0)
    finally:
        store.close()
        for p in proxies:
            p.close()
        for srv in (s1, s2):
            try:
                srv.shutdown(); srv.server_close()
            except Exception:
                pass


def test_cascading_failover_reseeds_on_new_fallback():
    """When the fallback shard dies too, a previously re-homed key moves
    to the NEXT alive shard and is re-seeded there (the ledger check
    compares the ledgered fallback against current routing, not just
    'already failed over')."""
    servers, addrs = [], []
    for _ in range(3):
        srv, th, a = _spawn_shard()
        servers.append(srv)
        addrs.append(a)
    store = RemoteStore(addrs, counters=ResilienceCounters(),
                        retry_policy=_fast_policy(max_attempts=2,
                                                  deadline=5.0))
    name = "t0"  # placed on shard 1 (see name_key formula)
    try:
        primary = store._shard_of(name)
        assert primary == 1
        store.init_tensor(name, np.zeros(4, np.float32))
        np.testing.assert_allclose(
            store.push_pull(name, np.ones(4, np.float32)), 1.0)

        servers[primary].kill()  # first failover -> shard 2
        np.testing.assert_allclose(
            store.push_pull(name, np.ones(4, np.float32)), 2.0)
        fb1 = store._router.fallback_for(name)
        assert fb1 is not None and fb1 != primary

        servers[fb1].kill()  # cascading: the fallback dies too
        out = store.push_pull(name, np.ones(4, np.float32))
        np.testing.assert_allclose(out, 3.0)  # re-seeded with 2 on fb2
        fb2 = store._router.fallback_for(name)
        assert fb2 not in (primary, fb1)
    finally:
        store.close()
        for srv in servers:
            try:
                srv.shutdown(); srv.server_close()
            except Exception:
                pass


# ----------------------------------------------------------- tracer surfacing


def test_resilience_counters_reach_tracer(tmp_path, monkeypatch):
    """ISSUE acceptance: with BYTEPS_TRACE_PATH set, resilience events
    (retries at minimum; failovers/heartbeat misses in the faulted
    flows) appear in the Tracer output."""
    import json

    from byteps_tpu.common import tracing

    trace = tmp_path / "trace.json"
    monkeypatch.setenv("BYTEPS_TRACE_PATH", str(trace))
    reset_config()
    tracing.reset_tracer()
    srv, thread, addr = _spawn_shard()
    proxy = FaultInjectingProxy(addr)
    try:
        store = RemoteStore([proxy.addr], retry_policy=_fast_policy())
        store.init_tensor("w", np.zeros(4, np.float32))
        proxy.script("drop_before")
        store.push_pull("w", np.ones(4, np.float32))   # retried
        proxy.script("drop_after")
        store.push_delta("w", np.ones(4, np.float32))  # deduped
        store.close()
        tracing.get_tracer().flush()
        events = json.loads(trace.read_text())["traceEvents"]
        names = {e["name"] for e in events}
        assert cn.RETRY in names
        assert cn.DEDUP in names
        assert cn.RECONNECT in names
        # both surfacing shapes: instant events + counter track
        phs = {e["ph"] for e in events if e["name"] == cn.RETRY}
        assert {"i", "C"} <= phs
    finally:
        proxy.close()
        srv.shutdown(); srv.server_close()
        tracing.reset_tracer()


def test_profiler_record_after_close_drops_loudly():
    """Satellite: ServerProfiler.record() after close() must not buffer
    events nothing will drain — it drops them (debug-logged) and leaves
    the closed JSON file untouched and valid."""
    import json

    import byteps_tpu.common.logging as bps_log
    from byteps_tpu.engine.ps_server import OP_PUSH, ServerProfiler

    path = None
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        path = f.name
    prof = ServerProfiler(path)
    prof.record(OP_PUSH, "w", "peer", 0.0, 1.0)
    prof.close()
    before = open(path).read()
    json.loads(before)  # valid strict JSON after close
    prof.record(OP_PUSH, "w", "peer", 2.0, 3.0)  # must be dropped
    assert open(path).read() == before
    assert prof._events == []  # nothing buffered forever
    prof.close()  # idempotent, no corruption
    json.loads(open(path).read())


# ---------------------------------------------------------------- satellites


def test_flash_bwd_blocks_distinguish_explicit_choice():
    """Satellite: explicit block_q/block_k — including an explicit
    1024x1024 equal to the defaults — bind the backward as they bind the
    forward: one pair of blocks serves every kernel of a call."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.observability.metrics import get_registry
    from byteps_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                DEFAULT_BLOCK_Q,
                                                _fwd_blocks, flash_attention)

    assert _fwd_blocks(None, None) == (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    assert _fwd_blocks(1024, 1024) == (1024, 1024)
    assert _fwd_blocks(128, 256) == (128, 256)
    # one side explicit: the other resolves to its default
    assert _fwd_blocks(512, None) == (512, DEFAULT_BLOCK_K)

    # [2048 / 512, 2048 / 1024] grid blocks of 2 x 4 sub-tiles, forward
    # and backward alike
    x = jax.ShapeDtypeStruct((1, 2048, 1, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, False, None, 512, None, True).astype(jnp.float32)),
        (0, 1, 2)), x, x, x)
    reg = get_registry()
    for kernel in ("fwd", "bwd"):
        assert reg.get("flash.tiles_total", kernel=kernel,
                       window="none").value == 64


def test_flash_attention_none_defaults_still_run():
    import jax
    import jax.numpy as jnp

    from byteps_tpu.ops.flash_attention import flash_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
    out = flash_attention(q, k, v, True)
    ref = flash_attention(q, k, v, True, None, 1024, 1024)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_init_cache_flat_tp_refusal_narrowed():
    """Satellite: layout="flat" under an active tp axis that DIVIDES
    kv_heads now shards the head-major minor axis (whole KV-head
    slices); only a NON-dividing tp axis keeps the typed refusal, and
    its message names both honest ways out (grouped fallback, head
    padding) — tests/test_tp_serving.py pins the paged-pool twin."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from byteps_tpu.models.transformer import TransformerConfig, init_cache

    devs = np.array(jax.devices()[:2])
    mesh = Mesh(devs, ("tp",))
    cfg = TransformerConfig(vocab_size=32, num_layers=1, num_heads=4,
                            d_model=32, d_ff=64, max_seq_len=32,
                            num_kv_heads=2, dtype=jnp.float32, mesh=mesh)
    # tp=2 divides kv_heads=2: flat works and tp-shards the minor axis
    caches = init_cache(cfg, 2, 16, layout="flat")
    assert caches[0]["k"].ndim == 3
    assert caches[0]["k"].sharding.spec[2] == "tp"
    # grouped + auto still fine under the mesh
    caches = init_cache(cfg, 2, 16, layout="grouped")
    assert caches[0]["k"].ndim == 4
    init_cache(cfg, 2, 16, layout="auto")
    # tp=2 does NOT divide kv_heads=1 (MQA): typed refusal naming the
    # grouped-layout fallback and the padding option
    cfg1 = TransformerConfig(vocab_size=32, num_layers=1, num_heads=4,
                             d_model=32, d_ff=64, max_seq_len=32,
                             num_kv_heads=1, dtype=jnp.float32, mesh=mesh)
    with pytest.raises(ValueError, match="divide kv_heads") as ei:
        init_cache(cfg1, 2, 16, layout="flat")
    assert 'layout="grouped"' in str(ei.value)
    assert "pad kv_heads" in str(ei.value)
    # and flat stays available without a mesh
    cfg2 = TransformerConfig(vocab_size=32, num_layers=1, num_heads=4,
                             d_model=32, d_ff=64, max_seq_len=32,
                             num_kv_heads=2, dtype=jnp.float32)
    caches = init_cache(cfg2, 2, 16, layout="flat")
    assert caches[0]["k"].ndim == 3
