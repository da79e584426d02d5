"""CI wiring for scripts/router_chaos.py.

The chaos proof (ISSUE 11 acceptance): N in-process replicas behind
the router with a fault-injecting proxy on every replica leg, a
deterministic mid-stream replica kill, and a drain leg — every
in-flight request either completes token-identical to a single-engine
``generate()`` reference (greedy AND seeded) or fails with a typed
error within its deadline; zero hangs, zero silent drops; the drain
leg sees zero client-visible errors.

All ``slow``-marked; the fast deterministic single-failover sibling
lives in tier-1 (tests/test_serving_router.py).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "scripts"))


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_router_chaos_kill_and_drain(temperature):
    """Mid-stream replica kill at a nonzero proxy fault rate: the
    victim's spliced stream is token-identical (failover +
    deterministic re-dispatch fired), background traffic completes or
    fails typed within its deadline, the drain leg retires a survivor
    with zero errors."""
    import router_chaos

    stats = router_chaos.run(requests=12, seed=0, temperature=temperature,
                             fault_rate=0.12, verbose=False)
    # run() already asserts the acceptance contract; pin the headline
    # numbers here so a silent weakening of run() cannot pass
    assert stats["mismatches"] == 0
    assert stats["untyped_failures"] == 0
    assert stats["hangs"] == 0
    assert stats["completed"] + stats["typed_failures"] == 12
    assert stats["killed_replica"] is not None
    assert stats["redispatches"] >= 1
    assert stats["drain_ok"] is True


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_router_chaos_kill_active_router(temperature):
    """The router-HA chaos leg (ISSUE 14 acceptance): 2 routers + 3
    replicas, a deterministic mid-stream kill of the ACTIVE router —
    every request token-identical to the single-router run (greedy AND
    seeded) or typed within deadline, zero hangs, and the dead epoch's
    late dispatch is refused by every replica (epoch fencing)."""
    import router_chaos

    stats = router_chaos.run_router_kill(
        requests=10, seed=0, temperature=temperature, kill_at=3,
        verbose=False)
    # run_router_kill() already asserts the contract; pin the headline
    # numbers so a silent weakening cannot pass
    assert stats["mismatches"] == 0
    assert stats["untyped_failures"] == 0
    assert stats["hangs"] == 0
    assert stats["completed"] + stats["typed_failures"] == 10
    assert stats["standby_active"] and stats["takeovers"] == 1
    assert stats["new_epoch"] > stats["old_epoch"]
    assert stats["fenced_replicas"] == 3


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_router_chaos_kill_prefill_mid_ship(temperature):
    """The disaggregation chaos leg (ISSUE 17 acceptance): the
    prefill-role replica is hard-killed after EXACTLY N shipped KV
    blocks of the victim's prefill.  The decode replica must never
    attend the torn ship: the victim completes token-identically via
    the decode-side re-prefill fallback (greedy AND seeded), follow-up
    traffic keeps completing on the survivor, zero hangs."""
    import router_chaos

    stats = router_chaos.run_prefill_kill(
        requests=8, seed=0, temperature=temperature, kill_blocks=2,
        verbose=False)
    # run_prefill_kill() already asserts the contract; pin the
    # headline numbers so a silent weakening cannot pass
    assert stats["mismatches"] == 0
    assert stats["untyped_failures"] == 0
    assert stats["hangs"] == 0
    assert stats["completed"] == 8
    assert stats["shipped_before_kill"] == 2
    assert stats["disagg_fallbacks"] >= 1


@pytest.mark.slow
def test_router_chaos_load_spike():
    """The elastic-capacity chaos leg (ISSUE 18 acceptance): a 1x ->
    4x -> 1x load wave against a live autoscaling controller — the
    tier grows under the spike and drains back to one replica, every
    guaranteed request completes token-identical (never shed), every
    best-effort request completes or sheds typed, zero hangs.  The
    fast deterministic sibling (the same ScalePolicy on scripted
    traces, zero sleeps) lives in tests/test_autoscale.py."""
    import router_chaos

    stats = router_chaos.run_load_spike(seed=0, verbose=False)
    # run_load_spike() already asserts the contract; pin the headline
    # numbers here so a silent weakening cannot pass
    assert stats["mismatches"] == 0
    assert stats["untyped_failures"] == 0
    assert stats["hangs"] == 0
    assert stats["shed_guaranteed"] == 0
    assert stats["scale_ups"] >= 1 and stats["scale_downs"] >= 1
    assert stats["spike_replicas"] > 1
    assert stats["final_replicas"] == 1
    assert (stats["best_effort_ok"] + stats["best_effort_shed"]
            + stats["guaranteed_ok"] == stats["requests"])
