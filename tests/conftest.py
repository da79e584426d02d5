"""Test harness: fake an 8-device mesh on CPU.

The reference has no multi-node test harness at all (SURVEY.md §4) — its
closest analog is ``BYTEPS_FORCE_DISTRIBUTED=1``.  We do what the survey
prescribes: run every test on a virtual 8-device CPU platform so collective
numerics and sharding are exercised without TPU hardware.

The environment variables are for the child processes tests spawn
(launcher roles, chaos scripts, benches); this process is configured
through ``jax.config`` before any backend is initialized.
"""

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The compile cache goes OUTSIDE the checkout (common/compile_cache.py
# would otherwise place it at <checkout>/.jax_cache, inside the tree the
# chip tool copies), into a directory made fresh per session so no test
# outcome depends on what an earlier session compiled.
_cache_dir = tempfile.mkdtemp(prefix="byteps_tpu_test_jax_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test gets a pristine byteps_tpu global state."""
    yield
    try:
        import byteps_tpu

        byteps_tpu.shutdown()
    except Exception:
        pass


@pytest.fixture
def devices():
    return jax.devices()


# --------------------------------------------------------------------------
# Tier-1 duration budget guard (docs/wire.md, ROADMAP "tier-1 budget"):
# the fast suite lives inside a hard 870 s timeout with thin headroom, and
# that headroom historically eroded one slow test at a time.  On budgeted
# runs (the tier-1 invocation, `-m 'not slow'`) any non-slow test whose
# CALL phase exceeds the budget FAILS with an actionable message — the
# in-run equivalent of parsing the `--durations` report after the fact,
# with blame attached to the exact offender.  Full/slow runs (no
# `not slow` markexpr) are never budgeted.  Override (e.g. for a known
# throttled host): BYTEPS_TEST_DURATION_BUDGET_S, 0 disables.
# --------------------------------------------------------------------------

_DURATION_BUDGET_S = float(
    os.environ.get("BYTEPS_TEST_DURATION_BUDGET_S", "20"))


def _duration_budget_active(config) -> bool:
    return (_DURATION_BUDGET_S > 0
            and "not slow" in (getattr(config.option, "markexpr", "") or ""))


def duration_budget_verdict(duration_s: float, budget_s: float):
    """None when within budget, else the failure message (split out so
    the guard logic itself is unit-testable)."""
    if duration_s <= budget_s:
        return None
    return (f"tier-1 duration budget exceeded: call took {duration_s:.1f}s "
            f"> {budget_s:.0f}s. slow-mark this test (keeping a fast "
            f"variant) or split it — the fast suite must fit the 870s "
            f"tier-1 timeout (ROADMAP.md). Budget knob: "
            f"BYTEPS_TEST_DURATION_BUDGET_S.")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if (report.when == "call" and report.passed
            and _duration_budget_active(item.config)
            and item.get_closest_marker("slow") is None):
        msg = duration_budget_verdict(call.duration, _DURATION_BUDGET_S)
        if msg is not None:
            report.outcome = "failed"
            report.longrepr = f"{item.nodeid}: {msg}"
