"""Test harness: fake an 8-device mesh on CPU.

The reference has no multi-node test harness at all (SURVEY.md §4) — its
closest analog is ``BYTEPS_FORCE_DISTRIBUTED=1``.  We do what the survey
prescribes: run every test on a virtual 8-device CPU platform so collective
numerics and sharding are exercised without TPU hardware.

The environment variables are for the child processes tests spawn
(launcher roles, chaos scripts); this process is configured
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

