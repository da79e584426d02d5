"""Bring-up guards (PR 21): ``chip_smoke.py`` refuses to run without a
chip, the compile cache is placed from outside the program, importing
the package touches no backend, and the serve stats surface says what
actually ran.  Everything that needs a fresh interpreter (no backend
initialised, no conftest environment) runs as a subprocess.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines()
            if ln.startswith("{")]


def test_no_chip_invocation_fails_fast_and_names_the_tpu():
    """In a sandbox with no accelerator the smoke exits non-zero in
    seconds, names the missing TPU, and prints no verdict — the platform
    is pinned in code, so the inherited ``JAX_PLATFORMS=cpu`` cannot
    turn it into a CPU run."""
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, SMOKE], capture_output=True,
                       text=True, timeout=60, cwd=ROOT)
    assert time.monotonic() - t0 < 60
    assert p.returncode != 0
    assert "TPU" in p.stdout and "tpu" in p.stdout
    lines = _json_lines(p.stdout)
    assert lines and all(ln.get("ok") is not True for ln in lines)
    assert [ln["leg"] for ln in lines] == ["device"]  # nothing ran


def test_train_kernels_name_what_the_step_builds():
    """The flash kernels the train leg looks for among the compiled
    step's Mosaic calls are the ones a gradient at its shape builds."""
    import importlib.util
    import re

    from byteps_tpu.ops.flash_attention import flash_attention

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = smoke   # (its dataclasses look it up)
    try:
        spec.loader.exec_module(smoke)
    finally:
        del sys.modules["chip_smoke"]
    size = smoke.FULL
    x = jax.ShapeDtypeStruct(
        (size.train_batch, size.train_T, size.heads, size.d_head),
        jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, interpret=True).astype(jnp.float32)),
        (0, 1, 2)))(x, x, x))
    built = set(re.findall(r"flash_(?:fwd|bwd_\w+)", jaxpr))
    assert built == {k for k in smoke.TRAIN_KERNELS if k.startswith("flash")}


@pytest.mark.slow
def test_rehearsal_passes_on_cpu_without_a_verdict():
    """``--rehearse`` (~60 s: every leg at the tiny size, interpret-mode
    kernels) passes on the CPU, stamps ``platform: cpu`` on every line
    and never prints the pass verdict."""
    p = subprocess.run([sys.executable, SMOKE, "--rehearse"],
                       capture_output=True, text=True, timeout=600,
                       cwd=ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = _json_lines(p.stdout)
    legs = [ln for ln in lines if "leg" in ln]
    assert legs and all(ln["platform"] == "cpu" for ln in legs)
    assert {"train", "serve", "kernel"} <= {
        ln["leg"] for ln in legs if ln.get("ok") is True}
    assert lines[-1] == {"rehearsed": True,
                         "device": lines[-1]["device"]}
    assert lines[-1]["device"]["platform"] == "cpu"
    assert "ok" not in lines[-1]


_FRESH = """
import os, sys
import byteps_tpu, byteps_tpu.launcher, byteps_tpu.training
import byteps_tpu.models, byteps_tpu.serving.frontend
import byteps_tpu.serving.router, byteps_tpu.ops.flash_attention
import byteps_tpu.ops.fused_cross_entropy, byteps_tpu.ops.decode_attention
import byteps_tpu.ops.paged_attention
import jax
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "import touched a backend"
from byteps_tpu.common.compile_cache import configure_compile_cache
print("CACHE", configure_compile_cache(), jax.config.jax_compilation_cache_dir)
assert not xla_bridge.backends_are_initialized(), "cache helper did"
if os.environ.get("PROBE_LANDED_ON_CPU"):
    from byteps_tpu.ops._pallas_utils import (KernelRefusedError,
                                              resolve_interpret)
    assert jax.default_backend() == "cpu"
    try:
        resolve_interpret(None, "probe")
    except KernelRefusedError as e:
        print("REFUSED", e.kernel)
"""


def _fresh(cwd, **extra_env):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS",
                        "XLA_FLAGS")}
    env.update(PYTHONPATH=ROOT, **extra_env)
    return subprocess.Popen([sys.executable, "-c", _FRESH], env=env,
                            cwd=cwd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _output(proc):
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err[-3000:]
    return out


def test_imports_touch_no_backend_and_cache_path_is_fixed(tmp_path):
    """One process per chip: ``launcher.py`` Popens the worker from a
    parent that has imported the package, so importing it (and placing
    the compile cache) must initialise no backend.  With the variable
    unset, two processes started from different directories agree on
    ``<checkout>/.jax_cache``; a process that merely LANDED on the CPU
    gets a typed refusal from the kernels, not the interpreter."""
    want = os.path.join(ROOT, ".jax_cache")
    pa = _fresh(ROOT, JAX_PLATFORMS="cpu")
    pb = _fresh(str(tmp_path), PROBE_LANDED_ON_CPU="1")
    a, b = _output(pa), _output(pb)
    for out in (a, b):
        line = next(ln for ln in out.splitlines() if ln.startswith("CACHE"))
        assert line.split()[1:] == [want, want]
    assert "REFUSED probe" in b and "REFUSED" not in a


def test_cache_helper_leaves_config_alone_when_variable_is_set():
    """conftest sets ``JAX_COMPILATION_CACHE_DIR`` (outside the checkout):
    JAX reads it itself, the helper must set nothing."""
    from byteps_tpu.common.compile_cache import (DEFAULT_CACHE_DIR,
                                                 configure_compile_cache)

    env_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert not env_dir.startswith(ROOT)
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == before == env_dir
    assert DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")


@pytest.fixture(scope="module")
def paged_frontend():
    from byteps_tpu.models.transformer import Transformer, TransformerConfig
    from byteps_tpu.serving import ServingEngine, serve

    cfg = TransformerConfig(vocab_size=61, num_layers=1, num_heads=2,
                            d_model=16, d_ff=32, max_seq_len=32,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    engine = ServingEngine(model, variables, n_slots=2, paged=True,
                           block=8)
    srv, thread = serve(engine, 0, host="127.0.0.1", in_thread=True)
    yield engine, f"127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()
    thread.join(timeout=10)


def test_op_stats_reports_device_and_attention_path(paged_frontend):
    from byteps_tpu.serving import RemoteServeClient

    engine, addr = paged_frontend
    client = RemoteServeClient(addr, timeout=30.0, transport="tcp")
    try:
        stats = client.stats()
    finally:
        client.close()
    d0 = jax.devices()[0]
    assert stats["device"] == {"platform": d0.platform,
                               "device_kind": d0.device_kind,
                               "count": len(jax.devices())}
    # off the chip `auto` resolves to the gather — and says so
    assert stats["attention_path"] == engine.attention_path == "paged_gather"


def test_failed_first_tick_reaches_the_client_typed(paged_frontend):
    """A compiler refusal at the first decode tick must fail every
    in-flight request with the typed ``ServeReplyError`` — never hang
    the client (engine._run -> _fail_all)."""
    from byteps_tpu.ops._pallas_utils import KernelRefusedError
    from byteps_tpu.serving import RemoteServeClient, ServeReplyError

    engine, addr = paged_frontend

    def refuse(hw):
        raise KernelRefusedError("paged_decode_attention",
                                 "Mosaic failed to compile TPU kernel")

    engine._paged_decode_fn = refuse
    client = RemoteServeClient(addr, timeout=30.0, transport="tcp")
    try:
        with pytest.raises(ServeReplyError, match="paged_decode_attention"):
            client.generate(np.arange(1, 6, dtype=np.int32), 4)
    finally:
        client.close()
