"""Tracing subsystem tests (reference docs/timeline.md behavior)."""

import json
import os

import jax.numpy as jnp
import numpy as np

import byteps_tpu as bps
from byteps_tpu.common.config import Config, set_config
from byteps_tpu.common import tracing


def test_tracer_records_spans(tmp_path):
    t = tracing.Tracer(path=str(tmp_path / "trace.json"))
    with t.span("Gradient_w", "push_pull", key=7, bytes=128):
        pass
    t.instant("start", "engine")
    path = t.flush()
    data = json.load(open(path))
    evs = data["traceEvents"]
    assert len(evs) == 2
    span = [e for e in evs if e["ph"] == "X"][0]
    assert span["name"] == "Gradient_w"
    assert span["args"]["key"] == 7
    assert span["dur"] >= 0


def test_tracer_key_filter():
    t = tracing.Tracer(path="unused.json", key_filter="Gradient")
    with t.span("Parameter_b", "push_pull"):
        pass
    with t.span("Gradient_w", "push_pull"):
        pass
    assert [e["name"] for e in t.events()] == ["Gradient_w"]


def test_disabled_tracer_is_noop():
    t = tracing.Tracer(path="")
    with t.span("x", "s"):
        pass
    assert t.events() == []
    assert t.flush() is None


def test_engine_emits_trace(tmp_path):
    trace_file = str(tmp_path / "bps_trace.json")
    cfg = Config.from_env()
    cfg.trace_path = trace_file
    set_config(cfg)
    tracing.reset_tracer()

    bps.init()
    n = bps.size()
    x = jnp.ones((n, 4), jnp.float32)
    out = bps.push_pull(x, average=False, name="traced_tensor")
    np.testing.assert_allclose(np.asarray(out), n)
    bps.shutdown()  # flushes

    data = json.load(open(trace_file))
    names = {e["name"] for e in data["traceEvents"]}
    assert any("traced_tensor" in s for s in names)
    stages = {e["tid"] for e in data["traceEvents"]}
    assert {"dispatch", "push_pull"} <= stages


def test_debug_sample_tensor_logs(monkeypatch):
    """BYTEPS_DEBUG_SAMPLE_TENSOR=<name> prints the tensor's first/last
    values after the stage completes (reference core_loops.cc:33-63)."""
    import logging

    import numpy as np

    import byteps_tpu as bps

    from byteps_tpu.common.config import reset_config

    bps.shutdown()  # drop engine + config so the env var is re-read
    # shutdown() of an engine that is not up returns before it drops the
    # config: one cached by an earlier test of this worker (any
    # get_config() after a shutdown) would hide the variable
    reset_config()
    monkeypatch.setenv("BYTEPS_DEBUG_SAMPLE_TENSOR", "dbg_probe")
    bps.init()
    # the byteps_tpu logger doesn't propagate and caches its level from
    # the first init; attach a handler + raise the level directly
    logger = logging.getLogger("byteps_tpu")
    messages = []

    class _Capture(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    handler = _Capture(level=logging.INFO)
    old_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        n = bps.size()
        x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
        out = bps.push_pull(x, average=False, name="dbg_probe_w")
        np.asarray(out)
        assert any("sample dbg_probe_w" in m for m in messages), messages
        # non-matching names stay silent
        messages.clear()
        bps.push_pull(x, average=False, name="other_tensor")
        assert not any("sample other" in m for m in messages)
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
        # undo the env BEFORE re-init, or the sampling config leaks into
        # the restored engine for the rest of the session
        monkeypatch.delenv("BYTEPS_DEBUG_SAMPLE_TENSOR", raising=False)
        bps.shutdown()
        reset_config()
        bps.init()  # restore a clean engine for subsequent tests
