"""The process that holds the chip: platform pin, compile accounting,
device identity and memory.  Imported only by code that is allowed to
touch JAX (the train runner, the serve child)."""

from __future__ import annotations

import re
import time


class NoChipError(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def claim_devices(chips: int, rehearse: bool):
    """Pin the platform in code BEFORE any backend touch and return the
    cell's devices.  A real run pins ``tpu``: with no chip JAX raises
    here instead of quietly handing out a CPU device.  A rehearsal pins
    the CPU with ``chips`` virtual devices."""
    import jax

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", max(1, chips))
    else:
        jax.config.update("jax_platforms", "tpu")
    want = "cpu" if rehearse else "tpu"
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChipError(f"no {want.upper()} for this process: {e}") from e
    if devices[0].platform != want:
        raise NoChipError(f"pinned {want!r}, JAX reports "
                          f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChipError(f"the cell asks for {chips} chips, JAX finds "
                          f"{len(devices)}")
    return devices[:chips]


class CompileLog:
    """Times of every backend compile (or persistent-cache load) this
    process makes, from JAX's monitoring events — the listener
    ``chip_smoke.py:Reporter.listen`` uses.  ``count_since(t)`` is what
    "nothing compiles inside the window" is checked against."""

    def __init__(self):
        from jax import monitoring

        self.events = []          # (wall clock at end, seconds)
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.time(), duration))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def count_since(self, wall: float) -> int:
        return sum(1 for end, _ in self.events if end >= wall)

    def summary(self) -> dict:
        return {"compiles": len(self.events),
                "compile_s": sum(d for _, d in self.events),
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses}


def mosaic_calls(compiled_text: str) -> list:
    """Names of the Mosaic (``tpu_custom_call``) instructions in a
    compiled program's HLO text (``chip_smoke.py:mosaic_calls``): a
    Pallas kernel's ``name=`` becomes the instruction name, so this
    reads what was compiled, not what a selection function chose."""
    return re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled_text)


def missing_kernels(names: list, wanted) -> list:
    # differentiation wraps the name (jvp_<name>_, transpose_jvp_<name>__)
    return [k for k in wanted if not any(k in n for n in names)]


def device_report(devices) -> dict:
    """The ``device`` object of the result line, as JAX reports it;
    ``memory_peak_bytes`` is the peak on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
