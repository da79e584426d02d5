"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window, averaged over the chips)."""

from benchmark.harness import xplane

SPEC = {"name": "device.idle_share", "unit": "%", "layer": "device",
        "source": "device_trace"}


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or not ctx.trace.window_s:
        return None
    return 100.0 * (1.0 - xplane.busy_s(ctx.trace) / ctx.trace.window_s)
