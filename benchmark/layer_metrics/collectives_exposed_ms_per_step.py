"""Collective time nobody hides: per step, the time inside collective
ops on a device during which no compute op runs on it, on the worst
chip.  The total time collectives were in flight goes to an earlier
line.  A one-chip trace has no collective op and reports nothing."""

from benchmark.harness import xplane

SPEC = {"name": "collectives.exposed_ms_per_step", "unit": "ms",
        "layer": "parallel.collectives", "source": "device_trace"}


def read(ctx):
    if ctx.trace is None or ctx.train is None:
        return None
    steps = ctx.train["traced_steps"]
    per_dev = xplane.collective_seconds(ctx.trace)
    if not steps or not any(v["events"] for v in per_dev.values()):
        return None
    ctx.note(event="collectives", steps=steps, per_device_ms_per_step={
        d: {"exposed": 1e3 * v["exposed"] / steps,
            "in_flight": 1e3 * v["total"] / steps,
            "ops": v["events"] // steps}
        for d, v in per_dev.items()})
    return 1e3 * max(v["exposed"] for v in per_dev.values()) / steps
