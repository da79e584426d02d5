"""Peak bytes in use on the fullest chip after the window, as the
runtime counts them (``device.memory_stats()["peak_bytes_in_use"]``).
Guards sizing: the batch or pool that fits sets what the cell can do."""

SPEC = {"name": "device.peak_hbm_gb", "unit": "GB", "layer": "device",
        "source": "program_counter"}


def read(ctx):
    if ctx.rehearse or not ctx.device.get("memory_peak_bytes"):
        return None
    return ctx.device["memory_peak_bytes"] / 1e9
