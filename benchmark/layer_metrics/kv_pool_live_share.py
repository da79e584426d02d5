"""Share of the paged K/V pool's blocks that hold live tokens when the
window closes (``kv_blocks.used / n_blocks`` of the STATS reply; the
permanently held null block counts as used; the reading at the window's
start is printed on an earlier line).  ``device.peak_hbm_gb`` counts
the whole pool because the engine reserves it at start: this is how
much of that reservation the traffic fills."""

SPEC = {"name": "kv_pool.live_share", "unit": "%",
        "layer": "serving.blocks", "source": "program_counter"}


def read(ctx):
    if ctx.serve is None:
        return None
    pool = ctx.serve["stats_after"].get("kv_blocks")
    if not pool or not pool.get("n_blocks"):
        return None
    ctx.note(event="kv_pool", n_blocks=pool["n_blocks"],
             used_at_window_end=pool["used"], used_at_window_start=(
                 ctx.serve["stats_before"].get("kv_blocks") or {}
             ).get("used"))
    return 100.0 * pool["used"] / pool["n_blocks"]
