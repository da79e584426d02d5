"""Median wait between submission and admission, from the engine's own
``serve.queue_wait`` histogram on the STATS reply after the window (a
bounded reservoir over the process's life: the few warm-up and ramp
requests are in it)."""

SPEC = {"name": "sched.queue_wait_p50_ms", "unit": "ms",
        "layer": "serving.scheduler", "source": "program_counter"}


def read(ctx):
    if ctx.serve is None:
        return None
    after = ctx.serve["stats_after"]
    if not after.get("queue_wait_n"):
        return None
    return 1e3 * after["queue_wait_p50_s"]
