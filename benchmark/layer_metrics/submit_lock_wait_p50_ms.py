"""Median wait of a ``submit()`` for the engine's lock — which the tick
thread holds for a whole tick — from the engine's own
``serve.submit_lock_wait_s`` histogram on the STATS reply after the
window (a bounded reservoir over the process's life: the few warm-up
and ramp requests are in it).  The wait is inside the engine's TTFT and
outside its queue wait (``sched.queue_wait_p50_ms``).  ``None`` on a
program from before the histogram."""

SPEC = {"name": "submit.lock_wait_p50_ms", "unit": "ms",
        "layer": "serving.scheduler", "source": "program_counter"}


def read(ctx):
    if ctx.serve is None:
        return None
    after = ctx.serve["stats_after"]
    if not after.get("submit_lock_wait_n"):
        return None
    ctx.note(event="submit_lock_wait", n=after["submit_lock_wait_n"],
             p99_ms=1e3 * after["submit_lock_wait_p99_s"])
    return 1e3 * after["submit_lock_wait_p50_s"]
