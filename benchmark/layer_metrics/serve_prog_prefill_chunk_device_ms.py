"""Median device time of one launch of the engine's slowest
chunk-prefill program (``jit(chunk_fn)``).  Every bucket is a program
of its own, told apart by the fingerprint in its module name; the
slowest is the full ``chunk``-token bucket, which long prompts run
through and which sets the stall a prefill puts between two decode
ticks.  The other buckets are printed on an earlier line: a median
pooled over buckets follows the mix of prompt lengths, not the program.
"""

from benchmark.harness import stats, xplane

SPEC = {"name": "serve_prog.prefill_chunk_device_ms", "unit": "ms",
        "layer": "serving.engine", "source": "device_trace"}


def read(ctx):
    if ctx.trace is None:
        return None
    medians = {name: 1e3 * stats.median(durs) for name, durs in
               xplane.module_launches(ctx.trace, "chunk_fn").items()}
    if not medians:
        return None
    ctx.note(event="program", program="chunk_fn",
             median_ms_by_bucket=medians)
    return max(medians.values())
