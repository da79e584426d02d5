"""Median device time of one launch of the engine's decode program
(``jit(decode_fn)``), found by the module name the trace prints."""

from benchmark.harness import stats, xplane

SPEC = {"name": "serve_prog.decode_device_ms", "unit": "ms",
        "layer": "serving.engine", "source": "device_trace"}


def read(ctx):
    if ctx.trace is None:
        return None
    durs = xplane.module_durations(ctx.trace, "decode_fn")
    if not durs:
        return None
    ctx.note(event="program", program="decode_fn", launches=len(durs),
             p90_ms=1e3 * stats.pctl(durs, 90))
    return 1e3 * stats.median(durs)
