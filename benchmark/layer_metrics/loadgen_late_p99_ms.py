"""How late the load generator ran: send instant minus due instant,
99th percentile over the window's requests.  A late generator falsifies
the latency it reports, so above 5 ms the run says so."""

from benchmark.harness import stats

SPEC = {"name": "loadgen.late_p99_ms", "unit": "ms",
        "layer": "benchmark.loadgen", "source": "host_clock"}


def read(ctx):
    if ctx.serve is None or not ctx.serve["samples"]["late_ms"]:
        return None
    late = stats.pctl(ctx.serve["samples"]["late_ms"], 99)
    if late > 5.0:
        ctx.note(event="starved generator", late_p99_ms=late)
    return late
