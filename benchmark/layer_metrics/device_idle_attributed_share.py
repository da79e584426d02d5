"""Share of the traced window's device-idle seconds that lie under a
``bps.*`` host span of the engine — the tick thread's innermost span
over each part of a gap (``idle_wait`` included: an engine with nothing
to do says so), else a ``bps.submit`` span of another thread.  The rest
is host time nobody named.  Counted between the tick thread's first and
last recorded span: a span in progress when the profiler starts or stops
is not in the trace.  By span: the ``tick_phases`` note.  ``None`` on a
program without the spans."""

from benchmark.harness import host_spans

SPEC = {"name": "device.idle_attributed_share", "unit": "%",
        "layer": "device", "source": "program_span"}


def read(ctx):
    spans = host_spans.for_run(ctx)
    if spans is None or not spans["idle_s"]:
        return None
    return host_spans.attributed_share(spans["idle_s"])
