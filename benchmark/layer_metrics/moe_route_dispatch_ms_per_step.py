"""What the expert layers spend around their experts: device self time
per step of the ops under ``moe/router``, ``moe/dispatch`` and
``moe/combine`` — the float32 router product and top-k, the sort and the
layout of the row buffer, the gathers into it and back — forward,
backward and recomputed forward together, averaged over chips."""

from benchmark.harness import module_spans

SPEC = {"name": "moe.route_dispatch_ms_per_step", "unit": "ms",
        "layer": "parallel.moe", "source": "program_span"}
CHILDREN = ("router", "dispatch", "combine")


def read(ctx):
    spans = module_spans.for_run(ctx, "moe")
    if spans is None:
        return None
    secs = sum(s for (child, _), s in spans.items() if child in CHILDREN)
    return 1e3 * secs if secs else None
