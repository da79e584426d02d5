"""Median device time of one launch of the step program (the longest
program in the traced window), against which the host's step time shows
what dispatch adds."""

from benchmark.harness import stats

SPEC = {"name": "train_prog.step_device_ms", "unit": "ms",
        "layer": "training.step", "source": "device_trace"}


def read(ctx):
    if ctx.trace is None or ctx.train is None or not ctx.trace.modules:
        return None
    evs = ctx.trace.modules[min(ctx.trace.modules)]
    if not evs:
        return None
    # the step is the program that takes most of the window
    by_name = {}
    for ev in evs:
        by_name.setdefault(ev.name, []).append(ev.dur)
    name, durs = max(by_name.items(), key=lambda kv: sum(kv[1]))
    ctx.note(event="step_program", module=name, launches=len(durs))
    return 1e3 * stats.median(durs)
