"""Host milliseconds a decode tick: per traced ``bps.tick`` span that
holds a ``decode`` child, the tick's span less its ``readback``
children (the host waiting for the program it launched); median over
the traced ticks.  The note sets the engine's always-on counters beside
it, over the whole window and not only its traced part: seconds by phase
of ``serve.tick_seconds``, after - before, a decode tick (the five
phases of a pass) or a worked tick (the others).  ``None`` on a program
without the spans."""

from benchmark.harness import host_spans, stats

SPEC = {"name": "tick.host_ms_per_decode_tick", "unit": "ms",
        "layer": "serving.engine", "source": "program_span"}
PASS = ("blocks", "build", "launch", "readback", "emit")


def counters_ms(before: dict, after: dict):
    """``{phase: ms a tick}`` from two STATS replies, or ``None``."""
    secs, secs0 = after.get("serve.tick_seconds"), before.get(
        "serve.tick_seconds", {})
    decode = (after.get("serve.decode_ticks", 0)
              - before.get("serve.decode_ticks", 0))
    worked = (after.get("serve.ticks_worked", 0)
              - before.get("serve.ticks_worked", 0))
    if not secs or decode <= 0 or worked <= 0:
        return None
    return {p: 1e3 * (s - secs0.get(p, 0.0))
            / (decode if p in PASS else worked) for p, s in secs.items()}


def read(ctx):
    spans = host_spans.for_run(ctx)
    if spans is None or not spans["host_ms_per_decode_tick"]:
        return None
    if ctx.serve is not None:
        ctx.note(event="tick_counters",
                 ms_per_tick_by_phase_over_the_window=counters_ms(
                     ctx.serve["stats_before"], ctx.serve["stats_after"]),
                 per="decode tick: " + ", ".join(PASS)
                     + "; worked tick: the others")
    return stats.median(spans["host_ms_per_decode_tick"])
