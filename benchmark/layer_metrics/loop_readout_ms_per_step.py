"""What a looped model's readouts cost: device self time per step of
every op under the program's ``loop_readout`` scope (inside
``bps.head``) — the final norm and the exit gate of every pass, the
head's cross-entropy kernels of every pass, the exit distribution and
its entropy — forward and backward together, averaged over chips.  The
note splits it into forward and backward (the R passes' readouts are
one op each, inside the map over the passes) and gives beside it the
least time of the R readouts' head with cross-entropy (``harness/flops_loop.py:
readouts_cost``, R times ``flops.fused_ce_cost``) at the chip's
peaks."""

from benchmark.harness import flops_loop, peaks, scopes, xplane

SPEC = {"name": "loop.readout_ms_per_step", "unit": "ms",
        "layer": "models.transformer", "source": "program_span"}
SCOPE = "loop_readout"


def readout_seconds(st):
    """``{"fwd" | "bwd": seconds per step}`` of the ops whose name lies
    under ``SCOPE``; ``None`` where the trace holds no launch of a step
    or no such op."""
    launches = scopes.step_launches(st)
    n_launch = sum(len(v) for v in launches.values())
    if not n_launch:
        return None
    out = {}
    for d, evs in st.ops.items():
        spans = [(ln.start, ln.end) for ln in launches[d]]
        for ev in evs:
            if (not ev.self_s or SCOPE not in scopes.components(ev.op_name)
                    or not any(s <= ev.start < e for s, e in spans)):
                continue
            which = "bwd" if ev.scope.bwd else "fwd"
            out[which] = out.get(which, 0.0) + ev.self_s
    if not out:
        return None
    return {k: v / n_launch for k, v in out.items()}


def read(ctx):
    if (getattr(ctx, "trace", None) is None or ctx.train is None
            or "loop_steps" not in ctx.dims):
        return None
    st = getattr(ctx, "scoped_trace", None)
    if st is None:
        path = xplane.find_xplane(scopes.trace_dir(ctx.cell["name"]))
        if path is None:
            return None
        st = ctx.scoped_trace = scopes.load(path)
    secs = readout_seconds(st)
    if secs is None:
        return None
    least = None
    if ctx.peaks is not None:
        rows = ctx.train["per_chip_batch"] * ctx.train["seq_len"]
        least = 1e3 * peaks.roofline_seconds(
            *flops_loop.readouts_cost(ctx.dims, rows), ctx.peaks)[0]
    ctx.note(event="loop_readout", ms_per_step_by_direction={
        k: 1e3 * v for k, v in sorted(secs.items())},
        loop_steps=ctx.dims["loop_steps"],
        head_ce_least_ms_per_step=least)
    return 1e3 * sum(secs.values())
