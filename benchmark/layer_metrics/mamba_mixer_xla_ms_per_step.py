"""What the Mamba-2 mixers spend around their scan: device self time per
step of the ops under ``mamba`` outside ``mamba/ssd`` — the in- and
out-projections, the causal depthwise convolution, the gated group norm
— forward, backward and recomputed forward together, averaged over
chips; the note splits it by child."""

from benchmark.harness import module_spans

SPEC = {"name": "mamba.mixer_xla_ms_per_step", "unit": "ms",
        "layer": "models.transformer", "source": "program_span"}


def read(ctx):
    spans = module_spans.for_run(ctx, "mamba")
    if spans is None:
        return None
    by_child = {}
    for (child, _), s in spans.items():
        if child != "ssd":
            by_child[child] = by_child.get(child, 0.0) + s
    secs = sum(by_child.values())
    if not secs:
        return None
    ctx.note(event="mixer_xla", ms_per_step_by_child={
        k or "(mixer)": 1e3 * v for k, v in sorted(by_child.items())})
    return 1e3 * secs
