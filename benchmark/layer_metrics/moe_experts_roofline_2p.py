"""``moe_experts_roofline`` for NON-GATED experts: the least time the
chip could take for the TWO products (up, down) of every expert layer on
the assignments routed to the experts held here (forward + backward,
``harness/flops_hybrid.py``; the assignments are those the program's
steps counted) over the device self time of every op under
``moe/experts`` per step — kernel or not, the recomputed forward in the
time and not in the count.  ``moe_experts_roofline`` counts three
products an expert and would read 1.5 x too high here."""

from benchmark.harness import (flops_hybrid, flops_sparse, module_spans,
                               peaks)

SPEC = {"name": "moe_experts_roofline_2p", "unit": "%",
        "layer": "parallel.moe", "source": "program_span"}


def read(ctx):
    if (ctx.peaks is None or ctx.train is None
            or "d_shared" not in ctx.dims):
        return None
    spans = module_spans.for_run(ctx, "moe")
    if spans is None:
        return None
    secs = sum(s for (child, _), s in spans.items() if child == "experts")
    if not secs:
        return None
    tokens = ctx.train["per_chip_batch"] * ctx.train["seq_len"]
    d = flops_sparse.counted(ctx.dims, tokens)
    f, b = flops_hybrid.held_experts_cost(d, tokens)
    least, bound = peaks.roofline_seconds(f, b, ctx.peaks)
    ctx.note(event="kernel", kernel="moe_experts_2p", bound=bound,
             device_ms_per_step=1e3 * secs, least_ms_per_step=1e3 * least,
             held_assignments_per_step=flops_hybrid.
             held_assignments_per_step(d, tokens))
    return 100.0 * least / secs
