"""Roofline share of the fused LM-head cross-entropy kernels where a
multi-token-prediction module runs the head a second time a step: the
least time for logits, dx and dw of every head pass
(``harness/flops_sparse.py:fused_ce_cost``) over the device time of
``fused_ce_fwd`` + ``fused_ce_bwd_dx`` + ``fused_ce_bwd_dw`` per step.
``fused_ce_roofline`` counts one pass and is not listed for such cells."""

from benchmark.harness import flops_sparse, peaks, xplane

SPEC = {"name": "fused_ce_roofline_mtp", "unit": "%",
        "layer": "ops.fused_cross_entropy", "source": "device_trace"}
KERNELS = ("fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw")


def read(ctx):
    if (ctx.trace is None or ctx.train is None or ctx.peaks is None
            or "mtp_layers" not in ctx.dims):
        return None
    events, secs = xplane.kernel_time(ctx.trace, KERNELS)
    steps = ctx.train["traced_steps"]
    if not events or not steps:
        return None
    rows = ctx.train["per_chip_batch"] * ctx.train["seq_len"]
    f, b = flops_sparse.fused_ce_cost(ctx.dims, rows)
    least, bound = peaks.roofline_seconds(f, b, ctx.peaks)
    ctx.note(event="kernel", kernel="fused_ce_mtp", bound=bound,
             device_ms_per_step=1e3 * secs / steps,
             least_ms_per_step=1e3 * least, calls_per_step=events // steps)
    return 100.0 * least / (secs / steps)
