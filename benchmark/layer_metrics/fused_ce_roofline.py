"""Roofline share of the fused LM-head cross-entropy kernels: the least
time for logits, dx and dw over the device time of ``fused_ce_fwd`` +
``fused_ce_bwd_dx`` + ``fused_ce_bwd_dw`` per step."""

import math

from benchmark.harness import flops, peaks, xplane

SPEC = {"name": "fused_ce_roofline", "unit": "%",
        "layer": "ops.fused_cross_entropy", "source": "device_trace"}
KERNELS = ("fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw")


def read(ctx):
    if ctx.trace is None or ctx.train is None or ctx.peaks is None:
        return None
    events, secs = xplane.kernel_time(ctx.trace, KERNELS)
    steps = ctx.train["traced_steps"]
    if not events or not steps:
        return None
    rows = ctx.train["per_chip_batch"] * ctx.train["seq_len"]
    f, b = flops.fused_ce_cost(ctx.dims, rows)
    least, bound = peaks.roofline_seconds(f, b, ctx.peaks)
    ctx.note(event="kernel", kernel="fused_ce", bound=bound,
             device_ms_per_step=1e3 * secs / steps,
             least_ms_per_step=1e3 * least,
             # the vocab block the kernel's fitter can resolve to
             block_v_gcd_rows_1024=math.gcd(ctx.train["table_rows"], 1024))
    return 100.0 * least / (secs / steps)
