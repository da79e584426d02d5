"""Roofline share of the flash-attention kernels in the train step:
the least time the chip could take for the attention of all layers
(forward + backward, FLOPs and bytes from shapes) over the device time
of ``flash_fwd`` + ``flash_bwd_dq`` + ``flash_bwd_dkv`` per step."""

from benchmark.harness import flops, peaks, xplane

SPEC = {"name": "flash_roofline", "unit": "%",
        "layer": "ops.flash_attention", "source": "device_trace"}
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(ctx):
    if ctx.trace is None or ctx.train is None or ctx.peaks is None:
        return None
    events, secs = xplane.kernel_time(ctx.trace, KERNELS)
    steps = ctx.train["traced_steps"]
    if not events or not steps:
        return None
    f, b = flops.flash_attention_cost(
        ctx.dims, ctx.train["per_chip_batch"], ctx.train["seq_len"])
    least, bound = peaks.roofline_seconds(
        f * ctx.dims["layers"], b * ctx.dims["layers"], ctx.peaks)
    ctx.note(event="kernel", kernel="flash", bound=bound,
             device_ms_per_step=1e3 * secs / steps,
             least_ms_per_step=1e3 * least, calls_per_step=events // steps)
    return 100.0 * least / (secs / steps)
