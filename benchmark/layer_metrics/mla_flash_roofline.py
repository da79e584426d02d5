"""Roofline share of the flash-attention kernels under latent attention
(q / k heads wider than v heads): the least time the chip could take for
the attention of every block (forward + backward, FLOPs and bytes from
shapes, ``harness/flops_sparse.py``) over the device time of
``flash_fwd`` + ``flash_bwd_dq`` + ``flash_bwd_dkv`` per step — the
forward a recomputed block runs again is in the time and not in the
count."""

from benchmark.harness import flops_sparse, peaks, xplane

SPEC = {"name": "mla_flash_roofline", "unit": "%",
        "layer": "ops.flash_attention", "source": "device_trace"}
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(ctx):
    if (ctx.trace is None or ctx.train is None or ctx.peaks is None
            or "d_nope" not in ctx.dims):
        return None
    events, secs = xplane.kernel_time(ctx.trace, KERNELS)
    steps = ctx.train["traced_steps"]
    if not events or not steps:
        return None
    f, b = flops_sparse.mla_flash_cost(
        ctx.dims, ctx.train["per_chip_batch"], ctx.train["seq_len"])
    n = flops_sparse.blocks(ctx.dims)
    least, bound = peaks.roofline_seconds(f * n, b * n, ctx.peaks)
    ctx.note(event="kernel", kernel="mla_flash", bound=bound,
             device_ms_per_step=1e3 * secs / steps,
             least_ms_per_step=1e3 * least, calls_per_step=events // steps)
    return 100.0 * least / (secs / steps)
