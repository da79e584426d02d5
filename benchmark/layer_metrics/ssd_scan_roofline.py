"""Roofline share of the mixers' state-space scan: the least time the
chip could take for every mixer's scan (forward + backward, FLOPs and
bytes from shapes, ``harness/flops_hybrid.py``) over the device self
time of every op under ``mamba/ssd`` per step — the ``ssd_*`` kernels
AND the XLA ops around them (the running sums, the layout of ``dt``),
forward, backward and recomputed, the recomputed forward in the time
and not in the count."""

from benchmark.harness import flops_hybrid, module_spans, peaks, xplane

SPEC = {"name": "ssd_scan_roofline", "unit": "%",
        "layer": "ops.ssd_scan", "source": "program_span"}
KERNELS = ("ssd_fwd", "ssd_bwd")


def read(ctx):
    if (ctx.peaks is None or ctx.train is None
            or "ssm_heads" not in ctx.dims):
        return None
    spans = module_spans.for_run(ctx, "mamba")
    if spans is None:
        return None
    by_pass = {which: s for (child, which), s in spans.items()
               if child == "ssd"}
    secs = sum(by_pass.values())
    if not secs:
        return None
    tokens = ctx.train["per_chip_batch"] * ctx.train["seq_len"]
    f, b = flops_hybrid.ssd_scan_cost(ctx.dims, tokens)
    least, bound = peaks.roofline_seconds(f, b, ctx.peaks)
    steps = ctx.train["traced_steps"]
    kernels = {k: xplane.kernel_time(ctx.trace, (k,)) for k in KERNELS}
    ctx.note(event="kernel", kernel="ssd_scan", bound=bound,
             device_ms_per_step=1e3 * secs, least_ms_per_step=1e3 * least,
             ms_per_step_by_pass={k: 1e3 * v
                                  for k, v in sorted(by_pass.items())},
             kernels_ms_per_step={k: 1e3 * s / steps
                                  for k, (_, s) in kernels.items()},
             kernel_calls_per_step={k: n // steps
                                    for k, (n, _) in kernels.items()})
    return 100.0 * least / secs
