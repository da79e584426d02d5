"""Roofline share of the paged decode-attention kernel: the K/V bytes
the generated tokens had to read (whole blocks, all layers; contexts
from the client's own token timestamps inside the traced interval)
over HBM bandwidth, against the kernel's device time there."""

from benchmark.harness import flops, latency, xplane

SPEC = {"name": "paged_decode_roofline", "unit": "%",
        "layer": "ops.paged_attention", "source": "device_trace"}


def read(ctx):
    if ctx.trace is None or ctx.serve is None or ctx.peaks is None:
        return None
    events, secs = xplane.kernel_time(ctx.trace, ("paged_decode_attention",))
    t0, t1 = ctx.serve["trace_interval"]
    contexts = latency.decode_contexts(ctx.serve["records"], t0, t1)
    if not events or not contexts:
        return None
    nbytes = flops.paged_decode_read_bytes(
        ctx.dims, contexts, ctx.config["engine"]["block"])
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.note(event="kernel", kernel="paged_decode", bound="memory",
             kernel_calls=events, device_s=secs, least_s=least,
             tokens_decoded=len(contexts),
             mean_context=sum(contexts) / len(contexts))
    return 100.0 * least / secs
