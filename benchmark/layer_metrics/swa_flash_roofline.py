"""Roofline share of the flash-attention kernels in a model whose layers
mix attention kinds (whole causal prefix, sliding window; grouped
key-value heads): the least time the chip could take for every layer's
attention inside its own band (forward + backward, FLOPs and bytes from
shapes, ``harness/flops_mixed.py``) over the device time of every
``flash_fwd*`` + ``flash_bwd*`` kernel per step.  A windowed build's
kernels carry the band in their names (``flash_fwd_w4096``), so the note
splits the time by kind.  The repeat of grouped keys and the group-sum
of their gradients run in XLA around the kernels and are in neither the
time nor the count (``model.blocks_xla_ms_per_step`` has them)."""

import re

from benchmark.harness import flops_mixed, peaks, xplane

SPEC = {"name": "swa_flash_roofline", "unit": "%",
        "layer": "ops.flash_attention", "source": "device_trace"}
KERNELS = ("flash_fwd", "flash_bwd")
BAND = re.compile(r"_w\d+")    # a windowed build's suffix: flash_fwd_w4096


def by_kind(trace) -> dict:
    """``{(kind, pass): (events, seconds)}`` of the flash kernels, kind
    ``window`` where the name carries a band (``_w<W>``), else ``full``."""
    out = {}
    for name, (n, secs) in xplane.op_totals(trace).items():
        which = next((k for k in KERNELS if k in name), None)
        if which is None:
            continue
        key = ("window" if BAND.search(name) else "full",
               which[len("flash_"):])
        was = out.get(key, (0, 0.0))
        out[key] = (was[0] + n, was[1] + secs)
    return out


def read(ctx):
    if (ctx.trace is None or ctx.train is None or ctx.peaks is None
            or "window_layout" not in ctx.dims):
        return None
    kinds = by_kind(ctx.trace)
    steps = ctx.train["traced_steps"]
    if not kinds or not steps:
        return None
    ms = {f"{kind}.{which}": 1e3 * s / steps
          for (kind, which), (_, s) in sorted(kinds.items())}
    ms_of = {kind: sum(v for k, v in ms.items() if k.startswith(kind))
             for kind in ("full", "window")}
    batch, T = ctx.train["per_chip_batch"], ctx.train["seq_len"]
    least = {"full": 0.0, "window": 0.0}
    bound = set()
    for window in ctx.dims["window_layout"]:
        f, b = flops_mixed.gqa_flash_cost(ctx.dims, batch, T, window)
        t, which = peaks.roofline_seconds(f, b, ctx.peaks)
        least["full" if window is None else "window"] += 1e3 * t
        bound.add(which)
    ctx.note(event="kernel", kernel="swa_flash", bound=sorted(bound),
             device_ms_per_step=sum(ms.values()),
             least_ms_per_step=sum(least.values()),
             least_ms_per_step_by_kind=least, ms_per_step_by_kind=ms,
             calls_per_step={
                 f"{kind}.{which}": n // steps
                 for (kind, which), (n, _) in sorted(kinds.items())},
             share_by_kind={kind: 100.0 * least[kind] / ms_of[kind]
                            for kind in least if ms_of[kind]})
    return 100.0 * sum(least.values()) / sum(ms.values())
