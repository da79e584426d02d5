"""Model FLOP/s utilization of the train step of a looped decoder: the
operations R passes need per token (``harness/flops_loop.py``: every
layer application's products and causal attention, the head and the
exit gate once a pass; recomputation not counted) x tokens/s over chips
x peak."""

from benchmark.harness import flops_loop

SPEC = {"name": "train_step.mfu_loop", "unit": "%",
        "layer": "training.step", "source": "host_clock"}


def read(ctx):
    if (ctx.train is None or ctx.peaks is None
            or "loop_steps" not in ctx.dims):
        return None
    per_token = flops_loop.train_flops_per_token(ctx.dims,
                                                 ctx.train["seq_len"])
    ctx.note(event="mfu_loop", flops_per_token=per_token,
             tokens_per_s=ctx.train["tokens_per_s"], chips=ctx.chips,
             layer_applications=ctx.dims["layers"],
             loop_steps=ctx.dims["loop_steps"])
    return (100.0 * per_token * ctx.train["tokens_per_s"]
            / (ctx.chips * ctx.peaks["bf16_flops"]))
