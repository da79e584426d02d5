"""Model FLOP/s utilization of the train step: operations the forward
and backward passes need per token (from shapes; recomputation and the
table's padding rows are not counted) x tokens/s over chips x peak."""

from benchmark.harness import flops

SPEC = {"name": "train_step.mfu", "unit": "%",
        "layer": "training.step", "source": "host_clock"}


def read(ctx):
    if ctx.train is None or ctx.peaks is None:
        return None
    per_token = flops.train_flops_per_token(ctx.dims, ctx.train["seq_len"])
    ctx.note(event="mfu", flops_per_token=per_token,
             tokens_per_s=ctx.train["tokens_per_s"], chips=ctx.chips)
    return (100.0 * per_token * ctx.train["tokens_per_s"]
            / (ctx.chips * ctx.peaks["bf16_flops"]))
