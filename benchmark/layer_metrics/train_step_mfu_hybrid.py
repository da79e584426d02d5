"""Model FLOP/s utilization of the train step of a hybrid decoder of
one-sublayer blocks (Mamba-2 mixers, attention, non-gated experts): the
operations this chip's share needs per token
(``harness/flops_hybrid.py``: the mixers' projections and scan,
attention under the causal mask, the experts held on the assignments the
program's steps counted, the vocabulary's slice; recomputation and
padding not counted) x tokens/s over chips x peak."""

from benchmark.harness import flops_hybrid, flops_sparse

SPEC = {"name": "train_step.mfu_hybrid", "unit": "%",
        "layer": "training.step", "source": "host_clock"}


def read(ctx):
    if (ctx.train is None or ctx.peaks is None
            or "ssm_heads" not in ctx.dims):
        return None
    d = flops_sparse.counted(
        ctx.dims, ctx.train["per_chip_batch"] * ctx.train["seq_len"])
    per_token = flops_hybrid.train_flops_per_token(d, ctx.train["seq_len"])
    ctx.note(event="mfu_hybrid", flops_per_token=per_token,
             tokens_per_s=ctx.train["tokens_per_s"], chips=ctx.chips,
             held_assignments_per_token_layer=d[
                 "held_assignments_per_token_layer"],
             counted=d is not ctx.dims)
    return (100.0 * per_token * ctx.train["tokens_per_s"]
            / (ctx.chips * ctx.peaks["bf16_flops"]))
