"""Operations and bytes a looped decoder needs (Ouro's LoopLM: one
stack of dense RoPE MHA / SwiGLU layers run R times a token with one set
of weights, an exit gate and the head read after every pass): the
counterpart of ``flops.py`` for configurations whose ``dims``
(``builders/ouro.py:dims``) carry ``layers`` (the layer APPLICATIONS a
step makes: held layers x passes), ``layers_held``, ``loop_steps`` (R)
and ``flops.py``'s ``d_model, heads, kv_heads, d_head, d_ff, vocab,
mlp``.

Counted is what R passes need: every layer application's matrix
products and its causal attention, the head's product R times (one
readout a pass) and the exit gate's R times.  ``flops.py`` counts the
head once, which is a looped model's truth for one pass only.
Recomputation, the norms and the exit distribution (no matrix product)
are never counted.
"""

from __future__ import annotations

from benchmark.harness import flops


def layer_flops_per_token(d: dict, seq_len: int) -> float:
    """One layer application, forward, a token: its matrix products and
    its causal attention (two products over half the square)."""
    d_attn = d["heads"] * d["d_head"]
    return (2.0 * flops.linear_params_per_layer(d)
            + 2 * 2 * d_attn * seq_len / 2.0)


def readout_flops_per_token(d: dict) -> float:
    """One pass's readout, forward, a token: the head and the gate."""
    return 2.0 * d["d_model"] * d["vocab"] + 2.0 * d["d_model"]


def forward_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward FLOPs a token needs: every application, every readout."""
    return (d["layers"] * layer_flops_per_token(d, seq_len)
            + d["loop_steps"] * readout_flops_per_token(d))


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward pass."""
    return 3.0 * forward_flops_per_token(d, seq_len)


def readouts_cost(d: dict, rows: int):
    """(flops, bytes) of the R readouts' head with cross-entropy,
    forward plus backward, on ``rows`` positions: R times
    ``flops.fused_ce_cost``."""
    f, b = flops.fused_ce_cost(d, rows)
    return d["loop_steps"] * f, d["loop_steps"] * b


def param_count(d: dict) -> int:
    """All parameters that hold memory: the held layers once (their
    matrices and four norms), the table and the untied head, the gate
    and the final norm."""
    layer = flops.linear_params_per_layer(d) + 4 * d["d_model"]
    return (d["layers_held"] * layer + 2 * d["d_model"] * d["vocab"]
            + (d["d_model"] + 1) + d["d_model"])
