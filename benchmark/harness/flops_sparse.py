"""Operations and bytes a latent-attention decoder with expert layers
needs, from shapes alone — the counterpart of ``flops.py`` (a dense
decoder with one head width and one feed-forward) for configurations
whose ``dims`` (``builders/joyai_flash.py:dims``) carry: ``layers,
dense_layers, expert_layers`` (the multi-token-prediction module's block
included), ``mtp_layers, d_model, heads, q_rank, kv_rank, d_nope, d_rope,
d_v, d_ff, d_expert, experts, experts_held, top_k, shared_experts, vocab,
held_assignments_per_token_layer`` (the nominal ``k * held / experts``)
and ``held_assignments_per_step`` (what the program's steps counted, or
None: ``counted`` lays it over the nominal).

Counted is what the chip's share of the model needs: the experts HELD
here on the assignments routed to them, the vocabulary's slice, every
block the configuration keeps.  Recomputation and padding are an implementation's
choice and are never counted.  Byte counts are for bf16 (2 bytes).
"""

from __future__ import annotations

BF16 = 2


def counted(d: dict, tokens: int) -> dict:
    """``d`` with the held assignments a token and layer that the steps
    of this run counted (``tokens`` a step), where they counted any."""
    per_step = d.get("held_assignments_per_step")
    if per_step is None:
        return d
    return dict(d, held_assignments_per_token_layer=per_step / (
        tokens * d["expert_layers"]))


def blocks(d: dict) -> int:
    """Blocks with attention: the layers and the prediction module's."""
    return d["layers"] + d["mtp_layers"]


def mla_params(d: dict) -> int:
    """Matrix-multiply weights of one latent-attention sublayer."""
    qk = d["d_nope"] + d["d_rope"]
    return (d["d_model"] * d["q_rank"] + d["q_rank"] * d["heads"] * qk
            + d["d_model"] * (d["kv_rank"] + d["d_rope"])
            + d["kv_rank"] * d["heads"] * (d["d_nope"] + d["d_v"])
            + d["heads"] * d["d_v"] * d["d_model"])


def swiglu_params(d_model: int, width: int) -> int:
    return 3 * d_model * width


def scores_flops_per_token(d: dict, seq_len: int) -> float:
    """QK^T and PV of one block, forward, per token: a query meets half
    the positions under the causal mask; q and k heads are ``d_nope +
    d_rope`` wide, v heads ``d_v``."""
    return 2.0 * d["heads"] * (d["d_nope"] + d["d_rope"] + d["d_v"]) * (
        seq_len / 2.0)


def forward_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward FLOPs a token needs on this chip's share."""
    per_block = 2.0 * mla_params(d) + scores_flops_per_token(d, seq_len)
    dense = 2.0 * swiglu_params(d["d_model"], d["d_ff"]) * d["dense_layers"]
    per_expert = 2.0 * swiglu_params(d["d_model"], d["d_expert"])
    expert_layer = (2.0 * d["d_model"] * d["experts"]            # router
                    + per_expert * d["shared_experts"]
                    + per_expert * d["held_assignments_per_token_layer"])
    head = 2.0 * d["d_model"] * d["vocab"] * (1 + d["mtp_layers"])
    mtp_proj = 2.0 * 2 * d["d_model"] * d["d_model"] * d["mtp_layers"]
    return (blocks(d) * per_block + dense
            + d["expert_layers"] * expert_layer + head + mtp_proj)


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward pass."""
    return 3.0 * forward_flops_per_token(d, seq_len)


def mla_flash_cost(d: dict, batch: int, seq_len: int):
    """(flops, bytes) of one block's causal attention, forward plus
    backward, as the flash algorithm needs them: 4 matmuls over q/k-wide
    heads (QK^T; backward S again, dQ, dK) and 3 over v-wide heads (PV;
    backward dV, dP), each 2*B*H*T*T*width over the square, half under
    the mask.  Bytes: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv."""
    qk, dv = d["d_nope"] + d["d_rope"], d["d_v"]
    square = 2.0 * batch * d["heads"] * seq_len * seq_len
    flops = (4 * qk + 3 * dv) * square / 2.0
    per_pos = (2 * qk + 2 * dv) + (4 * qk + 4 * dv)
    return flops, per_pos * batch * seq_len * d["heads"] * BF16


def fused_ce_cost(d: dict, rows: int):
    """(flops, bytes) of the LM head with cross-entropy, forward plus
    backward, on ``rows`` positions, once for the model's own head pass
    and once for each multi-token-prediction module's (the same head
    weight): logits, dx and dw are one ``rows x d_model x vocab`` matmul
    each a pass (``flops.py:fused_ce_cost`` counts one pass).  Bytes a
    pass: the activations and the head weight read forward and backward,
    dx and dw written."""
    passes = 1 + d["mtp_layers"]
    x = rows * d["d_model"] * BF16
    w = d["d_model"] * d["vocab"] * BF16
    return (passes * 3 * 2.0 * rows * d["d_model"] * d["vocab"],
            passes * (3 * x + 3 * w))


def held_assignments_per_step(d: dict, tokens: int) -> float:
    return d["held_assignments_per_token_layer"] * tokens * d["expert_layers"]


def held_experts_cost(d: dict, tokens: int):
    """(flops, bytes) a step of the held experts' three products, forward
    plus backward, over all expert layers: 6 FLOPs per weight and
    assignment.  Bytes: every held expert's weights read forward, read
    backward and their gradient written; a row read and written forward
    (x, out) and read twice, written once backward (x, dout, dx)."""
    w = swiglu_params(d["d_model"], d["d_expert"])
    rows = held_assignments_per_step(d, tokens)
    return (6.0 * w * rows,
            (3 * w * d["experts_held"] * d["expert_layers"]
             + 5 * rows * d["d_model"]) * BF16)


def param_count(d: dict, vocab_rows: int) -> int:
    """All parameters that hold memory on this chip (norms left out)."""
    expert_layer = (d["d_model"] * d["experts"]
                    + swiglu_params(d["d_model"], d["d_expert"])
                    * (d["shared_experts"] + d["experts_held"]))
    return (blocks(d) * mla_params(d)
            + d["dense_layers"] * swiglu_params(d["d_model"], d["d_ff"])
            + d["expert_layers"] * expert_layer
            + 2 * d["d_model"] * d["d_model"] * d["mtp_layers"]
            + 2 * d["d_model"] * vocab_rows)
