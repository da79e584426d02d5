"""Operations and bytes the algorithms need, from shapes alone.

``dims`` is the normalised description a builder gives of a dense
decoder (``builders/<name>.py:dims``): ``layers, d_model, heads,
kv_heads, d_head, d_ff, vocab, mlp ("gelu" | "swiglu"), tied``.
Recomputation and padding are an implementation's choice and are never
counted: ``vocab`` is the published vocabulary, not the padded table.
All byte counts are for bf16 activations and weights (2 bytes).
"""

from __future__ import annotations

import math

BF16 = 2


def linear_params_per_layer(d: dict) -> int:
    """Matrix-multiply weights of one block (biases and norms carry no
    matmul)."""
    attn = d["d_model"] * d["d_head"] * (2 * d["heads"] + 2 * d["kv_heads"])
    mlp = d["d_model"] * d["d_ff"] * (3 if d["mlp"] == "swiglu" else 2)
    return attn + mlp


def head_params(d: dict) -> int:
    return d["d_model"] * d["vocab"]


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward of a causal LM per trained token: 6 FLOPs per
    matmul weight (the head counts once, tied or not) plus causal
    attention — QK^T and PV are 2*d_attn*T FLOPs per token forward over
    the full square, half of it under the causal mask, three times that
    with the backward pass."""
    linear = 6.0 * (d["layers"] * linear_params_per_layer(d)
                    + head_params(d))
    d_attn = d["heads"] * d["d_head"]
    attn = 3.0 * (2 * 2 * d_attn * seq_len / 2.0) * d["layers"]
    return linear + attn


def flash_attention_cost(d: dict, batch: int, seq_len: int):
    """(flops, bytes) of one layer's causal attention, forward plus
    backward, as the flash algorithm needs them: 2 matmuls forward and 5
    backward (S recomputed once, dV, dP, dQ, dK), each 2*B*H*T*T*D over
    the square, half under the mask.  Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    q_elems = batch * seq_len * d["heads"] * d["d_head"]
    kv_elems = batch * seq_len * d["kv_heads"] * d["d_head"]
    per_matmul = 2.0 * batch * d["heads"] * seq_len * seq_len * d["d_head"]
    flops = (2 + 5) * per_matmul / 2.0
    fwd_bytes = (2 * q_elems + 2 * kv_elems) * BF16
    bwd_bytes = (4 * q_elems + 4 * kv_elems) * BF16
    return flops, fwd_bytes + bwd_bytes


def fused_ce_cost(d: dict, rows: int):
    """(flops, bytes) of the LM head with cross-entropy, forward plus
    backward, on ``rows`` positions: logits, dx and dw are one
    ``rows x d_model x vocab`` matmul each.  Bytes: the activations and
    the head weight read forward and backward, dx and dw written."""
    flops = 3 * 2.0 * rows * d["d_model"] * d["vocab"]
    x = rows * d["d_model"] * BF16
    w = d["d_model"] * d["vocab"] * BF16
    return flops, 3 * x + 3 * w


def kv_bytes_per_token(d: dict) -> int:
    """Bytes of K and V one position holds across all layers."""
    return 2 * d["layers"] * d["kv_heads"] * d["d_head"] * BF16


def paged_decode_read_bytes(d: dict, context_lens, block: int) -> float:
    """K/V bytes the paged decode kernel has to read, over all layers,
    to attend each of ``context_lens`` (one entry per generated token:
    the positions it attends, itself included) — whole blocks, because
    a block is the unit of the pool."""
    per_pos = kv_bytes_per_token(d)
    return float(sum(math.ceil(c / block) * block for c in context_lens)
                 * per_pos)


def decode_weight_bytes(d: dict) -> int:
    """Weight bytes one decode step streams: every block's matrices and
    the LM head (the embedding table is only indexed)."""
    return (d["layers"] * linear_params_per_layer(d)
            + head_params(d)) * BF16


def decode_tick_bytes(d: dict, context_lens, block: int) -> float:
    """Bytes one decode tick has to move: the weights once, plus the
    K/V of every running request's context."""
    return decode_weight_bytes(d) + paged_decode_read_bytes(
        d, context_lens, block)


def param_count(d: dict) -> int:
    """All parameters that hold memory: blocks, head, and the embedding
    table when it is not tied to the head."""
    n = d["layers"] * linear_params_per_layer(d) + head_params(d)
    if not d["tied"]:
        n += d["d_model"] * d["vocab"]
    return n
