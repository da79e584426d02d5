"""Operations and bytes a hybrid decoder needs whose blocks are ONE
sublayer each — a Mamba-2 mixer, grouped-query attention over the whole
causal prefix, or a non-gated (two-product) expert feed-forward beside a
shared expert: the counterpart of ``flops.py``, ``flops_sparse.py`` and
``flops_mixed.py`` for configurations whose ``dims``
(``builders/nemotron_h.py:dims``) carry ``layers, mamba_layers,
attn_layers, expert_layers, d_model, heads, kv_heads, d_head,
window_layout`` (one entry an ATTENTION layer: None), ``ssm_heads,
ssm_head_dim, ssm_groups, ssm_state, ssm_chunk, d_expert, d_shared,
experts, experts_held, top_k, vocab, held_assignments_per_token_layer``
(the nominal ``k * held / experts``) and ``held_assignments_per_step``
(what the program's steps counted, or None: ``flops_sparse.counted``
lays it over the nominal).

Counted is what the chip's share of the model needs, whatever implements
it: the scan by its chunked form's four products, attention's pairs
under the causal mask, the experts HELD here on the assignments routed
to them, the router over all experts, the vocabulary's slice.
Recomputation, padding, the repeat of grouped keys, the convolution and
the norms (no matrix product) are never counted.  Byte counts are for
bf16 (2 bytes) and float32 ``dt`` (4).
"""

from __future__ import annotations

from benchmark.harness import flops_mixed

BF16, F32 = 2, 4


def mixer_params(d: dict) -> int:
    """Matrix-multiply weights of one mixer: the in-projection to ``z |
    xBC | dt`` and the out-projection."""
    d_in = d["ssm_heads"] * d["ssm_head_dim"]
    gn = d["ssm_groups"] * d["ssm_state"]
    return d["d_model"] * (2 * d_in + 2 * gn + d["ssm_heads"]) + (
        d_in * d["d_model"])


def scan_flops_per_token(d: dict) -> float:
    """One layer's scan, forward, a token, in chunks of ``Q``: ``C B^T``
    a group and ``(C B^T o L)(dt o X)`` a head over the causal half of
    the chunk (``Q N G + Q P H``), the state built and read (``2 N P H``
    each)."""
    Q, H, P = d["ssm_chunk"], d["ssm_heads"], d["ssm_head_dim"]
    G, N = d["ssm_groups"], d["ssm_state"]
    return float(Q * N * G + Q * P * H + 4 * N * P * H)


def ssd_scan_cost(d: dict, tokens: int):
    """(flops, bytes) a step of every mixer's scan, forward plus
    backward (three times the forward's products).  Bytes a token and
    layer: X, B, C, dt read and Y written forward; those and dY read and
    dX, dB, dC, d-dt written backward."""
    hp = d["ssm_heads"] * d["ssm_head_dim"]
    gn = d["ssm_groups"] * d["ssm_state"]
    dt = d["ssm_heads"] * F32
    fwd = (2 * hp + 2 * gn) * BF16 + dt
    bwd = (2 * hp + 2 * gn) * BF16 + dt + (hp + 2 * gn) * BF16 + dt
    n = tokens * d["mamba_layers"]
    return 3.0 * scan_flops_per_token(d) * n, float(fwd + bwd) * n


def expert_params(d_model: int, width: int) -> int:
    """One non-gated expert: up, down."""
    return 2 * d_model * width


def forward_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward FLOPs a token needs on this chip's share."""
    mamba = 2.0 * mixer_params(d) + scan_flops_per_token(d)
    attn = 2.0 * flops_mixed.attn_params(d) + (
        flops_mixed.scores_flops_per_token(d, seq_len, None))
    experts = (2.0 * d["d_model"] * d["experts"]              # router
               + 2.0 * expert_params(d["d_model"], d["d_shared"])
               + 2.0 * expert_params(d["d_model"], d["d_expert"])
               * d["held_assignments_per_token_layer"])
    head = 2.0 * d["d_model"] * d["vocab"]
    return (d["mamba_layers"] * mamba + d["attn_layers"] * attn
            + d["expert_layers"] * experts + head)


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward pass."""
    return 3.0 * forward_flops_per_token(d, seq_len)


def held_assignments_per_step(d: dict, tokens: int) -> float:
    return d["held_assignments_per_token_layer"] * tokens * d["expert_layers"]


def held_experts_cost(d: dict, tokens: int):
    """(flops, bytes) a step of the held experts' TWO products, forward
    plus backward, over all expert layers: 6 FLOPs per weight and
    assignment.  Bytes as ``flops_sparse.held_experts_cost`` counts
    them: every held expert's weights read forward, read backward and
    their gradient written; a row read and written forward and read
    twice, written once backward."""
    w = expert_params(d["d_model"], d["d_expert"])
    rows = held_assignments_per_step(d, tokens)
    return (6.0 * w * rows,
            (3 * w * d["experts_held"] * d["expert_layers"]
             + 5 * rows * d["d_model"]) * BF16)


def param_count(d: dict, vocab_rows: int) -> int:
    """All parameters that hold memory on this chip (norms, the
    convolution and the scan's per-head leaves left out)."""
    experts = (d["d_model"] * d["experts"]
               + expert_params(d["d_model"], d["d_shared"])
               + expert_params(d["d_model"], d["d_expert"])
               * d["experts_held"])
    return (d["mamba_layers"] * mixer_params(d)
            + d["attn_layers"] * flops_mixed.attn_params(d)
            + d["expert_layers"] * experts + 2 * d["d_model"] * vocab_rows)
