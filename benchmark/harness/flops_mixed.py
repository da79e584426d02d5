"""Operations and bytes a grouped-query decoder needs whose layers mix
attention kinds — some attend the whole causal prefix, some a sliding
window — with an expert layer in every block: the counterpart of
``flops.py`` (dense, one attention kind) and ``flops_sparse.py`` (latent
attention) for configurations whose ``dims``
(``builders/smallthinker.py:dims``) carry ``layers, expert_layers,
d_model, heads, kv_heads, d_head, window_layout`` (per layer: its window
or None), ``d_expert, experts, experts_held, top_k, vocab,
held_assignments_per_token_layer`` (the nominal ``k * held / experts``)
and ``held_assignments_per_step`` (what the program's steps counted, or
None: ``flops_sparse.counted`` lays it over the nominal).

Counted is what the chip's share of the model needs, whatever
implements it: the pairs of positions inside each layer's band, the
experts HELD here on the assignments routed to them, the router over
all experts, the vocabulary's slice.  Recomputation, padding and the
repeat of grouped keys are an implementation's choice and are never
counted.  Byte counts are for bf16 (2 bytes).
"""

from __future__ import annotations

BF16 = 2


def band_pairs(seq_len: int, window) -> float:
    """Pairs (query, key) inside one sequence's band: half the square
    under the causal mask; with a window the first ``W`` queries see
    half a ``W`` square and every later one ``W`` keys."""
    if window is None or window >= seq_len:
        return seq_len * seq_len / 2.0
    return window * window / 2.0 + (seq_len - window) * float(window)


def attn_params(d: dict) -> int:
    """Matrix-multiply weights of one attention sublayer: q and o at the
    query heads, k and v at the key-value heads."""
    return d["d_model"] * d["d_head"] * 2 * (d["heads"] + d["kv_heads"])


def expert_params(d: dict) -> int:
    """One gated expert: gate, up, down."""
    return 3 * d["d_model"] * d["d_expert"]


def scores_flops_per_token(d: dict, seq_len: int, window) -> float:
    """QK^T and PV of one layer, forward, per token."""
    return 2.0 * 2 * d["heads"] * d["d_head"] * band_pairs(
        seq_len, window) / seq_len


def forward_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward FLOPs a token needs on this chip's share."""
    scores = sum(scores_flops_per_token(d, seq_len, w)
                 for w in d["window_layout"])
    block = (2.0 * attn_params(d) + 2.0 * d["d_model"] * d["experts"]
             + 2.0 * expert_params(d)
             * d["held_assignments_per_token_layer"])
    head = 2.0 * d["d_model"] * d["vocab"]
    return scores + d["layers"] * block + head


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward: three times the forward pass."""
    return 3.0 * forward_flops_per_token(d, seq_len)


def gqa_flash_cost(d: dict, batch: int, seq_len: int, window):
    """(flops, bytes) of one layer's attention inside its band, forward
    plus backward, as the flash algorithm needs them — the 7 products of
    ``flops_sparse.mla_flash_cost``'s convention (QK^T, PV; backward S
    again, dP, dV, dQ, dK), each ``2 * B * H * pairs * d_head``, so the
    share means the same in both cells.  Bytes: q, o, do and dq at the
    query heads, k, v, dk and dv at the key-value heads — forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq,
    dk, dv."""
    flops = 7 * 2.0 * batch * d["heads"] * d["d_head"] * band_pairs(
        seq_len, window)
    per_pos = 6 * d["heads"] + 6 * d["kv_heads"]
    return flops, per_pos * d["d_head"] * batch * seq_len * BF16


def param_count(d: dict, vocab_rows: int) -> int:
    """All parameters that hold memory on this chip (norms left out)."""
    block = (attn_params(d) + d["d_model"] * d["experts"]
             + expert_params(d) * d["experts_held"])
    return d["layers"] * block + 2 * d["d_model"] * vocab_rows
