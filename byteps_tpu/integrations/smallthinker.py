"""SmallThinker-family architecture compatibility: map a published
``config.json`` of the family (grouped-query attention whose layers
alternate between a sliding window with RoPE and the whole causal prefix
with no position encoding, a softmax top-k router placed BEFORE
attention over ReLU-gated experts, no shared expert) onto the
framework's ``TransformerConfig``.

The family's equations are in its report (arXiv:2507.20984) and its
config keys; ``models/transformer.py`` implements the training path.
Config axes the framework does not implement raise here rather than
silently diverging: a router without the softmax
(``moe_primary_router_apply_softmax: false``), unnormalised top-k
weights, ``rope_scaling``, tied embeddings, a layout whose length is not
the depth.  No weight converter: nothing of the family has been loaded
from a checkpoint here.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..models.transformer import TransformerConfig

__all__ = ["smallthinker_config"]

_REQUIRED = {"moe_primary_router_apply_softmax": True,
             "norm_topk_prob": True, "rope_scaling": None,
             "tie_word_embeddings": False}


def smallthinker_config(hf_config, dtype=jnp.float32, **overrides):
    """TransformerConfig mirroring an HF config of the family.
    ``overrides`` carry what a deployment sets: ``moe_held`` (the slice
    of experts this rank holds), ``vocab_size`` (a padded table),
    ``attn_impl``, ``remat``."""
    for key, want in _REQUIRED.items():
        got = getattr(hf_config, key, want)
        if got != want:
            raise ValueError(
                f"unsupported {key}={got!r}: the framework builds "
                f"{key}={want!r} only")
    depth = hf_config.num_hidden_layers
    layouts = {}
    for key in ("sliding_window_layout", "rope_layout"):
        layout = tuple(getattr(hf_config, key))
        if len(layout) != depth:
            raise ValueError(
                f"unsupported {key}: {len(layout)} entries for "
                f"{depth} layers")
        layouts[key] = layout
    window = int(hf_config.sliding_window_size)
    kw = dict(
        vocab_size=hf_config.vocab_size,
        num_layers=depth,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        head_dim=hf_config.head_dim,
        d_model=hf_config.hidden_size,
        d_ff=hf_config.moe_ffn_hidden_size,
        max_seq_len=hf_config.max_position_embeddings,
        dtype=dtype, causal=True, norm="rmsnorm",
        norm_eps=hf_config.rms_norm_eps, use_bias=False,
        tie_embeddings=False, pos_emb="rope", mlp="swiglu",
        rope_theta=float(hf_config.rope_theta),
        attn_window_layout=tuple(
            window if w else None for w in layouts["sliding_window_layout"]),
        rope_layout=tuple(bool(r) for r in layouts["rope_layout"]),
        moe_experts=hf_config.moe_num_primary_experts,
        moe_top_k=hf_config.moe_num_active_primary_experts,
        moe_d_ff=hf_config.moe_ffn_hidden_size,
        moe_shared=0, moe_scale=1.0, moe_scoring="softmax_topk",
        moe_act="relu", moe_router_pre_attn=True, dense_layers=0,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)
