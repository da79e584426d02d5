"""DeepSeek-V3-family architecture compatibility: map a published
``config.json`` of the family (latent attention, a sigmoid-scored
bias-corrected top-k router over fine-grained experts beside shared
ones, leading dense layers, multi-token-prediction modules) onto the
framework's ``TransformerConfig``.

The family's equations are published (DeepSeek-V2 report section 2.1:
latent attention; DeepSeek-V3 report sections 2.1.2 and 2.2: the router
and multi-token prediction); ``models/transformer.py`` implements the
training path.  Config axes the framework does not implement raise here
rather than silently diverging: grouped routing (``n_group`` /
``topk_group`` above 1), any scoring but ``sigmoid`` / ``noaux_tc``,
``rope_scaling`` (YaRN's mscale), biased projections, an expert layer on
every n-th block only.  No weight converter: nothing of the family has
been loaded from a checkpoint here.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..models.transformer import TransformerConfig

__all__ = ["deepseek_v3_config"]

_REQUIRED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
             "norm_topk_prob": True, "rope_scaling": None,
             "attention_bias": False, "hidden_act": "silu",
             "tie_word_embeddings": False}


def deepseek_v3_config(hf_config, dtype=jnp.float32, **overrides):
    """TransformerConfig mirroring an HF config of the family.
    ``overrides`` carry what a deployment sets: ``moe_held`` (the slice
    of experts this rank holds), ``vocab_size`` (a padded table),
    ``attn_impl``, ``remat``, ``mtp_loss_weight``."""
    for key, want in _REQUIRED.items():
        got = getattr(hf_config, key, want)
        if got != want:
            raise ValueError(
                f"unsupported {key}={got!r}: the framework builds "
                f"{key}={want!r} only")
    kw = dict(
        vocab_size=hf_config.vocab_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        d_model=hf_config.hidden_size,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        dtype=dtype, causal=True, norm="rmsnorm",
        norm_eps=hf_config.rms_norm_eps, use_bias=False,
        tie_embeddings=False, pos_emb="rope", mlp="swiglu",
        rope_theta=float(hf_config.rope_theta),
        attn_kind="mla",
        q_lora_rank=hf_config.q_lora_rank,
        kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        rope_interleave=bool(getattr(hf_config, "rope_interleave", False)),
        moe_experts=hf_config.n_routed_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_d_ff=hf_config.moe_intermediate_size,
        moe_shared=hf_config.n_shared_experts,
        moe_scale=float(hf_config.routed_scaling_factor),
        dense_layers=hf_config.first_k_dense_replace,
        mtp_layers=getattr(hf_config, "num_nextn_predict_layers", 0),
    )
    kw.update(overrides)
    return TransformerConfig(**kw)
