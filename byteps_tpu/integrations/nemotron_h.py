"""Nemotron-H-family architecture compatibility (``model_type:
nemotron_h``): map a published ``config.json`` of the family — a stack
of one-sublayer blocks whose kinds a pattern string gives (``M`` a
Mamba-2 mixer, ``*`` grouped-query attention with no position encoding,
``E`` sigmoid-routed non-gated relu^2 experts beside a shared expert)
— onto the framework's ``TransformerConfig``.

The family's equations are in its report (Nemotron-H, arXiv:2504.03624)
and its published modelling code; ``models/transformer.py`` implements
the training path (``SublayerBlock``, ``Mamba2Mixer``,
``ops/ssd_scan.py``).  Config axes the framework does not implement
raise here rather than silently diverging: group-limited routing
(``n_group`` / ``topk_group`` != 1), any projection bias, tied
embeddings, a float32 residual stream, unnormalised top-k weights, a
pattern letter outside ``M E *`` (the family's dense
feed-forward ``-`` among them: no published model here has one), a pattern whose length is not the
depth.  No weight converter: nothing of the family has been loaded from
a checkpoint here.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..models.transformer import TransformerConfig

__all__ = ["nemotron_h_config"]

_REQUIRED = {"n_group": 1, "topk_group": 1, "mamba_proj_bias": False,
             "attention_bias": False, "mlp_bias": False, "use_bias": False,
             "tie_word_embeddings": False, "residual_in_fp32": False,
             "norm_topk_prob": True, "use_conv_bias": True,
             "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2"}
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def nemotron_h_config(hf_config, dtype=jnp.float32, **overrides):
    """TransformerConfig mirroring an HF config of the family.
    ``overrides`` carry what a deployment sets: ``moe_held`` (the slice
    of experts this rank holds), ``vocab_size`` (a slice of the table),
    ``attn_impl``, ``remat``."""
    for key, want in _REQUIRED.items():
        got = getattr(hf_config, key, want)
        if got != want:
            raise ValueError(
                f"unsupported {key}={got!r}: the framework builds "
                f"{key}={want!r} only")
    depth = hf_config.num_hidden_layers
    pattern = hf_config.hybrid_override_pattern
    if len(pattern) != depth:
        raise ValueError(
            f"unsupported hybrid_override_pattern: {len(pattern)} letters "
            f"for {depth} layers")
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise ValueError(
            f"unsupported hybrid_override_pattern letters {unknown}: the "
            f"framework builds {sorted(KINDS)} only")
    width = hf_config.moe_intermediate_size
    shared = (hf_config.n_shared_experts
              * hf_config.moe_shared_expert_intermediate_size)
    if shared % width:
        raise ValueError(
            f"unsupported moe_shared_expert_intermediate_size: {shared} "
            f"in all is no multiple of the routed experts' {width}")
    kw = dict(
        vocab_size=hf_config.vocab_size,
        num_layers=depth,
        layer_kinds=tuple(KINDS[c] for c in pattern),
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        head_dim=hf_config.head_dim,
        d_model=hf_config.hidden_size,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        dtype=dtype, causal=True, norm="rmsnorm",
        norm_eps=hf_config.layer_norm_epsilon, use_bias=False,
        tie_embeddings=False, pos_emb="none", mlp="relu2",
        ssm_heads=hf_config.mamba_num_heads,
        ssm_head_dim=hf_config.mamba_head_dim,
        ssm_groups=hf_config.n_groups,
        ssm_state=hf_config.ssm_state_size,
        ssm_conv=hf_config.conv_kernel,
        ssm_chunk=hf_config.chunk_size,
        moe_experts=hf_config.n_routed_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_d_ff=width, moe_shared=shared // width,
        moe_scale=float(hf_config.routed_scaling_factor),
        moe_scoring="sigmoid", moe_act="relu2", dense_layers=0,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)
