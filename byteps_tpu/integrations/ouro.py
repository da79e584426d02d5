"""Ouro-family architecture compatibility (``model_type: ouro``, the
looped language model of "Scaling Latent Reasoning via Looped Language
Models", 2025): map a published ``config.json`` of the family onto the
framework's ``TransformerConfig``.

A layer is RoPE multi-head attention and a SwiGLU feed-forward, each
sublayer with a norm on its input AND on its output (sandwich norm):

    h = x + N2(attn(N1(x)))          out = h + N4(ffn(N3(h)))

and the whole stack of ``num_hidden_layers`` runs ``total_ut_steps``
times a token with one set of weights, the final norm after every pass
and its output fed to the next (``TransformerConfig.loop_steps``).  An
exit gate reads each pass's normed state; the loss is the exit
distribution's expected cross-entropy less its entropy's weight times
the entropy (``training/step.py:exit_weighted_loss``).

Config axes the framework does not implement raise here rather than
silently diverging: a sliding window, a ``layer_types`` entry other than
``full_attention`` (or a list shorter than the depth), ``rope_scaling``,
a tied table, an activation other than SiLU, fewer than one pass.  No
weight converter: nothing of the family has been loaded from a
checkpoint here.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..models.transformer import TransformerConfig

__all__ = ["ouro_config"]

_REQUIRED = {"use_sliding_window": False, "sliding_window": None,
             "rope_scaling": None, "tie_word_embeddings": False,
             "hidden_act": "silu"}


def ouro_config(hf_config, dtype=jnp.float32, **overrides):
    """TransformerConfig mirroring an HF config of the family.
    ``overrides`` carry what a deployment or a job sets: ``attn_impl``,
    ``remat``.  The entropy's weight, which the published config does
    not hold, is ``training/step.py:EXIT_ENTROPY_WEIGHT``."""
    for key, want in _REQUIRED.items():
        got = getattr(hf_config, key, want)
        if got != want:
            raise ValueError(
                f"unsupported {key}={got!r}: the framework builds "
                f"{key}={want!r} only")
    depth = hf_config.num_hidden_layers
    types = list(getattr(hf_config, "layer_types", None)
                 or ["full_attention"] * depth)
    if len(types) < depth:
        raise ValueError(
            f"unsupported layer_types: {len(types)} entries for {depth} "
            "layers")
    unknown = sorted(set(types[:depth]) - {"full_attention"})
    if unknown:
        raise ValueError(f"unsupported layer_types entries {unknown}: the "
                         "framework builds full_attention only")
    steps = hf_config.total_ut_steps
    if steps < 1:
        raise ValueError(f"unsupported total_ut_steps={steps}: a looped "
                         "model makes at least one pass")
    kw = dict(
        vocab_size=hf_config.vocab_size,
        num_layers=depth,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        head_dim=getattr(hf_config, "head_dim", None),
        d_model=hf_config.hidden_size,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        dtype=dtype, causal=True, norm="rmsnorm",
        norm_eps=hf_config.rms_norm_eps, use_bias=False,
        tie_embeddings=False, pos_emb="rope",
        rope_theta=float(hf_config.rope_theta), mlp="swiglu",
        sandwich_norm=True, loop_steps=steps,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)
