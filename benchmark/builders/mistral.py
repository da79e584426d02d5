"""Builder for Mistral-family dense configurations (HF ``MistralConfig``
keys): RMSNorm, rotary positions (half-split), grouped-query attention,
SwiGLU, untied head — the ``TransformerConfig`` axes the program's Llama
loader (``integrations/llama.py``) uses, served by the paged engine.

``attn_impl="local"`` because a paged engine refuses ``"flash"``
(``serving/engine.py``: whole-prompt flash prefill and chunked dense
prefill would differ in accumulation order).  The engine is built from
``ServingEngine(model, variables, **engine)`` directly:
``BYTEPS_SERVE_MODEL`` cannot say bf16, RoPE, SwiGLU or GQA.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"], "heads": heads,
            "kv_heads": cfg["num_key_value_heads"],
            "d_head": cfg.get("head_dim") or cfg["hidden_size"] // heads,
            "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "mlp": "swiglu", "tied": bool(cfg["tie_word_embeddings"])}


def transformer_config(cfg: dict):
    import jax.numpy as jnp

    from byteps_tpu.models import TransformerConfig

    if cfg["hidden_act"] != "silu" or cfg["sliding_window"] is not None:
        raise ValueError("this builder maps hidden_act=silu without a "
                         "sliding window only")
    d = dims(cfg)
    return TransformerConfig(
        vocab_size=d["vocab"], num_layers=d["layers"],
        num_heads=d["heads"], num_kv_heads=d["kv_heads"],
        d_model=d["d_model"], d_ff=d["d_ff"], head_dim=d["d_head"],
        max_seq_len=cfg["max_position_embeddings"], dtype=jnp.bfloat16,
        attn_impl="local", norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        pos_emb="rope", rope_theta=cfg["rope_theta"], mlp="swiglu",
        tie_embeddings=d["tied"])


def build_model(cfg: dict):
    from byteps_tpu.models import Transformer

    return Transformer(transformer_config(cfg))


def build_serving(cfg: dict, seed: int):
    """``(model, variables)`` with bf16 weights made on the device from
    the seed in one jitted call."""
    import jax.numpy as jnp

    from benchmark.harness import weights

    model = build_model(cfg)
    params = weights.make_params(weights.param_shapes(model), seed,
                                 jnp.bfloat16)
    return model, {"params": params}
