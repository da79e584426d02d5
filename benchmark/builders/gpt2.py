"""Builder for GPT-2-family configurations (HF ``GPT2Config`` keys).

Maps the published keys onto the program's ``TransformerConfig``
through the program's own ``integrations/gpt2.py:gpt2_config`` (pre-norm
LayerNorm with bias, biased projections, learned positions, tanh GELU =
``gelu_new``, head tied to the embedding) and builds the data-parallel
train step the way ``examples/benchmark_byteps.py --model transformer
--attn flash --fused-head --bf16`` does.
"""

from __future__ import annotations

import types


def dims(cfg: dict) -> dict:
    d_model, heads = cfg["n_embd"], cfg["n_head"]
    return {"layers": cfg["n_layer"], "d_model": d_model, "heads": heads,
            "kv_heads": heads, "d_head": d_model // heads,
            "d_ff": cfg["n_inner"] or 4 * d_model,
            "vocab": cfg["vocab_size"], "mlp": "gelu", "tied": True}


def vocab_rows(cfg: dict) -> int:
    """Rows of the embedding table as built: the padded count."""
    return (cfg.get("vocab_rows_padded")
            or cfg["assumed"]["vocab_rows_padded"])


def transformer_config(cfg: dict, attn_impl: str):
    import jax.numpy as jnp

    from byteps_tpu.integrations.gpt2 import gpt2_config

    hf = types.SimpleNamespace(
        vocab_size=cfg["vocab_size"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], n_embd=cfg["n_embd"],
        n_inner=cfg["n_inner"], n_positions=cfg["n_positions"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        activation_function=cfg["activation_function"])
    return gpt2_config(hf, dtype=jnp.bfloat16, vocab_size=vocab_rows(cfg),
                       attn_impl=attn_impl)


def build_step(cfg: dict, job: dict, mesh):
    """``(step, parameter shapes)``: the program's jitted data-parallel
    step for this configuration and job, nothing placed on a device yet
    (``aot_check.py`` lowers it for a chip that is only described)."""
    import optax

    from benchmark.harness import weights
    from byteps_tpu.models import Transformer
    from byteps_tpu.training import lm_loss_fn, make_data_parallel_step

    model = Transformer(transformer_config(cfg, job["attn_impl"]))
    step = make_data_parallel_step(
        lm_loss_fn(model, fused_head=job["fused_head"]),
        optax.adamw(job["learning_rate"]), mesh,
        partition_bytes=job["partition_bytes"])
    return step, weights.param_shapes(model)


def build_training(cfg: dict, job: dict, mesh, seed: int):
    """``(step, state, batches, meta)``: the jitted data-parallel step,
    its state (float32 master parameters from the seed, AdamW moments)
    replicated over ``mesh``, and a ring of distinct token batches made
    on the device from the seed (one repeated batch is memorised in ten
    steps)."""
    import functools

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.harness import weights
    from byteps_tpu.training.step import create_train_state

    step, shapes = build_step(cfg, job, mesh)
    world = mesh.size
    T = job["seq_len"]
    global_batch = job["per_chip_batch"] * world
    replicated = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(seed)

    @functools.partial(jax.jit, out_shardings=replicated)
    def make_state(key):
        import jax.numpy as jnp

        params = weights.make_tree(shapes, key, jnp.float32)
        return create_train_state(params, step.tx)

    @functools.partial(
        jax.jit, out_shardings=NamedSharding(mesh, P(mesh.axis_names)))
    def make_batch(key):
        # targets stay inside the published vocabulary; the padded rows
        # of the table are never a label
        return {"tokens": jax.random.randint(
            key, (global_batch, T), 0, cfg["vocab_size"])}

    state = make_state(jax.random.fold_in(key, 0))
    batches = [make_batch(jax.random.fold_in(key, 1 + i))
               for i in range(job["batch_ring"])]
    return step, state, batches, {
        "global_batch": global_batch, "seq_len": T,
        "tokens_per_step": global_batch * T}
