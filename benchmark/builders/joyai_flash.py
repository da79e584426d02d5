"""Builder for DeepSeek-V3-family configurations (the HF config keys of
``configs/joyai-llm-flash-l5-ep16.json``): latent attention, a leading
dense layer, expert layers of which this chip holds a slice, a
multi-token-prediction module.

Maps the published keys onto the program's ``TransformerConfig`` through
the program's own ``integrations/deepseek_v3.py:deepseek_v3_config`` and
builds the data-parallel train step through ``Transformer`` +
``lm_loss_fn(fused_head=True)`` + ``make_data_parallel_step``, as the
GPT-2 builder does.  The file's ``n_routed_experts`` is the count HELD
here (experts 0 .. count-1); the router keeps the published count
(``reduced_from``).

Before it hands the step over, ``build_training`` holds the program's
blocks, router and expert layers at the seeded weights to the plain
reference's, one at a time (``hold_to_reference``): the train runner
compares the step's first loss only, and at seeded weights that loss
cannot see a lower router precision, a dropped assignment or a missing
shared expert.
"""

from __future__ import annotations

import functools
import types


class ReferenceMismatch(Exception):
    """A block, the router or an expert layer of the program leaves the
    plain reference by more than the mix's ``reference_limits``."""


def vocab_rows(cfg: dict) -> int:
    """Rows of the embedding table and head as built: the padded count."""
    return cfg["assumed"]["vocab_rows_padded"]


def experts_published(cfg: dict) -> int:
    return cfg["reduced_from"]["n_routed_experts"]


def dims(cfg: dict) -> dict:
    """Sizes for ``harness/flops_sparse.py``.  The held assignments a
    step are the mean of the program's counter ``moe.assignments_held``
    over the steps it counted (``training/step.py:flush_step_counts``:
    every step of this process, warm, timed and traced); before any step,
    ``None`` — the counts then use the nominal ``k * held / experts`` a
    token and layer (random weights route far from evenly)."""
    from benchmark.harness import manifest

    expert_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    mtp = cfg["num_nextn_predict_layers"]
    counted = counted_assignments()
    if counted["steps"]:
        # assignments == rows_computed: nothing was dropped in any step
        manifest.note(event="held_assignments", **counted)
    return {
        "layers": cfg["num_hidden_layers"], "mtp_layers": mtp,
        "dense_layers": cfg["first_k_dense_replace"],
        "expert_layers": expert_layers + mtp,
        "d_model": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "d_nope": cfg["qk_nope_head_dim"], "d_rope": cfg["qk_rope_head_dim"],
        "d_v": cfg["v_head_dim"], "d_ff": cfg["intermediate_size"],
        "d_expert": cfg["moe_intermediate_size"],
        "experts": experts_published(cfg),
        "experts_held": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "shared_experts": cfg["n_shared_experts"],
        "vocab": cfg["vocab_size"],
        "held_assignments_per_token_layer": (
            cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / experts_published(cfg)),
        "held_assignments_per_step": counted["held"]}


def counted_assignments() -> dict:
    """What the program's steps counted so far (its registry): mean held
    assignments a step, and the steps, assignments and computed rows in
    all — the last two equal unless an assignment was dropped."""
    from byteps_tpu.observability.metrics import get_registry
    from byteps_tpu.training.step import flush_step_counts

    flush_step_counts()
    reg = get_registry()
    steps, held, rows = (reg.counter(n).value for n in (
        "train.steps_counted", "moe.assignments_held", "moe.rows_computed"))
    return {"steps": steps, "assignments": held, "rows_computed": rows,
            "held": held / steps if steps else None}


def transformer_config(cfg: dict, job: dict):
    import jax.numpy as jnp

    from benchmark.harness import manifest

    try:
        from byteps_tpu.integrations.deepseek_v3 import deepseek_v3_config
    except ImportError as e:
        raise manifest.ManifestError(
            "this program cannot build the configuration: it has no "
            f"byteps_tpu/integrations/deepseek_v3.py ({e})") from e

    hf = types.SimpleNamespace(**{
        k: v for k, v in cfg.items() if not isinstance(v, (dict, list))})
    hf.n_routed_experts = experts_published(cfg)
    return deepseek_v3_config(
        hf, dtype=jnp.bfloat16, vocab_size=vocab_rows(cfg),
        attn_impl=job["attn_impl"], remat=bool(job.get("remat")),
        moe_held=(0, cfg["n_routed_experts"]),
        mtp_loss_weight=cfg["assumed"]["mtp_loss_weight"])


def build_step(cfg: dict, job: dict, mesh):
    """``(step, parameter shapes)``: the program's jitted data-parallel
    step for this configuration and job, nothing placed on a device yet
    (``aot_check.py`` lowers it for a chip that is only described)."""
    import optax

    from benchmark.harness import weights
    from byteps_tpu.models import Transformer
    from byteps_tpu.training import lm_loss_fn, make_data_parallel_step

    model = Transformer(transformer_config(cfg, job))
    step = make_data_parallel_step(
        lm_loss_fn(model, fused_head=job["fused_head"]),
        optax.adamw(job["learning_rate"]), mesh,
        partition_bytes=job["partition_bytes"])
    return step, weights.param_shapes(model, seq_len=256)


def gap_programs(cfg: dict, job: dict):
    """The two jitted comparisons of ``reference_gaps``:
    ``block_gap(p, x, y, experts)`` for a block with float32 parameters
    ``p``, the reference's input ``x`` and output ``y [T, d]``, and
    ``layer_gaps(m, h)`` for an expert layer's parameters ``m`` and the
    reference's normalised input ``h`` (``aot_check_grouped.py`` lowers
    both for the described chip)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import manifest
    from byteps_tpu.models.transformer import Block, ExpertLayer
    from byteps_tpu.parallel import moe

    ref = manifest.load_module("reference", cfg["reference"])
    tc = transformer_config(cfg, dict(job, remat=False))
    c = ref.sizes(cfg)
    f32 = jnp.float32
    highest = functools.partial(jax.default_matmul_precision, "highest")

    @functools.partial(jax.jit, static_argnums=3)
    def block_gap(p, x, y, experts):
        got = Block(tc, experts=experts).apply(
            {"params": p}, x[None].astype(tc.dtype))[0].astype(f32)
        rms = jnp.sqrt(jnp.mean(jnp.sum(jnp.square(y - x), axis=-1)))
        return jnp.median(jnp.linalg.norm(got - y, axis=-1)) / rms

    def route(m, h):
        return moe.route(h, m["router"]["kernel"], m["router"]["bias"],
                         tc.moe_top_k, tc.moe_scale)

    @jax.jit
    def layer_gaps(m, h):
        idx, w = route(m, h)
        with highest():
            r_idx, r_w = ref.router(h, m["router"], c)
        same = idx[:, :, None] == r_idx[:, None, :]
        both = same.any(-1)
        w_ref = jnp.sum(jnp.where(same, r_w[:, None, :], 0.0), axis=-1)
        weight_gap = jnp.max(jnp.where(both, jnp.abs(w - w_ref), 0.0)) / (
            tc.moe_scale / tc.moe_top_k)
        held = h.astype(tc.dtype)
        got = ExpertLayer(tc).apply({"params": m}, held[None])[0]
        with highest():
            want = ref.routed(held.astype(f32), m, c,
                              route(m, held)) + ref.shared(
                                  held.astype(f32), m)
        err = jnp.linalg.norm(got.astype(f32) - want, axis=-1)
        rms = jnp.sqrt(jnp.mean(jnp.sum(jnp.square(want), axis=-1)))
        return 1.0 - jnp.mean(both), weight_gap, jnp.max(err) / rms

    return block_gap, layer_gaps


def reference_gaps(cfg: dict, job: dict, params, tokens) -> dict:
    """The program's blocks against the plain reference's, one at a time
    on the reference's OWN states (an error cannot ride from block to
    block), on the sequence ``tokens [T]`` at the weights under test.
    The step's loss cannot tell these apart — a mean over thousands of
    targets at seeded weights moves by 1e-4 under a bf16 router or a
    missing shared expert — so each is held to a limit of its own:

    ``block_gap``           a whole block (latent attention through the
                            flash kernel, feed-forward or expert layer)
                            as the step computes it: the median token's
                            error over the RMS norm of what the block
                            adds to a token (the median, because inside
                            a block a near-tie may flip a token's
                            experts on rounded attention output).
    ``router_flip_share``   the program's ``route`` on the reference's
    ``router_weight_gap``   float32 input: the share of its choices the
                            reference did not make, and the largest gap
                            of a weight both chose, over ``scale / k``.
    ``expert_worst_token``  the expert layer on the input as the program
                            holds it, the reference given the program's
                            own choice (a flipped near-tie is the
                            router's to answer for): the worst token's
                            error over the tokens' RMS norm — one
                            dropped assignment, a wrong weight, a
                            missing shared expert.

    Returns the worst of each over the blocks; notes every block's."""
    import jax

    from benchmark.harness import manifest

    ref = manifest.load_module("reference", cfg["reference"])
    block_gap, layer_gaps = gap_programs(cfg, job)
    highest = functools.partial(jax.default_matmul_precision, "highest")
    c = ref.sizes(cfg)
    names = ("block_gap", "router_flip_share", "router_weight_gap",
             "expert_worst_token")
    per_block = []
    states = ref.block_states(params, tokens, cfg["num_hidden_layers"], c)
    while True:
        with highest():     # the reference's side only
            state = next(states)
        if len(state) == 2:                 # the states the head reads
            break
        p, x, y, h = state
        gaps = [block_gap(p, x, y, "moe" in p)]
        if "moe" in p:
            gaps += layer_gaps(p["moe"], h)
        per_block.append(dict(zip(names, map(float, gaps))))
    worst = {n: max(b[n] for b in per_block if n in b) for n in names}
    manifest.note(event="reference_blocks", per_block=per_block)
    return worst


def hold_to_reference(cfg: dict, job: dict, params, tokens) -> None:
    """``reference_gaps`` against the mix's ``reference_limits``; the
    numbers beside their limits are the last thing a run at fault says
    before ``ReferenceMismatch`` ends it."""
    from benchmark.harness import manifest

    limits = job["reference_limits"]
    worst = reference_gaps(cfg, job, params, tokens)
    over = sorted(n for n, v in worst.items() if not v <= limits[n])
    manifest.note(event="reference_limits", over=over, **{
        n: {"value": v, "limit": limits[n]} for n, v in worst.items()})
    if over:
        raise ReferenceMismatch(
            f"{over} over the limit: " + ", ".join(
                f"{n} {worst[n]:.3g} > {limits[n]:.3g}" for n in over))


def build_training(cfg: dict, job: dict, mesh, seed: int):
    """``(step, state, batches, meta)``: the jitted data-parallel step,
    its state (float32 master parameters from the seed, AdamW moments)
    replicated over ``mesh``, and a ring of distinct token batches made
    on the device from the seed, ids drawn from the vocabulary's slice —
    after the program's blocks at those weights were held to the
    reference's (``hold_to_reference``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.harness import weights
    from byteps_tpu.training.step import create_train_state

    step, shapes = build_step(cfg, job, mesh)
    T = job["seq_len"]
    global_batch = job["per_chip_batch"] * mesh.size
    key = jax.random.PRNGKey(seed)

    @functools.partial(jax.jit, out_shardings=NamedSharding(mesh, P()))
    def make_state(key):
        params = weights.make_tree(shapes, key, jnp.float32)
        return create_train_state(params, step.tx)

    @functools.partial(
        jax.jit, out_shardings=NamedSharding(mesh, P(mesh.axis_names)))
    def make_batch(key):
        return {"tokens": jax.random.randint(
            key, (global_batch, T), 0, cfg["vocab_size"])}

    state = make_state(jax.random.fold_in(key, 0))
    batches = [make_batch(jax.random.fold_in(key, 1 + i))
               for i in range(job["batch_ring"])]
    hold_to_reference(cfg, job, state.params, batches[0]["tokens"][0])
    return step, state, batches, {
        "global_batch": global_batch, "seq_len": T,
        "tokens_per_step": global_batch * T}
