"""Builder for SmallThinker-family configurations (the HF config keys of
``configs/smallthinker-21b-l4-ep4.json``): grouped-query attention whose
layers alternate between a sliding window with RoPE and the whole causal
prefix with no position encoding, a softmax top-k router that reads the
block's attention input, ReLU-gated experts of which this chip holds a
slice.

Maps the published keys onto the program's ``TransformerConfig`` through
the program's own ``integrations/smallthinker.py:smallthinker_config``
and builds the data-parallel train step through ``Transformer`` +
``lm_loss_fn(fused_head=True)`` + ``make_data_parallel_step``, as the
other builders do.  The file's ``moe_num_primary_experts`` is the count
HELD here (experts 0 .. count-1); the router keeps the published count
(``reduced_from``).  Both layout lists stay as published; the first
``num_hidden_layers`` entries are the layers built.

Before it hands the step over, ``build_training`` holds the program's
blocks, attention sublayers, router and expert layers at the seeded
weights to the plain reference's, one at a time
(``hold_to_reference``): the train runner compares the step's first loss
only, and at seeded weights that loss cannot see a window one position
short, a lower router precision or a dropped assignment.
"""

from __future__ import annotations

import functools
import types


class ReferenceMismatch(Exception):
    """A block, an attention sublayer, the router or an expert layer of
    the program leaves the plain reference by more than the mix's
    ``reference_limits``."""


def vocab_rows(cfg: dict) -> int:
    """Rows of the embedding table and head as built: the padded count."""
    return cfg["assumed"]["vocab_rows_padded"]


def experts_published(cfg: dict) -> int:
    return cfg["reduced_from"]["moe_num_primary_experts"]


def window_layout(cfg: dict) -> list:
    """Per layer built: its window, or None where it attends the whole
    causal prefix."""
    n = cfg["num_hidden_layers"]
    return [cfg["sliding_window_size"] if w else None
            for w in cfg["sliding_window_layout"][:n]]


def dims(cfg: dict) -> dict:
    """Sizes for ``harness/flops_mixed.py`` — and, under the names they
    read, for ``flops.fused_ce_cost`` (one head pass) and
    ``flops_sparse.held_experts_cost`` (three products a held expert).
    The held assignments a step are the mean of the program's counter
    ``moe.assignments_held`` over the steps it counted
    (``training/step.py:flush_step_counts``); before any step, ``None`` —
    the counts then use the nominal ``k * held / experts`` a token and
    layer."""
    from benchmark.harness import manifest

    counted = counted_assignments()
    if counted["steps"]:
        # assignments == rows_computed: nothing was dropped in any step
        manifest.note(event="held_assignments", **counted)
    layers = cfg["num_hidden_layers"]
    return {
        "layers": layers, "expert_layers": layers,
        "d_model": cfg["hidden_size"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "d_head": cfg["head_dim"],
        "window_layout": window_layout(cfg),
        "d_expert": cfg["moe_ffn_hidden_size"],
        "experts": experts_published(cfg),
        "experts_held": cfg["moe_num_primary_experts"],
        "top_k": cfg["moe_num_active_primary_experts"],
        "vocab": cfg["vocab_size"],
        "held_assignments_per_token_layer": (
            cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"] / experts_published(cfg)),
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
        from byteps_tpu.integrations import smallthinker
    except ImportError as e:
        raise manifest.ManifestError(
            "this program cannot build the configuration: it has no "
            f"byteps_tpu/integrations/smallthinker.py ({e})") from e

    n = cfg["num_hidden_layers"]
    hf = types.SimpleNamespace(**{
        k: v for k, v in cfg.items() if not isinstance(v, (dict, list))})
    hf.moe_num_primary_experts = experts_published(cfg)
    hf.sliding_window_layout = cfg["sliding_window_layout"][:n]
    hf.rope_layout = cfg["rope_layout"][:n]
    return smallthinker.smallthinker_config(
        hf, dtype=jnp.bfloat16, vocab_size=vocab_rows(cfg),
        attn_impl=job["attn_impl"], remat=bool(job.get("remat")),
        moe_held=(0, cfg["moe_num_primary_experts"]))


def build_step(cfg: dict, job: dict, mesh):
    """``(step, parameter shapes)``: the program's jitted data-parallel
    step for this configuration and job, nothing placed on a device yet
    (``aot_check_mixed.py`` lowers it for a chip that is only
    described)."""
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
    """The three jitted comparisons of ``reference_gaps``, each on the
    reference's own float32 states ``[T, d]``:
    ``block_gap(p, x, y, layer)`` for a block's parameters ``p``, input
    ``x`` and output ``y``; ``attn_gaps(a, n1, out, short, layer)`` for
    an attention sublayer's parameters, normalised input, output and —
    for a window layer, else None — the reference's output with the
    window one position short; and
    ``layer_gaps(m, n1, n2)`` for an expert layer's parameters, the
    router's input and the experts' (``aot_check_mixed.py`` lowers all
    three for the described chip).  ``layer`` is static: the programs
    are compiled once a kind of layer, not once a layer."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import manifest
    from byteps_tpu.models.transformer import Attention, Block, ExpertLayer
    from byteps_tpu.parallel import moe

    ref = manifest.load_module("reference", cfg["reference"])
    tc = transformer_config(cfg, dict(job, remat=False))
    c = ref.sizes(cfg)
    f32 = jnp.float32
    highest = functools.partial(jax.default_matmul_precision, "highest")

    def token_errors(got, want):
        return jnp.linalg.norm(got.astype(f32) - want, axis=-1)

    def rms(rows):
        return jnp.sqrt(jnp.mean(jnp.sum(jnp.square(rows), axis=-1)))

    @functools.partial(jax.jit, static_argnums=3)
    def block_gap(p, x, y, layer):
        got = Block(tc, experts=True, layer=layer).apply(
            {"params": p}, x[None].astype(tc.dtype))[0]
        err = token_errors(got, y) / rms(y - x)
        return jnp.median(err), jnp.percentile(err, 90)

    @functools.partial(jax.jit, static_argnums=4)
    def attn_gaps(a, n1, out, short, layer):
        got = Attention(tc, layer=layer).apply(
            {"params": a}, n1[None].astype(tc.dtype))[0].astype(f32)
        # each token by its OWN norm: an early token's output is one or
        # a few value rows, a late one's the mean of thousands — far
        # smaller, so one scale for all would let the early tokens'
        # rounding stand in for every token's
        err = jnp.linalg.norm(got - out, axis=-1) / jnp.linalg.norm(
            out, axis=-1)
        if short is None:               # no window, no edge
            return jnp.median(err), jnp.max(err), jnp.zeros(())
        # what the key at the window's edge adds, over all tokens: the
        # reference minus the reference one position short; the share of
        # it found in the program's output is 1 when the window is right
        edge = out - short
        share = jnp.sum((got - short) * edge) / jnp.sum(edge * edge)
        return jnp.median(err), jnp.max(err), jnp.abs(1.0 - share)

    def route(m, n1):
        return moe.route(n1, m["router"]["kernel"], None, tc.moe_top_k,
                         tc.moe_scale, scoring=tc.moe_scoring)

    @jax.jit
    def layer_gaps(m, n1, n2):
        idx, w = route(m, n1)
        with highest():
            r_idx, r_w = ref.router(n1, m["router"], c)
        same = idx[:, :, None] == r_idx[:, None, :]
        both = same.any(-1)
        w_ref = jnp.sum(jnp.where(same, r_w[:, None, :], 0.0), axis=-1)
        weight_gap = jnp.max(jnp.where(both, jnp.abs(w - w_ref), 0.0)) / (
            tc.moe_scale / tc.moe_top_k)
        held1, held2 = n1.astype(tc.dtype), n2.astype(tc.dtype)
        got = ExpertLayer(tc).apply({"params": m}, held2[None],
                                    held1[None])[0]
        with highest():
            want = ref.routed(held2.astype(f32), m, c, route(m, held1))
        return (jnp.mean(~both), weight_gap,
                jnp.max(token_errors(got, want)) / rms(want))

    return block_gap, attn_gaps, layer_gaps


def kind_layers(cfg: dict) -> list:
    """For each layer built, the first layer of its kind (window or
    whole prefix, RoPE or none): the comparisons compile once a kind."""
    n = cfg["num_hidden_layers"]
    kinds = list(zip(cfg["sliding_window_layout"][:n],
                     cfg["rope_layout"][:n]))
    return [kinds.index(k) for k in kinds]


def reference_gaps(cfg: dict, job: dict, params, tokens) -> dict:
    """The program's blocks against the plain reference's, one at a time
    on the reference's OWN states (an error cannot ride from block to
    block), on the sequence ``tokens [T]`` at the weights under test.
    The step's loss cannot tell these apart — a mean over thousands of
    targets at seeded weights moves by 1e-4 under any of the faults
    below — so each is held to a limit of its own:

    ``block_p90``           a whole block (attention through the flash
                            kernels, the router on the attention input,
                            the expert layer) as the step computes it:
                            the 90th-percentile token's error over the
                            RMS norm of what the block adds to a token
                            (not the worst: a near-tie may flip a
                            token's experts on a rounded input; not the
                            median, ``block_gap``, noted beside it: the
                            bf16 rounding of the token's own state sets
                            that, and a router fed the feed-forward
                            input — nearly the same input — moves other
                            experts onto a fifth of the tokens, not onto
                            half) — a router fed the feed-forward input.
    ``attn_worst_token``    the attention sublayer on the reference's
                            normalised input: the worst token's error
                            over that token's own norm — RoPE on the
                            wrong kind of layer.  ``attn_median`` is
                            noted beside it and held to no limit.
    ``window_edge_gap``     a window layer's edge, which the worst token
                            resolves poorly (one key of 4096: it moves a
                            token by 1-17 %, by how alike the tokens
                            are): the
                            reference minus the reference with the
                            window ONE POSITION SHORT is what the edge
                            key adds; ``|1 - share|`` of it, summed over
                            all tokens, found in the program's output —
                            0 when the window is right, 1 when it is one
                            short (or one long).
    ``router_flip_share``   the program's ``route`` on the reference's
    ``router_weight_gap``   float32 input: the share of its choices the
                            reference did not make, and the largest gap
                            of a weight both chose, over ``1 / k`` — a
                            router in bfloat16.
    ``expert_worst_token``  the expert layer on the inputs as the
                            program holds them, the reference given the
                            program's own choice (a flipped near-tie is
                            the router's to answer for): the worst
                            token's error over the tokens' RMS norm —
                            one dropped assignment, SiLU for ReLU.

    Returns the worst of each over the blocks; notes every block's."""
    import jax

    from benchmark.harness import manifest

    ref = manifest.load_module("reference", cfg["reference"])
    block_gap, attn_gaps, layer_gaps = gap_programs(cfg, job)
    highest = functools.partial(jax.default_matmul_precision, "highest")
    kinds = kind_layers(cfg)
    names = ("block_gap", "block_p90", "attn_median", "attn_worst_token",
             "window_edge_gap", "router_flip_share", "router_weight_gap",
             "expert_worst_token")
    c = ref.sizes(cfg)
    short_attention = jax.jit(ref.attention, static_argnums=(2, 3, 4))
    per_block = []
    states = ref.block_states(params, tokens, cfg["num_hidden_layers"], c)
    for layer in range(cfg["num_hidden_layers"]):
        rotated, window = ref.kind(c, layer)
        with highest():     # the reference's side only
            s = next(states)
            short = None if window is None else short_attention(
                s.n1, s.params["attn"], rotated, window - 1, c.theta)
        gaps = list(block_gap(s.params, s.x, s.y, kinds[layer]))
        gaps += attn_gaps(s.params["attn"], s.n1, s.attn, short,
                          kinds[layer])
        gaps += layer_gaps(s.params["moe"], s.n1, s.n2)
        per_block.append(dict(zip(names, map(float, gaps))))
    worst = {n: max(b[n] for b in per_block) for n in names}
    manifest.note(event="reference_blocks", per_block=per_block)
    return worst


def hold_to_reference(cfg: dict, job: dict, params, tokens) -> None:
    """``reference_gaps`` against the mix's ``reference_limits``; the
    numbers beside their limits are the last thing a run at fault says
    before ``ReferenceMismatch`` ends it."""
    from benchmark.harness import manifest

    limits = job["reference_limits"]
    worst = reference_gaps(cfg, job, params, tokens)
    over = sorted(n for n in limits if not worst[n] <= limits[n])
    manifest.note(event="reference_limits", over=over, **{
        n: {"value": v, "limit": limits.get(n)} for n, v in worst.items()})
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

    from benchmark.harness import manifest, weights
    from byteps_tpu.training.step import create_train_state

    step, shapes = build_step(cfg, job, mesh)
    T = job["seq_len"]
    global_batch = job["per_chip_batch"] * mesh.size
    key = jax.random.PRNGKey(seed)

    @functools.partial(jax.jit, out_shardings=NamedSharding(mesh, P()))
    def make_state(key):
        std = cfg["assumed"]["matrix_std"]
        params = weights.make_tree(shapes, key, jnp.float32, std)
        # the table at unit variance (``assumed.initializer`` says why:
        # tokens that are all alike route alike, and the seed then draws
        # how many of their experts are held here)
        params["embed"]["embedding"] *= cfg["assumed"]["embedding_std"] / std
        return create_train_state(params, step.tx)

    @functools.partial(
        jax.jit, out_shardings=NamedSharding(mesh, P(mesh.axis_names)))
    def make_batch(key):
        return {"tokens": jax.random.randint(
            key, (global_batch, T), 0, cfg["vocab_size"])}

    state = make_state(jax.random.fold_in(key, 0))
    batches = [make_batch(jax.random.fold_in(key, 1 + i))
               for i in range(job["batch_ring"])]
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(state.params))
    manifest.note(event="built", parameters=n_params,
                  bytes_master_grad_moments=16 * n_params,
                  bytes_step_arguments=12 * n_params)
    hold_to_reference(cfg, job, state.params, batches[0]["tokens"][0])
    return step, state, batches, {
        "global_batch": global_batch, "seq_len": T,
        "tokens_per_step": global_batch * T}
