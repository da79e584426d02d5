"""Builder for Nemotron-H-family configurations (the HF config keys of
``configs/nemotron3-nano-30b-l9-ep16.json``): a stack of ONE-sublayer
blocks whose kinds a pattern string gives — Mamba-2 mixers (the chunked
state-space scan of ``ops/ssd_scan.py``), grouped-query attention with
no position encoding, sigmoid-routed non-gated relu^2 experts of which
this chip holds a slice, beside a shared expert.

Maps the published keys onto the program's ``TransformerConfig`` through
the program's own ``integrations/nemotron_h.py:nemotron_h_config`` and
builds the data-parallel train step through ``Transformer`` +
``lm_loss_fn(fused_head=True)`` + ``make_data_parallel_step``, as the
other builders do.  The file's ``n_routed_experts`` is the count HELD
here (experts 0 .. count-1); the router keeps the published count
(``reduced_from``).  The pattern stays as published; its first
``num_hidden_layers`` letters are the blocks built.

Before it hands the step over, ``build_training`` holds the program's
blocks, mixers, carried scan state, router and expert layers at the
seeded weights to the plain reference's, one at a time
(``hold_to_reference``): the train runner compares the step's first loss
only, and at seeded weights that loss cannot see a convolution one tap
late, a state carried in bfloat16 or a dropped assignment.
"""

from __future__ import annotations

import functools
import types


class ReferenceMismatch(Exception):
    """A block, a mixer, the scan's carried state, the router or an
    expert layer of the program leaves the plain reference by more than
    the mix's ``reference_limits``."""


KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
STATE_CHANNELS = 8    # of each head, held to the float64 recurrence


def vocab_rows(cfg: dict) -> int:
    """Rows of the embedding table and head as built: the slice needs no
    padding."""
    return cfg["vocab_size"]


def experts_published(cfg: dict) -> int:
    return cfg["reduced_from"]["n_routed_experts"]


def pattern(cfg: dict) -> str:
    """The letters of the blocks built."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def kinds(cfg: dict) -> list:
    return [KINDS[c] for c in pattern(cfg)]


def dims(cfg: dict) -> dict:
    """Sizes for ``harness/flops_hybrid.py`` — and, under the names they
    read, for ``flops.fused_ce_cost`` (one head pass),
    ``flops_mixed.gqa_flash_cost`` (``window_layout``: one entry an
    attention layer, None) and ``flops_sparse.counted``.  The held
    assignments a step are the mean of the program's counter
    ``moe.assignments_held`` over the steps it counted
    (``training/step.py:flush_step_counts``); before any step, ``None``
    — the counts then use the nominal ``k * held / experts`` a token and
    layer."""
    from benchmark.harness import manifest

    counted = counted_assignments()
    if counted["steps"]:
        # assignments == rows_computed: nothing was dropped in any step
        manifest.note(event="held_assignments", **counted)
    ks = kinds(cfg)
    top_k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    return {
        "layers": len(ks), "mamba_layers": ks.count("mamba"),
        "attn_layers": ks.count("attn"), "expert_layers": ks.count("moe"),
        "d_model": cfg["hidden_size"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "d_head": cfg["head_dim"],
        "window_layout": [None] * ks.count("attn"),
        "ssm_heads": cfg["mamba_num_heads"],
        "ssm_head_dim": cfg["mamba_head_dim"],
        "ssm_groups": cfg["n_groups"], "ssm_state": cfg["ssm_state_size"],
        "ssm_conv": cfg["conv_kernel"], "ssm_chunk": cfg["chunk_size"],
        "d_expert": cfg["moe_intermediate_size"],
        "d_shared": (cfg["n_shared_experts"]
                     * cfg["moe_shared_expert_intermediate_size"]),
        "experts": experts_published(cfg), "experts_held": held,
        "top_k": top_k, "vocab": cfg["vocab_size"],
        "held_assignments_per_token_layer": (
            top_k * held / experts_published(cfg)),
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
        from byteps_tpu.integrations import nemotron_h
    except ImportError as e:
        raise manifest.ManifestError(
            "this program cannot build the configuration: it has no "
            f"byteps_tpu/integrations/nemotron_h.py ({e})") from e

    hf = types.SimpleNamespace(**{
        k: v for k, v in cfg.items() if not isinstance(v, (dict, list))})
    hf.n_routed_experts = experts_published(cfg)
    hf.hybrid_override_pattern = pattern(cfg)
    return nemotron_h.nemotron_h_config(
        hf, dtype=jnp.bfloat16, vocab_size=vocab_rows(cfg),
        attn_impl=job["attn_impl"], remat=bool(job.get("remat")),
        moe_held=(0, cfg["n_routed_experts"]))


def build_step(cfg: dict, job: dict, mesh):
    """``(step, parameter shapes)``: the program's jitted data-parallel
    step for this configuration and job, nothing placed on a device yet
    (``aot_check_hybrid.py`` lowers it for a chip that is only
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


def seeded_mixer_leaves(params, cfg: dict, key):
    """The mixers' leaves that are no N(0, std) matrix, as the published
    initialiser has them (``assumed.initializer``): ``A_log = log(1 ..
    H)``, ``D = 1``, ``dt_bias`` the inverse softplus of a log-uniform
    draw in ``[time_step_min, time_step_max]`` floored at
    ``time_step_floor``, the convolution's kernel ``U(-1/sqrt(K),
    1/sqrt(K))``.  Traceable."""
    import jax
    import jax.numpy as jnp

    lo, hi = cfg["time_step_min"], cfg["time_step_max"]
    for i, kind in enumerate(kinds(cfg)):
        if kind != "mamba":
            continue
        m = params[f"block_{i}"]["mamba"]
        k_dt, k_conv = jax.random.split(jax.random.fold_in(key, i))
        H = m["ssd"]["A_log"].shape[0]
        dt = jnp.maximum(jnp.exp(
            jax.random.uniform(k_dt, (H,)) * (jnp.log(hi) - jnp.log(lo))
            + jnp.log(lo)), cfg["time_step_floor"])
        m["ssd"] = {"A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                    "D": jnp.ones((H,), jnp.float32),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}
        w = m["conv"]["kernel"]
        bound = w.shape[0] ** -0.5
        m["conv"]["kernel"] = jax.random.uniform(
            k_conv, w.shape, w.dtype, -bound, bound)
    return params


def balanced_router_bias(cfg: dict, params, tokens):
    """``params`` with every router's selection bias set to what the
    published balancing rule (the bias of an expert that is chosen too
    often falls, of one chosen too seldom rises) settles at on the
    sequence ``tokens [T]`` at these weights, in closed form: minus the
    score that expert exceeds on ``k / E`` of the tokens, so that every
    expert is above its threshold equally often.  Block by block on the
    reference's own float32 states — an earlier layer's choice moves a
    later layer's input.  Why (``assumed.router_bias``): at seeded
    weights the normalised states share a component (the mean of
    ``silu`` is not zero), every token prefers the same experts, and the
    seed draws how many of those are among the held."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import manifest

    ref = manifest.load_module("reference", cfg["reference"])
    c = ref.sizes(cfg)
    top = 1.0 - c.top_k / experts_published(cfg)

    @jax.jit
    def balance(x, norm, kernel):
        s = jax.nn.sigmoid(ref.rms_norm(x, norm, c.eps) @ kernel)
        bias = -jnp.quantile(s, top, axis=0)
        return bias - jnp.mean(bias)

    with jax.default_matmul_precision("highest"):
        x = ref._f32(params["embed"]["embedding"])[tokens]
        for i, kind in enumerate(kinds(cfg)):
            p = ref._f32(params[f"block_{i}"])
            if kind == "moe":
                router = p["moe"]["router"]
                router["bias"] = balance(x, p["norm"], router["kernel"])
                params[f"block_{i}"]["moe"]["router"]["bias"] = router["bias"]
            x = ref._block(x, p, c, kind)[0]
    return params


def gap_programs(cfg: dict, job: dict):
    """The four jitted comparisons of ``reference_gaps``, each on the
    reference's own float32 states ``[T, d]``: ``block_gap(p, x, y,
    layer)`` for a block's parameters, input and output;
    ``mixer_gaps(m, n, out)`` for a mixer's parameters, normalised input
    and output; ``state_gap(x, dt, a, b, c, d, want)`` for what the
    scan reads and the state the reference's recurrence holds when the
    last chunk begins; ``layer_gaps(m, n)`` for an expert layer's
    parameters and input (``aot_check_hybrid.py`` lowers all four for
    the described chip).  ``layer`` is static: a program a KIND of
    block."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import manifest
    from byteps_tpu.models.transformer import (ExpertLayer, Mamba2Mixer,
                                               SublayerBlock)
    from byteps_tpu.ops.ssd_scan import ssd_scan_with_states
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
        got = SublayerBlock(tc, layer=layer).apply(
            {"params": p}, x[None].astype(tc.dtype))[0]
        err = token_errors(got, y) / rms(y - x)
        return jnp.median(err), jnp.percentile(err, 90)

    @jax.jit
    def mixer_gaps(m, n, out):
        got = Mamba2Mixer(tc).apply({"params": m}, n[None].astype(tc.dtype))
        err = token_errors(got[0], out) / jnp.linalg.norm(out, axis=-1)
        return jnp.median(err), jnp.max(err)

    @jax.jit
    def state_gap(x, dt, a, b, cc, d, want):
        # the program's scan on what the reference's scan read, rounded
        # to the compute dtype on BOTH sides (``want`` is the recurrence
        # on the rounded values, the first channels of every head): what
        # is left is the kernel's own arithmetic — the decay, the running
        # sums, the carry
        _, states = ssd_scan_with_states(
            x[None], dt[None], a, b[None], cc[None], d, chunk=tc.ssm_chunk)
        err = states[0, -1][:, :want.shape[1]] - want      # [H, P', N]
        by_head = jnp.linalg.norm(err, axis=(1, 2)) / jnp.linalg.norm(
            want, axis=(1, 2))
        return jnp.linalg.norm(err) / jnp.linalg.norm(want), jnp.max(by_head)

    def route(m, n):
        return moe.route(n, m["router"]["kernel"], m["router"]["bias"],
                         tc.moe_top_k, tc.moe_scale)

    @jax.jit
    def layer_gaps(m, n):
        idx, w = route(m, n)
        with highest():
            r_idx, r_w = ref.router(n, m["router"], c)
        same = idx[:, :, None] == r_idx[:, None, :]
        both = same.any(-1)
        w_ref = jnp.sum(jnp.where(same, r_w[:, None, :], 0.0), axis=-1)
        weight_gap = jnp.max(jnp.where(both, jnp.abs(w - w_ref), 0.0)) / (
            tc.moe_scale / tc.moe_top_k)
        held = n.astype(tc.dtype)
        got = ExpertLayer(tc).apply({"params": m}, held[None])[0]
        with highest():
            h32 = held.astype(f32)
            want = ref.routed(h32, m, c, route(m, held)) + ref.shared(h32, m)
        return (jnp.mean(~both), weight_gap,
                jnp.max(token_errors(got, want)) / rms(want),
                jnp.mean((idx >= c.first) & (idx < c.first + c.held))
                * tc.moe_top_k)

    return block_gap, mixer_gaps, state_gap, layer_gaps


def kind_layers(cfg: dict) -> list:
    """For each block built, the first block of its kind: the
    comparisons compile once a kind."""
    ks = kinds(cfg)
    return [ks.index(k) for k in ks]


def reference_gaps(cfg: dict, job: dict, params, tokens) -> dict:
    """The program's blocks against the plain reference's, one at a time
    on the reference's OWN states (an error cannot ride from block to
    block), on the sequence ``tokens [T]`` at the weights under test.
    The step's loss cannot tell these apart — a mean over thousands of
    targets at seeded weights — so each is held to a limit of its own:

    ``block_p90``           a whole block as the step computes it (the
                            mixer through ``ssd_fwd``, attention through
                            the flash kernels at 16 query heads a
                            key-value head, the expert layer with its
                            shared expert): the 90th-percentile token's
                            error over the RMS norm of what the block
                            adds to a token.  ``block_gap``, the median,
                            is noted beside it.
    ``mixer_worst_token``   the mixer alone on the reference's
                            normalised input, each token's error over
                            that token's own norm: a missing softplus, a
                            convolution that sees ``t + 1``, heads
                            reading the wrong group, the norm before the
                            gate, ``D`` left out.  ``mixer_median`` is
                            noted beside it.
    ``state_gap``           the state the scan carries into its LAST
                            chunk against the recurrence at that
                            position in float64 on the host
                            (``reference.carried_state``: the first 8
                            channels of every head), both on the same
                            rounded inputs: the error's norm over the
                            state's, all heads together — a state
                            carried in bfloat16 (2^-9 into every head
                            alike).  The WORST head's own relative
                            error, ``state_worst_head``, is noted beside
                            it and held to no limit.
    ``router_flip_share``   the program's ``route`` on the reference's
    ``router_weight_gap``   float32 input: the share of its choices the
                            reference did not make, and the largest gap
                            of a weight both chose, over ``scale / k`` —
                            a router in bfloat16.
    ``expert_worst_token``  the expert layer (routed + shared) on the
                            input as the program holds it, the reference
                            given the program's own choice: the worst
                            token's error over the tokens' RMS norm —
                            one dropped assignment, relu for relu^2.

    Returns the worst of each over the blocks; notes every block's, and
    for an expert block the held assignments a token its router made."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import manifest

    ref = manifest.load_module("reference", cfg["reference"])
    block_gap, mixer_gaps, state_gap, layer_gaps = gap_programs(cfg, job)
    highest = functools.partial(jax.default_matmul_precision, "highest")
    first_of_kind = kind_layers(cfg)
    c = ref.sizes(cfg)
    tc = transformer_config(cfg, job)
    parts_of = jax.jit(ref.mixer_parts, static_argnums=2)
    mixer_of = jax.jit(ref.mixer, static_argnums=2)
    T = tokens.shape[0]
    carried = (-(-T // tc.ssm_chunk) - 1) * tc.ssm_chunk   # last chunk's start
    per_block = []
    states = ref.block_states(params, tokens, cfg["num_hidden_layers"], c)
    for layer in range(cfg["num_hidden_layers"]):
        with highest():     # the reference's side only
            s = next(states)
        gaps = dict(zip(("block_gap", "block_p90"), block_gap(
            s.params, s.x, s.y, first_of_kind[layer])))
        if s.kind == "mamba":
            m = s.params["mamba"]
            with highest():
                out = mixer_of(s.n, m, c)
                parts = parts_of(s.n, m, c)
                low = parts._replace(**{
                    k: getattr(parts, k).astype(tc.dtype).astype(jnp.float32)
                    for k in ("x", "b", "c")})
            want = jnp.asarray(ref.carried_state(
                low, carried, STATE_CHANNELS), jnp.float32)
            gaps.update(zip(("mixer_median", "mixer_worst_token"),
                            mixer_gaps(m, s.n, out)))
            gaps.update(zip(("state_gap", "state_worst_head"), state_gap(
                low.x, low.dt, low.a, low.b, low.c, low.d, want)))
        elif s.kind == "moe":
            gaps.update(zip(
                ("router_flip_share", "router_weight_gap",
                 "expert_worst_token", "held_assignments_per_token"),
                layer_gaps(s.params["moe"], s.n)))
        per_block.append({"kind": s.kind,
                          **{k: float(v) for k, v in gaps.items()}})
    names = sorted({k for b in per_block for k in b} - {
        "kind", "held_assignments_per_token"})
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

    replicated = NamedSharding(mesh, P())

    @functools.partial(jax.jit, out_shardings=replicated)
    def make_params(key):
        std = cfg["assumed"]["matrix_std"]
        params = weights.make_tree(shapes, key, jnp.float32, std)
        params["embed"]["embedding"] *= cfg["assumed"]["embedding_std"] / std
        return seeded_mixer_leaves(params, cfg, jax.random.fold_in(key, 1))

    make_state = jax.jit(lambda params: create_train_state(params, step.tx),
                         out_shardings=replicated, donate_argnums=0)

    @functools.partial(
        jax.jit, out_shardings=NamedSharding(mesh, P(mesh.axis_names)))
    def make_batch(key):
        return {"tokens": jax.random.randint(
            key, (global_batch, T), 0, cfg["vocab_size"])}

    batches = [make_batch(jax.random.fold_in(key, 1 + i))
               for i in range(job["batch_ring"])]
    state = make_state(balanced_router_bias(
        cfg, make_params(jax.random.fold_in(key, 0)),
        jax.device_put(batches[0]["tokens"], replicated)[0]))
    by_kind = {}
    for i, kind in enumerate(kinds(cfg)):
        by_kind[kind] = sum(
            a.size for a in jax.tree_util.tree_leaves(
                state.params[f"block_{i}"]))
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(state.params))
    manifest.note(event="built", parameters=n_params,
                  parameters_a_block_by_kind=by_kind,
                  bytes_master_grad_moments=16 * n_params,
                  bytes_step_arguments=12 * n_params)
    hold_to_reference(cfg, job, state.params, batches[0]["tokens"][0])
    return step, state, batches, {
        "global_batch": global_batch, "seq_len": T,
        "tokens_per_step": global_batch * T}
