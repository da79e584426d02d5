"""Builder for Ouro configurations (the HF config keys of
``configs/ouro-2.6b-l7.json``): a looped language model — one stack of
sandwich-normed RoPE MHA / SwiGLU layers run ``total_ut_steps`` times a
token with one set of weights, the final norm after every pass and fed
to the next, an exit gate on each pass's state, the loss the exit
distribution's expected cross-entropy less its entropy's weight times
the entropy.

Maps the published keys onto the program's ``TransformerConfig``
through the program's own ``integrations/ouro.py:ouro_config`` and
builds the data-parallel train step through ``Transformer`` +
``lm_loss_fn(fused_head=True)`` + ``make_data_parallel_step``, as the
other builders do.  The layers built are the first
``num_hidden_layers`` entries of ``layer_types``.

Before it hands the step over, ``build_training`` holds the program to
the plain reference at the seeded weights (``hold_to_reference``):
every layer application of every pass, forward and backward, on the
reference's own states, then every pass's readout and the exit
distribution through the program's whole forward.  The train runner
compares the step's first loss only, and at seeded weights every pass's
cross-entropy lies near ln(vocabulary), so that loss barely depends on
the exit distribution: it cannot see a wrong gate.
"""

from __future__ import annotations

import functools
import types


# the backward comparison's cotangent covers each sequence's first
# positions, so that the reference's gradient needs no later one
GRAD_POSITIONS = 2048


class ReferenceMismatch(Exception):
    """A layer application, a pass's readout or the exit distribution of
    the program leaves the plain reference by more than the mix's
    ``reference_limits``."""


def _reference(cfg: dict):
    from benchmark.harness import manifest

    return manifest.load_module("reference", cfg["reference"])


def vocab_rows(cfg: dict) -> int:
    """Rows of the table and the head as built: no padding needed."""
    return cfg["vocab_size"]


def dims(cfg: dict) -> dict:
    """Sizes for ``harness/flops_loop.py`` and, under the names they
    read, ``flops.flash_attention_cost`` and ``flops.fused_ce_cost``:
    ``layers`` is the layer APPLICATIONS a step makes (the held layers
    times the passes), so that a reader multiplying one layer's cost by
    it counts every pass; ``layers_held`` and ``loop_steps`` beside
    it."""
    held, steps = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    return {
        "layers": held * steps, "layers_held": held, "loop_steps": steps,
        "d_model": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "d_head": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "mlp": "swiglu", "tied": False}


def transformer_config(cfg: dict, job: dict):
    import jax.numpy as jnp

    from benchmark.harness import manifest

    try:
        from byteps_tpu.integrations import ouro
    except ImportError as e:
        raise manifest.ManifestError(
            "this program cannot build the configuration: it has no "
            f"byteps_tpu/integrations/ouro.py ({e})") from e

    hf = types.SimpleNamespace(**{
        k: v for k, v in cfg.items() if not isinstance(v, dict)})
    return ouro.ouro_config(
        hf, dtype=jnp.bfloat16, attn_impl=job["attn_impl"],
        remat=bool(job.get("remat")))


def build_step(cfg: dict, job: dict, mesh):
    """``(step, parameter shapes)``: the program's jitted data-parallel
    step for this configuration and job, nothing placed on a device
    yet."""
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


def next_targets(tokens):
    """The step's targets: the next token, the last position none."""
    import jax.numpy as jnp

    return jnp.roll(tokens, -1, axis=1).at[:, -1].set(-100)


def gap_programs(cfg: dict, job: dict):
    """The jitted comparisons of ``reference_gaps``: ``block_gaps(p, x,
    y, g, dx, dp)`` one layer application as the step builds it
    (``block_cls``: with the job's ``remat``, recomputed in the backward
    pass) on the reference's float32 state ``x [B, T, d]``, its forward
    against the reference's output ``y`` and its backward, for the
    cotangent ``g [B, P, d]`` on the first ``P`` positions, against the
    reference's input and parameter gradients ``dx [B, P, d]``, ``dp``;
    ``ref_grads(p, x, g)`` the reference layer's gradients on ONE
    sequence's first ``P`` positions (every layer is causal, so a
    cotangent that is zero after them needs no later position);
    ``readouts(params, tokens)`` the program's ``(loss, ce [R, B, T],
    p [R, B, T])`` through its whole looped forward and the step's own
    readout and loss functions."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import Transformer
    from byteps_tpu.training import step as train_step

    ref = _reference(cfg)
    tc = transformer_config(cfg, job)
    block_cls = tc.block_cls()
    model = Transformer(tc)
    c = ref.sizes(cfg)
    f32 = jnp.float32

    def token_errors(got, want):
        return jnp.linalg.norm(got.astype(f32) - want, axis=-1)

    def rms(rows):
        return jnp.sqrt(jnp.mean(jnp.sum(jnp.square(rows), axis=-1)))

    def leaf_gap(got, want):
        norm = jnp.linalg.norm(want)
        return jnp.linalg.norm(got - want) / jnp.where(norm > 0, norm, 1.0)

    @jax.jit
    def block_gaps(p, x, y, g, dx_ref, dp_ref):
        def apply(p, x):
            return block_cls(tc, layer=0).apply({"params": p}, x)

        got, vjp = jax.vjp(apply, p, x.astype(tc.dtype))
        P = g.shape[1]
        dp, dx = vjp(jnp.pad(g, ((0, 0), (0, x.shape[1] - P), (0, 0))
                             ).astype(tc.dtype))
        err = token_errors(got, y) / rms(y - x)
        # what the layer adds to the gradient the residual passes on
        d_err = token_errors(dx[:, :P], dx_ref) / rms(dx_ref - g)
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(leaf_gap, dp, dp_ref))
        return (jnp.median(err), jnp.percentile(err, 90),
                jnp.percentile(d_err, 90), jnp.max(jnp.stack(leaves)))

    @jax.jit
    def ref_grads(p, x, g):
        _, vjp = jax.vjp(lambda p, x: ref.layer(x, p, c), p, x)
        return vjp(g)

    @jax.jit
    def readouts(params, tokens):
        ce, log_p, valid = train_step.loop_readouts(
            params, model, tokens, next_targets(tokens), job["fused_head"])
        loss, _ = train_step.exit_weighted_loss(
            ce, log_p, valid, train_step.EXIT_ENTROPY_WEIGHT)
        shape = (ce.shape[0],) + tokens.shape
        return loss, ce.reshape(shape), jnp.exp(log_p).reshape(shape)

    return block_gaps, ref_grads, readouts


def cotangent(index: int, shape):
    """The seeded cotangent of layer application ``index``'s output,
    exact in bfloat16 so that both sides start from the same one."""
    import jax
    import jax.numpy as jnp

    g = jax.random.normal(jax.random.PRNGKey(index), shape)
    return g.astype(jnp.bfloat16).astype(jnp.float32)


def reference_gaps(cfg: dict, job: dict, params, tokens) -> dict:
    """The program against the plain reference on the batch ``tokens [B,
    T]`` the step takes first, at the weights under test, each gap held
    to a limit of its own — the step's loss cannot tell these apart:

    ``block_p90``       one layer application as the step computes it
                        (attention through the flash kernels, the
                        SwiGLU, the four norms) on the reference's OWN
                        input state of that pass and layer (an error
                        cannot ride from application to application):
                        the 90th-percentile token's error over the RMS
                        norm of what the layer adds to a token.
                        ``block_gap``, the median, is noted beside it.
    ``grad_p90``        the same application's backward pass — the
                        recomputed forward, the flash backward — for a
                        seeded cotangent on the first ``GRAD_POSITIONS``
                        of every sequence: the 90th-percentile token's
                        error in the input's gradient over the RMS norm
                        of what the layer adds to it.
    ``param_grad_gap``  its parameters' gradients: the worst leaf's
                        error over that leaf's norm.
    ``loop_ce_gap``     every pass's readout through the program's whole
                        looped forward (its own states, pass to pass)
                        and the step's own readout code: the worst
                        pass's mean over the targets of the absolute gap
                        of a target's cross-entropy to the reference's —
                        a pass fed the wrong state, a readout a pass
                        late.
    ``exit_p_gap``      the exit distribution the same way: the largest
                        gap of any target's probability of any pass — a
                        gate read off the wrong state, the last pass
                        short of the remaining mass.
    ``loop_loss_gap``   the program's loss on the batch (the step's own
                        loss function on those readouts) against the
                        reference's — the entropy's sign.

    Returns the worst of each over the applications and passes; notes
    every application's and pass's."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import manifest

    ref = _reference(cfg)
    block_gaps, ref_grads, readouts = gap_programs(cfg, job)
    highest = functools.partial(jax.default_matmul_precision, "highest")
    c = ref.sizes(cfg)
    B, T = tokens.shape
    P = min(T, GRAD_POSITIONS)
    per_block, per_pass, ref_ce, ref_gate = [], [], [], []
    for st in ref.states(params, tokens, c):
        if isinstance(st, ref.PassState):
            with highest():
                ref_ce.append(jnp.stack([
                    ref._ce_rows(st.s[b, :-1], ref.head(params),
                                 tokens[b, 1:]) for b in range(B)]))
            ref_gate.append(st.gate[:, :-1])
            continue
        index = st.step * c.layers + st.layer
        with highest():     # the reference's side only
            g = cotangent(index, (B, P, st.x.shape[-1]))
            dp_ref, dx_ref = None, []
            for b in range(B):
                dp, dx = ref_grads(st.params, st.x[b, :P], g[b])
                dp_ref = dp if dp_ref is None else jax.tree_util.tree_map(
                    lambda a, d: a + d, dp_ref, dp)
                dx_ref.append(dx)
        gaps = dict(zip(
            ("block_gap", "block_p90", "grad_p90", "param_grad_gap"),
            block_gaps(st.params, st.x, st.y, g, jnp.stack(dx_ref),
                       dp_ref)))
        del dp_ref, dx_ref
        per_block.append({"pass": st.step, "layer": st.layer,
                          **{k: float(v) for k, v in gaps.items()}})
    with highest():
        # [R, B, T - 1]: the gates' logits become the distribution
        want_ce = jnp.stack(ref_ce)
        want_p = ref.exit_probs(jnp.stack(ref_gate))
        want_loss = float(jnp.mean(ref.loss_terms(
            want_ce.reshape(c.steps, -1), want_p.reshape(c.steps, -1),
            c.beta)))
    loss, ce, p = readouts(params, tokens)
    ce_gap = jnp.mean(jnp.abs(ce[:, :, :-1] - want_ce), axis=(1, 2))
    p_gap = jnp.max(jnp.abs(p[:, :, :-1] - want_p), axis=(1, 2))
    for t in range(c.steps):
        per_pass.append({"pass": t, "loop_ce_gap": float(ce_gap[t]),
                         "exit_p_gap": float(p_gap[t]),
                         "ce": float(jnp.mean(want_ce[t])),
                         "p": float(jnp.mean(want_p[t]))})
    worst = {n: max(b[n] for b in per_block) for n in (
        "block_gap", "block_p90", "grad_p90", "param_grad_gap")}
    worst.update({n: max(q[n] for q in per_pass)
                  for n in ("loop_ce_gap", "exit_p_gap")})
    worst["loop_loss_gap"] = abs(float(loss) - want_loss)
    manifest.note(event="reference_blocks", per_block=per_block,
                  per_pass=per_pass, system_loss=float(loss),
                  reference_loss=want_loss)
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
    on the device from the seed — after the program at those weights was
    held to the reference (``hold_to_reference``)."""
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

    make_params = jax.jit(
        lambda key: weights.make_tree(shapes, key, jnp.float32,
                                      cfg["assumed"]["matrix_std"]),
        out_shardings=replicated)
    make_state = jax.jit(lambda params: create_train_state(params, step.tx),
                         out_shardings=replicated, donate_argnums=0)

    @functools.partial(
        jax.jit, out_shardings=NamedSharding(mesh, P(mesh.axis_names)))
    def make_batch(key):
        return {"tokens": jax.random.randint(
            key, (global_batch, T), 0, cfg["vocab_size"])}

    batches = [make_batch(jax.random.fold_in(key, 1 + i))
               for i in range(job["batch_ring"])]
    params = make_params(jax.random.fold_in(key, 0))
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    manifest.note(event="built", parameters=n_params,
                  parameters_a_layer=sum(a.size for a in jax.tree_util.
                                         tree_leaves(params["block_0"])),
                  layers_held=cfg["num_hidden_layers"],
                  loop_steps=cfg["total_ut_steps"],
                  bytes_master_grad_moments=16 * n_params,
                  bytes_step_arguments=12 * n_params)
    # before the moments exist: the comparisons' working set is the
    # largest of set-up
    hold_to_reference(cfg, job, params,
                      batches[0]["tokens"][:job["per_chip_batch"]])
    state = make_state(params)
    return step, state, batches, {
        "global_batch": global_batch, "seq_len": T,
        "tokens_per_step": global_batch * T}
