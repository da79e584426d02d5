"""Ouro's looped LM through the normal path — one stack of
sandwich-normed RoPE MHA / SwiGLU blocks run R times a token with one
set of weights (``loop_steps``, one ``nn.scan``), the final norm after
every pass and fed to the next, an exit gate on each pass's state and
the exit distribution's expected loss less its entropy — each against
``benchmark/reference/ouro.py`` (plain float32 ``jax.numpy``) or a plain
formula, on seeded weights at a small size on the CPU: 2 layers at
hidden 256, 4 heads of 64, SwiGLU 384, vocabulary 512, R 3.
"""

import dataclasses
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro as ref
from byteps_tpu.integrations.ouro import ouro_config
from byteps_tpu.models.transformer import (Block, Transformer,
                                           TransformerConfig,
                                           exit_log_probs)
from byteps_tpu.observability.metrics import get_registry
from byteps_tpu.training import lm_loss_fn

L, D, H, DH, F, V, R, T = 2, 256, 4, 64, 384, 512, 3, 64
BETA = 0.1
# the published keys at the small size (what the reference reads)
HF = {"hidden_size": D, "num_hidden_layers": L, "num_attention_heads": H,
      "num_key_value_heads": H, "head_dim": DH, "intermediate_size": F,
      "vocab_size": V, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
      "total_ut_steps": R, "max_position_embeddings": T,
      "layer_types": ["full_attention"] * 4, "tie_word_embeddings": False,
      "hidden_act": "silu", "use_sliding_window": False,
      "sliding_window": None, "rope_scaling": None,
      "assumed": {"entropy_weight": BETA}}
CFG = ouro_config(types.SimpleNamespace(**HF), attn_impl="local")
SIZES = ref.sizes(HF)


def seeded(shapes, seed, std=0.1):
    """Weights that make every leaf matter: matrices N(0, std), norm
    scales about 1, the gate's bias of a logit's size."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        out = []
        for i, (path, a) in enumerate(leaves):
            draw = jax.random.normal(jax.random.fold_in(key, i), a.shape,
                                     jnp.float32)
            name = path[-1].key
            out.append(1.0 + 0.2 * draw if name == "scale"
                       else 0.5 * draw if name == "bias" else std * draw)
        return out

    return jax.tree_util.tree_unflatten(
        treedef, make(jax.random.PRNGKey(seed)))


def init_shapes(module, *args):
    return jax.eval_shape(module.init, jax.random.PRNGKey(0), *args)["params"]


def tokens_of(seed, batch=1, length=T):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length),
                              0, V)


# ---------------------------------------------------- the family's config


def test_the_family_config_maps_onto_a_looped_sandwich_normed_stack():
    assert (CFG.num_layers, CFG.loop_steps, CFG.sandwich_norm) == (L, R,
                                                                   True)
    assert (CFG.num_heads, CFG.kv_heads, CFG.d_head, CFG.d_ff, CFG.mlp) == (
        H, H, DH, F, "swiglu")
    assert (CFG.pos_emb, CFG.rope_theta, CFG.norm, CFG.norm_eps) == (
        "rope", 1e6, "rmsnorm", 1e-6)
    assert not CFG.tie_embeddings and not CFG.use_bias
    assert CFG.block_cls() is Block
    # the benchmark's file maps too, at its published widths
    from benchmark.harness import manifest

    body = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "ouro-2.6b-l7.json"))
    tc = manifest.load_module("builders", body["builder"]).transformer_config(
        body, {"attn_impl": "flash", "remat": True})
    assert (tc.num_layers, tc.loop_steps, tc.d_model, tc.num_heads,
            tc.kv_heads, tc.d_head, tc.d_ff, tc.vocab_size) == (
        7, 4, 2048, 16, 16, 128, 5632, 49152)
    assert (tc.max_seq_len, tc.norm_eps, tc.rope_theta) == (65536, 1e-6,
                                                            1e6)
    # the file's assumed weight is the program's
    from byteps_tpu.training.step import EXIT_ENTROPY_WEIGHT

    assert body["assumed"]["entropy_weight"] == EXIT_ENTROPY_WEIGHT
    assert tc.remat and tc.sandwich_norm and not tc.tie_embeddings


@pytest.mark.parametrize("key,value,match", [
    ("use_sliding_window", True, "use_sliding_window"),
    ("sliding_window", 4096, "sliding_window"),
    ("layer_types", ["full_attention", "sliding_attention"], "layer_types"),
    ("layer_types", ["full_attention"], "layer_types"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("total_ut_steps", 0, "total_ut_steps"),
    ("rope_scaling", {"rope_type": "linear", "factor": 2.0},
     "rope_scaling"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_the_family_config_refuses_what_is_not_built(key, value, match):
    hf = types.SimpleNamespace(**dict(HF, **{key: value}))
    with pytest.raises(ValueError, match=match):
        ouro_config(hf)


def test_a_looped_model_refuses_what_it_is_not_built_with():
    tokens = tokens_of(0)
    for extra in (dict(moe_experts=4, moe_top_k=2, moe_d_ff=32),
                  dict(mtp_layers=1), dict(layer_kinds=("attn", "mlp"))):
        with pytest.raises(ValueError, match="looped"):
            init_shapes(Transformer(dataclasses.replace(CFG, **extra)),
                        tokens)
    with pytest.raises(ValueError, match="loop_steps"):
        init_shapes(Transformer(dataclasses.replace(CFG, loop_steps=0)),
                    tokens)
    with pytest.raises(ValueError, match="early_exit"):
        lm_loss_fn(Transformer(CFG), early_exit=(1, 0.1))


def test_the_model_says_how_many_passes_and_layers_it_holds():
    jax.eval_shape(Transformer(CFG).init, jax.random.PRNGKey(0),
                   tokens_of(0))
    reg = get_registry()
    assert reg.gauge("loop.steps").value == R
    assert reg.gauge("model.layers_held").value == L


# --------------------------------------------------------- the older trees


@pytest.mark.parametrize("name,cfg", [
    ("gpt2", dict(num_layers=2, num_heads=4, d_model=64, d_ff=128,
                  vocab_size=96, norm="layernorm", use_bias=True,
                  tie_embeddings=True)),
    ("lfm2", dict(num_layers=4, num_heads=4, num_kv_heads=2, d_model=64,
                  vocab_size=96, pos_emb="rope", qk_norm=True,
                  layer_kinds=("conv", "mlp", "attn", "moe"), mlp="swiglu",
                  moe_experts=8, moe_top_k=2, moe_d_ff=32,
                  tie_embeddings=True)),
    ("joyai", dict(num_layers=2, num_heads=4, d_model=64, vocab_size=96,
                   pos_emb="rope", attn_kind="mla", q_lora_rank=32,
                   kv_lora_rank=16, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, moe_experts=8,
                   moe_top_k=2, moe_d_ff=32, moe_shared=1, dense_layers=1,
                   mtp_layers=1)),
])
def test_the_defaults_leave_the_older_models_unchanged(name, cfg):
    """One pass and no sandwich norms build the tree they built before:
    no output norm, no exit gate, and no readout scope in the program;
    with the norms on each block gains exactly two ``[d_model]``
    scales."""
    tc = TransformerConfig(**cfg)
    assert (tc.loop_steps, tc.sandwich_norm) == (1, False)
    tokens = jnp.zeros((1, 32), jnp.int32)
    off = init_shapes(Transformer(tc), tokens)
    paths = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(off)}
    assert not any("_out" in p or "exit_gate" in p for p in paths), name
    text = jax.jit(lambda p, t: Transformer(tc).apply(
        {"params": p}, t)).lower(off, tokens).as_text(debug_info=True)
    assert "loop_readout" not in text, name
    if tc.layer_kinds is not None:       # one-sublayer blocks: no Block
        return
    on = init_shapes(Transformer(dataclasses.replace(tc, sandwich_norm=True)),
                     tokens)
    added = {jax.tree_util.keystr(p): a.shape for p, a in
             jax.tree_util.tree_leaves_with_path(on)
             if jax.tree_util.keystr(p) not in paths}
    norms = {p.rsplit("[", 1)[0] for p in added}       # a layer norm: + bias
    blocks = tc.num_layers + tc.mtp_layers
    assert len(norms) == 2 * blocks and all(
        s == (tc.d_model,) for s in added.values()), added


# ----------------------------------------------------- the exit distribution


def test_the_exit_distribution_is_the_products_and_sums_to_one():
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (4, 50))
    got = jnp.exp(exit_log_probs(logits))
    lam = jax.nn.sigmoid(logits)
    want = np.stack([lam[0], lam[1] * (1 - lam[0]),
                     lam[2] * (1 - lam[0]) * (1 - lam[1]),
                     (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got, ref.exit_probs(logits), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(jnp.sum(got, axis=0), 1.0, rtol=1e-6)
    # a gate far on either side keeps a finite log: the entropy's term
    far = exit_log_probs(jnp.array([[60.0], [-60.0], [5.0]]))
    assert bool(jnp.all(jnp.isfinite(far)))


# ------------------------------------------------------- the whole model


@pytest.fixture(scope="module")
def whole():
    """Program and reference on one batch at seeded weights: every
    pass's logits, the exit distribution, the loss and the gradients of
    every leaf, the plain head and the fused one."""
    m = Transformer(CFG)
    tokens = tokens_of(7)
    params = seeded(init_shapes(m, tokens), 8)

    def program(p):
        return lm_loss_fn(m)(p, {}, {"tokens": tokens})[0]

    def reference(p):
        return ref.sequence_loss_sum(p, tokens[0], SIZES) / (T - 1)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(program))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference))(params)
        logits = m.apply({"params": params}, tokens)[:, 0]
        ref_logits = ref.logits(params, tokens[0], SIZES)
        states, gates = m.apply({"params": params}, tokens,
                                method=m.hidden)
        _, ref_gates = ref.forward(params, tokens[0], SIZES)
        fused, _, means = jax.jit(lm_loss_fn(m, fused_head=True))(
            params, {}, {"tokens": tokens})
    return dict(m=m, params=params, tokens=tokens, loss=float(loss),
                grads=grads, ref_loss=float(ref_loss), ref_grads=ref_grads,
                logits=logits, ref_logits=ref_logits, gates=gates[:, 0],
                ref_gates=ref_gates, fused=float(fused), means=means)


def test_every_pass_s_logits_and_the_exit_distribution_match(whole):
    params = whole["params"]
    assert set(params) == {"embed", "ln_f", "lm_head", "exit_gate"} | {
        f"block_{i}" for i in range(L)}
    assert set(params["block_0"]) == {"ln1", "attn", "ln1_out", "ln2",
                                      "mlp", "ln2_out"}
    assert whole["logits"].shape == (R, T, V)
    np.testing.assert_allclose(whole["logits"], whole["ref_logits"],
                               rtol=1e-3, atol=3e-4)
    # the passes differ: a loop that ran the stack once would not
    assert float(jnp.max(jnp.abs(whole["logits"][0] - whole["logits"][1]))
                 ) > 0.1
    np.testing.assert_allclose(whole["gates"], whole["ref_gates"],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        jnp.exp(exit_log_probs(whole["gates"])),
        ref.exit_probs(whole["ref_gates"]), rtol=1e-4, atol=1e-5)


def test_the_loss_matches_the_reference_and_reports_each_pass(whole):
    assert whole["loss"] == pytest.approx(whole["ref_loss"], abs=2e-5)
    # the fused head is the benchmark's path
    assert whole["fused"] == pytest.approx(whole["ref_loss"], abs=2e-4)
    with jax.default_matmul_precision("highest"):
        assert ref.loss(whole["params"], whole["tokens"], HF) == (
            pytest.approx(whole["ref_loss"], abs=1e-5))
    means = {k: float(v) for k, v in whole["means"].items()}
    assert set(means) == {f"loop_{q}{t}" for q in ("ce", "exit_p")
                          for t in range(1, R + 1)}
    assert sum(means[f"loop_exit_p{t}"] for t in range(1, R + 1)) == (
        pytest.approx(1.0, abs=1e-5))
    with jax.default_matmul_precision("highest"):
        s, g = ref.forward(whole["params"], whole["tokens"][0], SIZES)
        p = ref.exit_probs(g[:, :-1])
        for t in range(R):
            ce = ref.ce_rows(s[t, :-1], ref.head(whole["params"]),
                             whole["tokens"][0, 1:])
            assert means[f"loop_ce{t + 1}"] == pytest.approx(
                float(jnp.mean(ce)), abs=2e-4)
            assert means[f"loop_exit_p{t + 1}"] == pytest.approx(
                float(jnp.mean(p[t])), abs=1e-5)
    # the entropy's term is in the loss, with its sign
    expected = sum(means[f"loop_exit_p{t}"] * 0 for t in range(1, R + 1))
    assert whole["loss"] < max(means[f"loop_ce{t}"]
                               for t in range(1, R + 1)) + expected


def test_every_gradient_leaf_matches_the_reference(whole):
    """Every leaf of the shared blocks — their output norms among them —
    the gate's kernel and bias, the final norm, the head and the table.
    rtol 5e-3 on a leaf's scale: the gradients sum over 3 passes, each
    in another order than the reference's."""
    got = jax.tree_util.tree_leaves_with_path(whole["grads"])
    want = jax.tree_util.tree_leaves(whole["ref_grads"])
    # 2 blocks of 4 norms, 4 attention and 3 SwiGLU leaves; the table,
    # the head, the gate's two, the final norm
    assert len(got) == len(want) == L * (4 + 4 + 3) + 5
    for (path, g), w in zip(got, want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        np.testing.assert_allclose(g, w, rtol=5e-3, atol=2e-3 * scale,
                                   err_msg=name)


def test_the_shared_gradient_is_the_sum_over_untied_copies(whole):
    """The reference's loop written with a copy of the blocks' weights a
    pass: each copy's gradient is its pass's share, and the three sum to
    the program's gradient of the one shared set."""
    params, tokens = whole["params"], whole["tokens"][0]

    def untied(copies, rest):
        x = rest["embed"]["embedding"][tokens]
        s_all, g_all = [], []
        for t in range(R):
            for i in range(L):
                x = ref.layer(x, copies[t][f"block_{i}"], SIZES)
            s, g = ref.readout(ref.rms_norm(x, rest["ln_f"], SIZES.eps),
                               rest)
            s_all.append(s)
            g_all.append(g)
            x = s
        ce = jnp.stack([ref.ce_rows(s[:-1], rest["lm_head"]["kernel"],
                                    tokens[1:]) for s in s_all])
        probs = ref.exit_probs(jnp.stack(g_all)[:, :-1])
        return jnp.sum(ref.loss_terms(ce, probs, BETA)) / (T - 1)

    blocks = {k: v for k, v in params.items() if k.startswith("block_")}
    rest = {k: v for k, v in params.items() if not k.startswith("block_")}
    with jax.default_matmul_precision("highest"):
        per_copy = jax.jit(jax.grad(untied))([blocks] * R, rest)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *per_copy)
    for i in range(L):
        for (path, g), w in zip(
                jax.tree_util.tree_leaves_with_path(
                    whole["grads"][f"block_{i}"]),
                jax.tree_util.tree_leaves(summed[f"block_{i}"])):
            scale = float(jnp.max(jnp.abs(w)))
            np.testing.assert_allclose(
                g, w, rtol=5e-3, atol=2e-3 * scale,
                err_msg=jax.tree_util.keystr(path))
    # and no single pass's share is the whole
    one = jax.tree_util.tree_leaves(per_copy[0]["block_0"])
    tot = jax.tree_util.tree_leaves(summed["block_0"])
    assert max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(one, tot)
               ) > 1e-3


def test_a_recomputed_model_gives_the_same_loss_and_gradients(whole):
    rm = Transformer(dataclasses.replace(CFG, remat=True))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: lm_loss_fn(rm)(
            p, {}, {"tokens": whole["tokens"]})[0]))(whole["params"])
    assert float(loss) == pytest.approx(whole["loss"], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(whole["grads"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_flash_attention_gives_the_same_loss():
    """The benchmark's attention path (the flash kernels, interpreted
    here) under the loop."""
    m = Transformer(dataclasses.replace(CFG, attn_impl="flash"))
    tokens = tokens_of(9)
    params = seeded(init_shapes(m, tokens), 10)
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(lm_loss_fn(m, fused_head=True))(
            params, {}, {"tokens": tokens})[0])
        want = float(ref.sequence_loss_sum(params, tokens[0], SIZES)) / (
            T - 1)
    assert got == pytest.approx(want, abs=2e-4)


# ------------------------------------------------- the step and its program


def lowered_step_text(loop_steps, layers=8):
    """The data-parallel step of a looped model on one CPU device."""
    import optax
    from jax.sharding import Mesh

    from byteps_tpu.training import make_data_parallel_step
    from byteps_tpu.training.step import create_train_state

    cfg = dataclasses.replace(CFG, num_layers=layers, loop_steps=loop_steps,
                              remat=True)
    m = Transformer(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step = make_data_parallel_step(lm_loss_fn(m, fused_head=True),
                                   optax.adamw(1e-4), mesh)
    tokens = tokens_of(1)
    state = jax.eval_shape(lambda: create_train_state(
        m.init(jax.random.PRNGKey(0), tokens)["params"], step.tx))
    return step.lower(state, {"tokens": tokens}).as_text(), step, m


def test_the_passes_are_a_scan_the_step_does_not_grow_with_them():
    """R 4 lowers within 1.3x of R 1 (an unrolled loop would be ~4x):
    the passes are one scan over the shared stack."""
    one, _, _ = lowered_step_text(1)
    four, _, _ = lowered_step_text(4)
    assert len(four) < 1.3 * len(one), (len(four), len(one))
    # the scan's while loop and the map over the readouts
    assert four.count("stablehlo.while") > one.count("stablehlo.while")


def test_the_step_reports_each_pass_and_sets_its_gauges():
    from jax.sharding import Mesh

    from byteps_tpu.training.step import create_train_state, \
        flush_step_counts

    _, step, m = lowered_step_text(R, layers=L)
    tokens = tokens_of(3)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    state = step.init_state(seeded(init_shapes(m, tokens), 4)) if hasattr(
        step, "init_state") else create_train_state(
        seeded(init_shapes(m, tokens), 4), step.tx)
    del mesh
    _, metrics = step(state, {"tokens": tokens})
    flush_step_counts()
    assert set(metrics) == {"loss"} | {f"loop_{q}{t}" for q in (
        "ce", "exit_p") for t in range(1, R + 1)}
    reg = get_registry()
    for t in range(1, R + 1):
        assert reg.gauge(f"loop.ce{t}").value == pytest.approx(
            float(metrics[f"loop_ce{t}"]))
        assert reg.gauge(f"loop.exit_p{t}").value == pytest.approx(
            float(metrics[f"loop_exit_p{t}"]))


def test_the_readouts_lie_under_their_scope_inside_the_head_s():
    """``bps.head/loop_readout`` holds the final norm and the gate (in
    the scan over the passes) and the head's cross-entropy and the exit
    distribution (in the loss): what
    ``benchmark/layer_metrics/loop_readout_ms_per_step.py`` reads, and
    what ``model.blocks_xla_ms_per_step`` leaves out."""
    m = Transformer(CFG)
    tokens = tokens_of(2)
    params = seeded(init_shapes(m, tokens), 3)
    import re

    from benchmark.harness import scopes

    text = jax.jit(jax.grad(lambda p: lm_loss_fn(m, fused_head=True)(
        p, {}, {"tokens": tokens})[0])).lower(params).as_text(
        debug_info=True)
    under = [name for name in set(re.findall(r'loc\("([^"]+)"', text))
             if "loop_readout" in scopes.components(name)]
    assert under and all(scopes.parse(n).stage == "head" for n in under)
    # ``while``: the map of the head's cross-entropy over the passes
    for what in ("ln_f", "exit_gate", "while", "log_sigmoid"):
        assert any(what in n for n in under), what
    assert any("transpose(" in n for n in under)      # the backward too


# --------------------------------------------------- no cache for a loop


@pytest.mark.parametrize("method", [
    "decode", "prefill_chunk", "decode_paged", "prefill_chunk_paged",
    "decode_paged_fused", "verify_tokens", "verify_tokens_paged"])
def test_the_cache_paths_refuse_a_looped_model(method):
    m = Transformer(CFG)
    tokens = tokens_of(0, length=4)
    params = init_shapes(m, tokens)
    args = {
        "decode": (tokens, (), 0),
        "prefill_chunk": (tokens, (), 0, 3),
        "decode_paged": (tokens, (), jnp.zeros(4, jnp.int32), 0),
        "prefill_chunk_paged": (tokens, (), jnp.zeros(4, jnp.int32), 0, 3),
        "decode_paged_fused": (tokens, (), jnp.zeros((1, 4), jnp.int32),
                               jnp.zeros(1, jnp.int32),
                               jnp.zeros((1, 4), jnp.int32),
                               jnp.zeros((1, 4), jnp.int32)),
        "verify_tokens": (tokens, (), 0),
        "verify_tokens_paged": (tokens, (), jnp.zeros(4, jnp.int32), 0),
    }[method]
    with pytest.raises(NotImplementedError, match="looped model"):
        m.apply({"params": params}, *args, method=getattr(m, method))
