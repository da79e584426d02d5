"""A per-layer KIND layout of one-sublayer blocks — Mamba-2 mixers over
the chunked scan of ``ops/ssd_scan.py``, full NoPE attention at 16 query
heads a key-value head, sigmoid-routed non-gated relu^2 experts beside a
shared expert — each against ``benchmark/reference/nemotron_h.py``
(plain float32 ``jax.numpy``, the mixer by its recurrence) or a plain
formula, on seeded weights at a small size on the CPU: 18 blocks = the
nine-letter pattern twice, hidden 64, a mixer of 8 heads of 8 in 2
groups, state 16, chunk 16 at T 64, 16 / 1 heads, 16 experts top 6.
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
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.reference import joyai_flash as joyai_ref
from benchmark.reference import nemotron_h as ref
from byteps_tpu.integrations.nemotron_h import nemotron_h_config
from byteps_tpu.models.transformer import (ExpertLayer, Mamba2Mixer,
                                           SublayerBlock, Transformer,
                                           causal_depthwise_conv, init_cache)
from byteps_tpu.observability.metrics import get_registry
from byteps_tpu.ops.ssd_scan import ssd_scan, ssd_scan_with_states
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.collectives import shard_map
from byteps_tpu.training import lm_loss_fn

PATTERN = "MEMEM*EME" * 2
L, D, T, V = len(PATTERN), 64, 64, 96
MH, MP, MG, MN, MK, Q = 8, 8, 2, 16, 4, 16      # the mixer
H, KV, DH = 16, 1, 8                            # attention
E, K, F, FS = 16, 6, 16, 32                     # experts
# the published keys at the small size (what the reference reads)
HF = {"hidden_size": D, "num_hidden_layers": L,
      "hybrid_override_pattern": PATTERN, "num_attention_heads": H,
      "num_key_value_heads": KV, "head_dim": DH, "intermediate_size": F,
      "max_position_embeddings": T, "layer_norm_epsilon": 1e-5,
      "mamba_num_heads": MH, "mamba_head_dim": MP, "n_groups": MG,
      "ssm_state_size": MN, "conv_kernel": MK, "chunk_size": Q,
      "n_routed_experts": E, "num_experts_per_tok": K,
      "moe_intermediate_size": F, "n_shared_experts": 1,
      "moe_shared_expert_intermediate_size": FS,
      "routed_scaling_factor": 2.5, "vocab_size": V, "n_group": 1,
      "topk_group": 1, "mamba_proj_bias": False, "attention_bias": False,
      "mlp_bias": False, "tie_word_embeddings": False,
      "residual_in_fp32": False, "norm_topk_prob": True}
CFG = nemotron_h_config(types.SimpleNamespace(**HF), attn_impl="flash")
SIZES = ref.sizes(HF)


def seeded(shapes, seed, std):
    """Weights that make every leaf matter: matrices N(0, std); the
    mixers' decay rates, step biases and skips spread out; the router's
    bias of the size of a score gap."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit                       # one program, not one a leaf
    def make(key):
        out = []
        for i, (path, a) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = path[-1].key
            draw = jax.random.normal(k, a.shape, jnp.float32)
            if name == "A_log":
                out.append(jnp.log(jnp.arange(1.0, a.shape[0] + 1)))
            elif name == "dt_bias":
                out.append(-2.0 + draw)
            elif name == "D":
                out.append(1.0 + 0.3 * draw)
            elif name == "scale":
                out.append(1.0 + 0.1 * draw)
            elif path[-2].key == "conv":
                out.append(0.4 * draw)
            elif path[-2].key == "router" and name == "bias":
                out.append(0.05 * draw)
            else:
                out.append(std * draw)
        return out

    return jax.tree_util.tree_unflatten(
        treedef, make(jax.random.PRNGKey(seed)))


def init_shapes(module, *args):
    return jax.eval_shape(module.init, jax.random.PRNGKey(0), *args)["params"]


# ---------------------------------------------------- the family's config


def test_the_family_config_maps_onto_the_model():
    assert CFG.layer_kinds == ("mamba", "moe", "mamba", "moe", "mamba",
                               "attn", "moe", "mamba", "moe") * 2
    assert [CFG.layer_kind(i) for i in (0, 1, 5)] == ["mamba", "moe", "attn"]
    assert (CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_groups, CFG.ssm_state,
            CFG.ssm_conv, CFG.ssm_chunk) == (MH, MP, MG, MN, MK, Q)
    assert (CFG.num_heads, CFG.kv_heads, CFG.d_head, CFG.pos_emb) == (
        H, KV, DH, "none")
    assert (CFG.moe_experts, CFG.moe_top_k, CFG.moe_d_ff, CFG.moe_shared,
            CFG.moe_scale) == (E, K, F, FS // F, 2.5)
    assert (CFG.moe_scoring, CFG.moe_act, CFG.mlp) == (
        "sigmoid", "relu2", "relu2")
    assert CFG.block_cls() is SublayerBlock
    # without a kind layout the two-sublayer block stands, as before
    assert dataclasses.replace(CFG, layer_kinds=None).block_cls().__name__ == (
        "Block")
    # the benchmark's file maps too, at its published widths
    from benchmark.harness import manifest

    body = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "nemotron3-nano-30b-l9-ep16.json"))
    tc = manifest.load_module("builders", body["builder"]).transformer_config(
        body, {"attn_impl": "flash", "remat": True})
    assert tc.layer_kinds == ("mamba", "moe", "mamba", "moe", "mamba",
                              "attn", "moe", "mamba", "moe")
    assert (tc.d_model, tc.num_heads, tc.kv_heads, tc.d_head) == (
        2688, 32, 2, 128)
    assert (tc.ssm_heads, tc.ssm_head_dim, tc.ssm_groups, tc.ssm_state,
            tc.ssm_conv, tc.ssm_chunk) == (64, 64, 8, 128, 4, 128)
    assert (tc.moe_experts, tc.moe_held, tc.moe_top_k, tc.moe_d_ff,
            tc.moe_shared, tc.moe_scale) == (128, (0, 8), 6, 1856, 2, 2.5)
    assert (tc.vocab_size, tc.max_seq_len, tc.norm_eps) == (
        16384, 262144, 1e-5)


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("mamba_proj_bias", True),
    ("attention_bias", True), ("mlp_bias", True),
    ("tie_word_embeddings", True), ("residual_in_fp32", True),
    ("norm_topk_prob", False),
    ("hybrid_override_pattern", PATTERN[:-1] + "-"),
    ("hybrid_override_pattern", PATTERN[:9]),
    ("moe_shared_expert_intermediate_size", F + 1),
])
def test_the_family_config_refuses_what_is_not_built(key, value):
    hf = types.SimpleNamespace(**dict(HF, **{key: value}))
    with pytest.raises(ValueError, match=key):
        nemotron_h_config(hf)


def test_a_kind_layout_of_the_wrong_length_or_an_unknown_kind_is_refused():
    short = dataclasses.replace(CFG, layer_kinds=("mamba", "moe"))
    with pytest.raises(ValueError, match="2 entries"):
        short.layer_kind(0)
    odd = dataclasses.replace(CFG, layer_kinds=("conv",) * L)
    with pytest.raises(ValueError, match="unknown layer kind"):
        odd.layer_kind(0)
    with pytest.raises(ValueError, match="kind layout"):
        Transformer(dataclasses.replace(CFG, mtp_layers=1)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_the_cache_paths_raise_on_a_kind_layout():
    """Serving a model with recurrent-state layers needs a cache that
    holds a state beside the keys and values: not built, and said so."""
    m = Transformer(CFG)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), tokens)
    plain = dataclasses.replace(CFG, layer_kinds=None, moe_experts=0)
    caches = init_cache(plain, 1, 16)
    with pytest.raises(NotImplementedError, match="kind layout"):
        m.apply(params, tokens, caches, jnp.zeros((), jnp.int32),
                method=Transformer.decode)


def test_the_model_says_how_many_layers_of_each_kind_it_has():
    m = Transformer(CFG)
    jax.eval_shape(m.init, jax.random.PRNGKey(0),
                   jnp.zeros((1, T), jnp.int32))
    reg = get_registry()
    assert {k: reg.gauge("model.layers", kind=k).value
            for k in ("mamba", "moe", "attn")} == {
        "mamba": 8, "moe": 8, "attn": 2}
    assert reg.gauge("attn.layers", kind="full").value == 2
    assert reg.gauge("attn.layers", kind="window").value == 0
    for which in ("fwd",):
        assert reg.gauge("ssd.chunk", kernel=which).value == Q
        assert reg.gauge("ssd.chunks", kernel=which).value == T // Q
        assert reg.gauge("ssd.heads", kernel=which).value == MH


# -------------------------------------------------- the scan, by itself


def recurrence(x, dt, A, B, C, D):
    """The reference's recurrence on a batch: ``(y, final state)``."""
    def one(x, dt, B, C):
        return ref.recurrence(ref.MixerParts(x, dt, A, B, C, D, None))

    return jax.vmap(one)(x, dt, B, C)


def scan_inputs(seed, Bt, T, H, Pd, G, N, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (Bt, T, H, Pd)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (Bt, T, H)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (H,))),
            (0.5 * jax.random.normal(k[3], (Bt, T, G, N))).astype(dtype),
            (0.5 * jax.random.normal(k[4], (Bt, T, G, N))).astype(dtype),
            jax.random.normal(k[5], (H,)))


@pytest.mark.parametrize("T,H,Pd,G,N,chunk", [
    (64, 8, 8, 2, 16, 16),       # the mixer of this file
    (64, 8, 8, 8, 16, 32),       # a group a head
    (48, 4, 16, 1, 8, 16),       # one group for all heads
    (32, 16, 8, 2, 16, 8),       # 8 heads a group, as the cell's
])
def test_the_scan_matches_the_recurrence_value_and_every_gradient(
        T, H, Pd, G, N, chunk):
    """``ssd_scan`` interpreted against one position a step, in float32:
    the value and the gradients of all six arguments (``A`` stands for
    ``A_log``, ``dt`` for ``dt_bias``: the mixer test has those).  What
    is left is the order of the sums: 1e-5 of a leaf's scale."""
    args = scan_inputs(1, 2, T, H, Pd, G, N)
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, H, Pd))
    y = ssd_scan(*args, chunk=chunk)
    want, _ = recurrence(*args)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=chunk) * w),
                   argnums=tuple(range(6)))(*args)
    ref_g = jax.grad(lambda *a: jnp.sum(recurrence(*a)[0] * w),
                     argnums=tuple(range(6)))(*args)
    for name, g, r in zip("x dt A B C D".split(), got, ref_g):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("T", [16, 32, 80, 50, 7])
def test_the_state_is_carried_across_chunk_boundaries(T):
    """One chunk, two, many, and lengths that are no whole number of
    chunks (padded with ``dt = 0``: no decay, nothing added): the value,
    and the state each chunk entered with against the recurrence stopped
    there."""
    args = scan_inputs(3, 1, T, 4, 8, 2, 8)
    y, states = ssd_scan_with_states(*args, chunk=16)
    want, _ = recurrence(*args)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert states.shape == (1, -(-T // 16), 4, 8, 8)
    np.testing.assert_array_equal(states[:, 0], 0.0)
    for c in range(1, states.shape[1]):
        _, at = recurrence(*(a[:, :16 * c] if a.ndim > 1 else a
                             for a in args))
        np.testing.assert_allclose(states[:, c], at, rtol=1e-5, atol=1e-6)


def test_a_long_decay_does_not_overflow_above_the_diagonal():
    """``exp(cs_t - cs_s)`` above the diagonal would be ``exp(+800)``:
    masked before the ``exp``, so the value and the gradients stay
    finite."""
    x, dt, A, B, C, Dk = scan_inputs(4, 1, 32, 4, 8, 2, 8)
    A = jnp.full_like(A, -64.0)
    dt = jnp.full_like(dt, 0.8)
    out = jax.value_and_grad(lambda x, dt: jnp.sum(
        ssd_scan(x, dt, A, B, C, Dk, chunk=16)), argnums=(0, 1))(x, dt)
    assert all(bool(jnp.all(jnp.isfinite(a)))
               for a in jax.tree_util.tree_leaves(out))
    np.testing.assert_allclose(
        ssd_scan(x, dt, A, B, C, Dk, chunk=16),
        recurrence(x, dt, A, B, C, Dk)[0], rtol=1e-5, atol=1e-5)


def test_the_scan_in_bfloat16_carries_its_state_in_float32(monkeypatch):
    """bfloat16 operands: the output is within bfloat16 rounding of the
    recurrence on the same rounded inputs, and the carried state is exact
    to ~1e-5 — what a chunk adds goes in as a high and a low half — where
    a state carried in bfloat16 is off by 1e-3."""
    import byteps_tpu.ops.ssd_scan  # noqa: F401

    mod = sys.modules["byteps_tpu.ops.ssd_scan"]
    args = scan_inputs(5, 1, 128, 8, 8, 2, 16, jnp.bfloat16)
    f32 = tuple(a.astype(jnp.float32) for a in args)
    want, _ = recurrence(*f32)
    _, at = recurrence(*(a[:, :112] if a.ndim > 1 else a for a in f32))

    def gap():
        y, states = ssd_scan_with_states(*args, chunk=16)
        rel = jnp.linalg.norm(states[:, -1] - at, axis=(2, 3)) / (
            jnp.linalg.norm(at, axis=(2, 3)))
        return y, float(jnp.max(rel))

    y, sound = gap()
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=0.03,
                               atol=0.03 * float(jnp.max(jnp.abs(want))))
    assert sound < 3e-5
    monkeypatch.setattr(mod, "CARRY_DTYPE", jnp.bfloat16)
    assert gap()[1] > 1e-3


def test_heads_that_do_not_divide_into_groups_are_refused():
    x, dt, A, B, C, Dk = scan_inputs(6, 1, 16, 6, 8, 4, 8)
    with pytest.raises(ValueError, match="6 heads"):
        ssd_scan(x, dt, A, B, C, Dk, chunk=16)


# ----------------------------------------------------------- the mixer


@pytest.fixture(scope="module")
def mixer():
    layer = Mamba2Mixer(CFG)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, T, D))
    return layer, seeded(init_shapes(layer, x), 8, 0.3), x


def test_the_mixer_matches_the_reference_and_all_its_leaves_learn(mixer):
    """Output and the gradient of every leaf — ``A_log``, ``dt_bias``,
    ``D``, the convolution's kernel and bias, the norm's scale, both
    projections — against the reference's recurrence."""
    layer, params, x = mixer
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def program(p):
        y = layer.apply({"params": p}, x)
        return jnp.sum(y * w), y

    def reference(p):
        y = jax.vmap(lambda n: ref.mixer(n, p, SIZES))(x)
        return jnp.sum(y * w), y

    with jax.default_matmul_precision("highest"):
        (_, y), g = jax.jit(jax.value_and_grad(program, has_aux=True))(params)
        (_, want), r = jax.jit(jax.value_and_grad(reference, has_aux=True))(
            params)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    got = jax.tree_util.tree_leaves_with_path(g)
    assert len(got) == 8
    for (path, a), b in zip(got, jax.tree_util.tree_leaves(r)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_mixer_is_causal(mixer):
    """A change at position ``t`` moves nothing before ``t`` — through
    the convolution's taps and the scan alike — and does move ``t``."""
    layer, params, x = mixer
    t = 37
    moved = x.at[:, t].add(1.0)
    a = layer.apply({"params": params}, x)
    b = layer.apply({"params": params}, moved)
    np.testing.assert_array_equal(a[:, :t], b[:, :t])
    assert float(jnp.max(jnp.abs(a[:, t] - b[:, t]))) > 1e-3
    # the convolution by itself: position t sees t - 3 .. t
    u = jax.random.normal(jax.random.PRNGKey(10), (1, 12, 5))
    w = jax.random.normal(jax.random.PRNGKey(11), (4, 5))
    full = causal_depthwise_conv(u, w)
    np.testing.assert_allclose(
        full[0, 6], sum(w[k] * u[0, 3 + k] for k in range(4)), rtol=1e-6)
    np.testing.assert_allclose(full[0, 0], w[3] * u[0, 0], rtol=1e-6)
    bumped = causal_depthwise_conv(u.at[:, 6].add(1.0), w)
    np.testing.assert_array_equal(full[:, :6], bumped[:, :6])
    np.testing.assert_array_equal(full[:, 10:], bumped[:, 10:])


def test_the_mixers_scopes_are_in_the_lowered_program(mixer):
    """``in_proj``, ``conv``, ``ssd``, ``norm``, ``out_proj`` under the
    module's name: what ``benchmark/harness/module_spans.py`` reads."""
    layer, params, x = mixer
    text = jax.jit(lambda p, x: Mamba2Mixer(CFG, name="mamba").apply(
        {"params": p}, x)).lower(params, x).as_text(debug_info=True)
    for scope in ("in_proj", "conv", "ssd", "norm", "out_proj"):
        assert f"mamba/{scope}" in text, scope


# --------------------------------------------------- the expert layer


def test_the_routers_rule_is_the_sigmoid_rule_of_the_other_expert_cell():
    """Sigmoid scores, the top k of score + bias, the uncorrected scores
    of the chosen over their sum, times 2.5: ``route``'s default rule,
    this reference's and the joyai reference's agree on the same
    inputs."""
    x = jax.random.normal(jax.random.PRNGKey(12), (40, D))
    p = {"kernel": 0.3 * jax.random.normal(jax.random.PRNGKey(13), (D, E)),
         "bias": 0.2 * jax.random.normal(jax.random.PRNGKey(14), (E,))}
    with jax.default_matmul_precision("highest"):
        idx, w = moe.route(x, p["kernel"], p["bias"], K, 2.5)
        r_idx, r_w = ref.router(x, p, SIZES)
        j_idx, j_w = joyai_ref.router(x, p, types.SimpleNamespace(
            top_k=K, scale=2.5))
    np.testing.assert_array_equal(idx, r_idx)
    np.testing.assert_array_equal(r_idx, j_idx)
    np.testing.assert_allclose(w, r_w, rtol=1e-5)
    np.testing.assert_allclose(r_w, j_w, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.5, rtol=1e-5)
    # the bias moves the choice, not the weights
    free, _ = moe.route(x, p["kernel"], jnp.zeros((E,)), K, 2.5)
    assert bool(jnp.any(jnp.sort(free, axis=-1) != jnp.sort(idx, axis=-1)))


def layer_weights(seed, experts=E):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"router": {"kernel": 0.3 * jax.random.normal(k[0], (D, experts)),
                       "bias": 0.05 * jax.random.normal(k[1], (experts,))},
            "experts": {"up": 0.3 * jax.random.normal(k[2], (experts, D, F)),
                        "down": 0.3 * jax.random.normal(k[3],
                                                        (experts, F, D))},
            "shared": {"up": {"kernel": 0.3 * jax.random.normal(
                k[4], (D, FS))}, "down": {"kernel": 0.3 * jax.random.normal(
                    k[5], (FS, D))}}}


def test_a_non_gated_expert_has_no_gate_leaf_and_squares_its_relu():
    layer = ExpertLayer(CFG)
    x = jax.random.normal(jax.random.PRNGKey(15), (1, 24, D))
    shapes = init_shapes(layer, x)
    assert set(shapes["experts"]) == {"up", "down"}
    assert set(shapes["shared"]) == {"up", "down"}
    assert shapes["shared"]["up"]["kernel"].shape == (D, FS)
    assert set(shapes["router"]) == {"kernel", "bias"}
    params = layer_weights(16)
    with jax.default_matmul_precision("highest"):
        y = layer.apply({"params": params}, x)[0]
        want = ref.expert_layer(x[0], params, SIZES)
        # relu^2, not relu: the plain formula on one expert's rows
        one = ref.relu2_ffn(x[0], params["experts"]["up"][0],
                            params["experts"]["down"][0])
        np.testing.assert_allclose(one, jnp.square(jnp.maximum(
            x[0] @ params["experts"]["up"][0], 0.0))
            @ params["experts"]["down"][0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    # a gated layer keeps its three leaves
    gated = ExpertLayer(dataclasses.replace(CFG, moe_act="silu"))
    assert set(init_shapes(gated, x)["experts"]) == {"gate", "up", "down"}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The deployment's cut in small: 16 experts, 6 a token, four shares
    of 4 — the parts that the shares ``(0, 4) ... (12, 4)`` give, with
    the shared expert (which every chip computes alike) counted ONCE,
    add up to the uncut reference layer, every assignment served exactly
    once."""
    params = layer_weights(17)
    x = jax.random.normal(jax.random.PRNGKey(18), (1, 40, D))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.expert_layer(x, p, SIZES))(
            params, x[0])
        once = ref.shared(x[0], params)
        total, held_total = once, 0
        for first in range(0, E, 4):
            share = dict(params, experts={
                n: a[first:first + 4] for n, a in params["experts"].items()})
            layer = ExpertLayer(dataclasses.replace(
                CFG, moe_held=(first, 4)))
            y, stats = jax.jit(lambda p, x, layer=layer: layer.apply(
                {"params": p}, x, mutable=["moe_stats"]))(share, x)
            total = total + (y[0] - once)
            held_total += int(stats["moe_stats"]["assignments_held"])
            assert int(stats["moe_stats"]["rows_computed"]) == int(
                stats["moe_stats"]["assignments_held"])
            # the reference, given the same share, agrees share by share
            c = SIZES._replace(first=first, held=4)
            np.testing.assert_allclose(y[0], jax.jit(
                lambda p, x, c=c: ref.expert_layer(x, p, c))(share, x[0]),
                rtol=1e-4, atol=1e-4)
    assert held_total == 40 * K          # every assignment, once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_the_ep_exchange_takes_the_non_gated_form():
    """2 ranks of 8 experts over the CPU mesh, each with its own tokens:
    outputs and the weights' gradients equal one rank holding all 16,
    with no gate (two grouped products) and the sigmoid rule."""
    n = 2
    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
    w = layer_weights(19)
    x = jax.random.normal(jax.random.PRNGKey(20), (n * 16, D))
    rule = dict(top_k=K, scale=2.5, act="relu2")

    def layer(e, x, **kw):
        return moe.expert_layer(x, w["router"]["kernel"],
                                w["router"]["bias"], None, e["up"],
                                e["down"], **rule, **kw)

    def single(e):
        y, cnt = layer(e, x)
        return jnp.sum(jnp.sin(y)), (y, cnt)

    def sharded(e):
        y, cnt = shard_map(
            lambda e, x: layer(e, x, axis_name="ep"), mesh,
            in_specs=(P("ep"), P("ep")), out_specs=(P("ep"), P()))(e, x)
        return jnp.sum(jnp.sin(y)), (y, cnt)

    with jax.default_matmul_precision("highest"):
        (_, (y1, c1)), g1 = jax.value_and_grad(single, has_aux=True)(
            w["experts"])
        (_, (y2, c2)), g2 = jax.jit(jax.value_and_grad(
            sharded, has_aux=True))(w["experts"])
        want = ref.routed(x, w, SIZES, ref.router(x, w["router"], SIZES))
    assert [int(c) for c in c1 + c2] == [x.shape[0] * K] * 4
    np.testing.assert_allclose(y1, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y2, y1, rtol=1e-4, atol=1e-5)
    assert set(g1) == {"up", "down"}
    for a, b in zip(jax.tree_util.tree_leaves(g2),
                    jax.tree_util.tree_leaves(g1)):
        # (relu^2 doubles a rounding of its argument: gradients of
        # order 10, one element in 16 384 off by 3e-5)
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


# ------------------------------------------------------- the whole model


@pytest.fixture(scope="module")
def whole():
    """Program and reference on one batch at seeded weights: logits, loss
    and gradients of all 18 blocks."""
    m = Transformer(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(21), (1, T), 0, V)
    params = seeded(init_shapes(m, tokens), 22, 0.1)

    def program(p):
        return lm_loss_fn(m)(p, {}, {"tokens": tokens})[0]

    def reference(p):
        return ref.sequence_loss_sum(p, tokens[0], L, SIZES) / (T - 1)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(program))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference))(params)
        logits = m.apply({"params": params}, tokens)[0]
        ref_logits = ref.logits(params, tokens[0], L, SIZES)
        fused, _, counts = jax.jit(lm_loss_fn(m, fused_head=True))(
            params, {}, {"tokens": tokens})
    return dict(m=m, params=params, tokens=tokens, loss=float(loss),
                grads=grads, ref_loss=float(ref_loss), ref_grads=ref_grads,
                logits=logits, ref_logits=ref_logits, fused=float(fused),
                counts=counts)


def test_logits_and_loss_match_the_reference(whole):
    # float32 both sides at "highest": what is left is the order of the
    # sums (the chunked scan against the recurrence, the flash kernel's
    # online softmax, the row buffer's gathers)
    np.testing.assert_allclose(whole["logits"], whole["ref_logits"],
                               rtol=1e-3, atol=3e-4)
    assert whole["loss"] == pytest.approx(whole["ref_loss"], abs=2e-5)
    # the fused head is the benchmark's path
    assert whole["fused"] == pytest.approx(whole["ref_loss"], abs=2e-4)
    with jax.default_matmul_precision("highest"):
        assert ref.loss(whole["params"], whole["tokens"], HF) == (
            pytest.approx(whole["ref_loss"], abs=1e-5))
    # all 16 experts are held: every assignment of the 8 expert layers
    assert {k: int(v) for k, v in whole["counts"].items()} == {
        "moe_assignments_held": 8 * T * K, "moe_rows_computed": 8 * T * K}


def test_every_gradient_leaf_matches_the_reference(whole):
    """Every leaf of all 18 blocks — ``A_log``, ``dt_bias``, ``D``, the
    convolution and the gated norm's scale among them — the table, the
    final norm and the head.  rtol 5e-3 on a leaf's scale: the gradients
    pass through 8 scans, 8 top-k weightings and 2 softmaxes, each summed
    in another order than the reference's; a wrong group, tap, gate or
    norm order moves a leaf by tens of per cent.  (The router's bias
    takes part in the choice alone: its gradient is zero on both
    sides.)"""
    got = jax.tree_util.tree_leaves_with_path(whole["grads"])
    want = jax.tree_util.tree_leaves(whole["ref_grads"])
    # a norm a block; 8 leaves a mixer, 6 an expert layer, 4 attention
    assert len(got) == len(want) == 18 + 8 * 8 + 8 * 6 + 2 * 4 + 3
    for (path, g), w in zip(got, want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(w)))
        if "router" in name and "bias" in name:
            assert scale == 0 and not bool(jnp.any(g)), name
            continue
        assert scale > 0, name
        np.testing.assert_allclose(g, w, rtol=5e-3, atol=2e-3 * scale,
                                   err_msg=name)


def test_a_recomputed_model_gives_the_same_loss_and_gradients(whole):
    rm = Transformer(dataclasses.replace(CFG, remat=True))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: lm_loss_fn(rm)(
            p, {}, {"tokens": whole["tokens"]})[0]))(whole["params"])
    assert float(loss) == pytest.approx(whole["loss"], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(whole["grads"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_a_dense_relu2_block_runs_under_a_kind_layout():
    """The ``mlp`` kind: ``x + down(relu(up norm(x))^2)``."""
    cfg = dataclasses.replace(CFG, layer_kinds=("mlp",), num_layers=1,
                              d_ff=F)
    block = SublayerBlock(cfg, layer=0)
    x = jax.random.normal(jax.random.PRNGKey(23), (1, 8, D))
    p = seeded(init_shapes(block, x), 24, 0.3)
    with jax.default_matmul_precision("highest"):
        n = ref.rms_norm(x[0], p["norm"], 1e-5)
        want = x[0] + ref.relu2_ffn(n, p["mlp"]["up"]["kernel"],
                                    p["mlp"]["down"]["kernel"])
        got = block.apply({"params": p}, x)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
