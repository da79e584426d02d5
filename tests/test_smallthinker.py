"""A per-layer attention layout (window + RoPE layers beside full NoPE
layers, 7 query heads a key-value head), the router that reads the
attention input, the softmax top-k rule and ReLU-gated experts, each
against ``benchmark/reference/smallthinker.py`` (plain float32
``jax.numpy`` from the family's equations) or a plain formula, on seeded
weights at a small size on the CPU: 8 layers = two periods of the
layout, 14 / 2 heads, window 16 at T 64, 8 experts top 3.
"""

import dataclasses
import os
import re
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.reference import smallthinker as ref
from byteps_tpu.integrations.smallthinker import smallthinker_config
from byteps_tpu.models.transformer import (Attention, Block, ExpertLayer,
                                           Transformer, init_cache)
from byteps_tpu.observability.metrics import get_registry
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.collectives import shard_map
from byteps_tpu.training import lm_loss_fn

import byteps_tpu.ops.flash_attention  # noqa: F401,E402

fa = sys.modules["byteps_tpu.ops.flash_attention"]

L, D, H, KV, DH, E, K, F, V, T, W = 8, 48, 14, 2, 8, 8, 3, 16, 96, 64, 16
# the published keys at the small size (what the reference reads)
HF = {"hidden_size": D, "num_attention_heads": H, "num_key_value_heads": KV,
      "head_dim": DH, "num_hidden_layers": L, "moe_ffn_hidden_size": F,
      "moe_num_primary_experts": E, "moe_num_active_primary_experts": K,
      "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
      "sliding_window_size": W, "sliding_window_layout": [0, 1, 1, 1] * 2,
      "rope_layout": [0, 1, 1, 1] * 2, "rope_theta": 1.5e6,
      "rope_scaling": None, "rms_norm_eps": 1e-6, "vocab_size": V,
      "max_position_embeddings": T, "tie_word_embeddings": False}
CFG = smallthinker_config(types.SimpleNamespace(**HF), attn_impl="flash")
SIZES = ref.sizes(HF)


def seeded(shapes, seed, std):
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    @jax.jit                       # one program, not one a leaf
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return [std * jax.random.normal(k, a.shape, jnp.float32)
                for k, a in zip(keys, leaves)]

    return jax.tree_util.tree_unflatten(
        treedef, make(jax.random.PRNGKey(seed)))


def init_shapes(module, *args):
    return jax.eval_shape(module.init, jax.random.PRNGKey(0), *args)["params"]


@pytest.fixture()
def small_blocks(monkeypatch):
    """Grid blocks of 32 cut into 16-wide sub-tiles: at T 64 a 2 x 2 grid
    whose window-16 band prunes sub-tiles, as 1024 / 256 / 4096 do at
    16 384 positions."""
    monkeypatch.setattr(fa, "_SUB_TILE", 16)
    monkeypatch.setattr(fa, "DEFAULT_BLOCK_Q", 32)
    monkeypatch.setattr(fa, "DEFAULT_BLOCK_K", 32)


# ---------------------------------------------------- the family's config


def test_the_family_config_maps_onto_the_model():
    assert (CFG.num_heads, CFG.kv_heads, CFG.d_head) == (H, KV, DH)
    assert CFG.attn_window_layout == (None, W, W, W) * 2
    assert CFG.rope_layout == (False, True, True, True) * 2
    assert (CFG.moe_experts, CFG.moe_top_k, CFG.moe_d_ff) == (E, K, F)
    assert (CFG.moe_scoring, CFG.moe_act, CFG.moe_router_pre_attn) == (
        "softmax_topk", "relu", True)
    assert (CFG.moe_shared, CFG.moe_scale, CFG.dense_layers) == (0, 1.0, 0)
    assert [CFG.layer_window(i) for i in range(4)] == [None, W, W, W]
    assert [CFG.layer_rope(i) for i in range(4)] == [False, True, True, True]
    # without a layout, or without a layer, the scalars hold
    plain = dataclasses.replace(CFG, attn_window_layout=None,
                                rope_layout=None, attn_window=5)
    assert plain.layer_window(3) == 5 and plain.layer_rope(0)
    assert CFG.layer_window(None) is None and CFG.layer_rope(None)
    # the benchmark's file maps too, at its published widths
    from benchmark.harness import manifest

    body = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "smallthinker-21b-l4-ep4.json"))
    tc = manifest.load_module("builders", body["builder"]).transformer_config(
        body, {"attn_impl": "flash", "remat": True})
    assert (tc.d_model, tc.num_heads, tc.kv_heads, tc.d_head) == (
        2560, 28, 4, 128)
    assert (tc.moe_experts, tc.moe_held, tc.moe_top_k, tc.moe_d_ff) == (
        64, (0, 16), 6, 768)
    assert tc.attn_window_layout == (None, 4096, 4096, 4096)
    assert tc.rope_layout == (False, True, True, True)
    assert (tc.vocab_size, tc.max_seq_len, tc.rope_theta) == (
        19456, 16384, 1.5e6)


@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False),
    ("norm_topk_prob", False),
    ("rope_scaling", {"rope_type": "linear", "factor": 2.0}),
    ("tie_word_embeddings", True),
    ("sliding_window_layout", [0, 1, 1, 1]),
    ("rope_layout", [0, 1, 1, 1] * 3),
])
def test_the_family_config_refuses_what_is_not_built(key, value):
    hf = types.SimpleNamespace(**dict(HF, **{key: value}))
    with pytest.raises(ValueError, match=key):
        smallthinker_config(hf)


def test_a_layout_of_the_wrong_length_or_a_learned_table_is_refused():
    short = dataclasses.replace(CFG, attn_window_layout=(None, W))
    with pytest.raises(ValueError, match="2 entries"):
        short.layer_window(0)
    learned = dataclasses.replace(CFG, pos_emb="learned")
    with pytest.raises(ValueError, match="learned"):
        learned.layer_rope(1)


def test_the_cache_paths_raise_on_a_layout():
    """Serving a model whose layers differ in kind needs a cache that
    knows the layer's kind: not built, and said so."""
    m = Transformer(CFG)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = init_shapes(m, tokens)
    caches = init_cache(CFG, 1, 16, layout="grouped")
    with pytest.raises(NotImplementedError, match="layout"):
        jax.eval_shape(lambda p: m.apply(
            {"params": p}, tokens, caches, 0, method=m.decode), params)


# ------------------------------------------- attention, one kind a layer


def dense_attention(n1, a, rotated, window):
    """The layout test's own reference: the full ``[T, T]`` mask at once
    (the benchmark's reference works in blocks of rows)."""
    q = jnp.einsum("td,dhk->thk", n1, a["q"]["kernel"])
    k = jnp.einsum("td,dhk->thk", n1, a["k"]["kernel"])
    v = jnp.einsum("td,dhk->thk", n1, a["v"]["kernel"])
    if rotated:
        q, k = ref.rope_halves(q, 1.5e6), ref.rope_halves(k, 1.5e6)
    k, v = (jnp.repeat(x, H // KV, axis=1) for x in (k, v))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = (i >= j) if window is None else (i >= j) & (i - j < window)
    s = jnp.where(keep[None], jnp.einsum("qhk,shk->hqs", q, k)
                  / np.sqrt(DH), -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("qhk,hkd->qd", o, a["o"]["kernel"])


@pytest.mark.parametrize("rotated", [False, True])
@pytest.mark.parametrize("window", [None, W])
def test_each_kind_of_layer_matches_a_dense_mask(small_blocks, window,
                                                 rotated):
    """Full + NoPE and window + RoPE (the published kinds) and the two
    crossed ones, through the interpreted flash kernels at 7 query heads
    a key-value head on a 2 x 2 grid: output and every gradient."""
    cfg = dataclasses.replace(
        CFG, num_layers=1, attn_window_layout=(window,),
        rope_layout=(rotated,))
    layer = Attention(cfg, layer=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, D))
    a = seeded(init_shapes(layer, x), 2, 0.3)

    def loss(f):
        return lambda a, x: jnp.sum(jnp.sin(f(a, x)))

    program = lambda a, x: layer.apply({"params": a}, x)[0]  # noqa: E731
    plain = lambda a, x: dense_attention(x[0], a, rotated, window)  # noqa
    blocks = lambda a, x: ref.attention(  # noqa: E731
        x[0], a, rotated, window, 1.5e6)
    with jax.default_matmul_precision("highest"):
        want = plain(a, x)
        np.testing.assert_allclose(program(a, x), want, rtol=2e-4,
                                   atol=2e-5)
        # the benchmark's reference (rows in blocks) says the same
        np.testing.assert_allclose(blocks(a, x), want, rtol=1e-5, atol=1e-6)
        got = jax.grad(loss(program), (0, 1))(a, x)
        exp = jax.grad(loss(plain), (0, 1))(a, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(exp)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4)
    # the kinds differ from one another: a crossed layout would show
    other = dense_attention(x[0], a, not rotated, window)
    assert not np.allclose(other, want, atol=1e-3)
    if window is not None:
        short = dense_attention(x[0], a, rotated, window - 1)
        assert not np.allclose(short, want, atol=1e-3)


def test_two_kinds_in_one_model_keep_a_record_and_a_name_each(small_blocks):
    """Tracing a model with both kinds: the tile gauges and
    ``flash.bwd_fused`` carry the band, the windowed kernels carry it in
    their names, and ``attn.layers`` counts the kinds."""
    reg = get_registry()
    reg.remove_prefix("flash.")
    reg.remove_prefix("attn.")
    m = Transformer(CFG)
    tokens = jnp.zeros((1, T), jnp.int32)
    params = init_shapes(m, tokens)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: lm_loss_fn(m)(
        p, {}, {"tokens": tokens})[0]))(params))
    names = set(re.findall(r"flash_(?:fwd|bwd)\w*", jaxpr))
    assert {"flash_fwd", f"flash_fwd_w{W}", "flash_bwd_dq_flash_bwd_dkv",
            f"flash_bwd_dq_flash_bwd_dkv_w{W}"} <= names
    assert reg.get("attn.layers", kind="full").value == 2
    assert reg.get("attn.layers", kind="window").value == 6
    total = (T // 16) ** 2
    for kernel in ("fwd", "bwd"):
        full = reg.get("flash.tiles_visited", kernel=kernel, window="none")
        band = reg.get("flash.tiles_visited", kernel=kernel, window=str(W))
        # the triangle of 4 x 4 sub-tiles; the band keeps its two diagonals
        assert (full.value, band.value) == (10, 7)
        for window in ("none", str(W)):
            assert reg.get("flash.tiles_total", kernel=kernel,
                           window=window).value == total
    for window in ("none", str(W)):
        assert reg.get("flash.bwd_fused", window=window).value == 1
    # a model without windows records under ``window="none"`` alone
    assert fa.tile_visits(T, 32, 32, 16, True, W)[0][(1, 0)] == 1


# ----------------------------------------------------------------- router


def test_router_takes_the_top_k_of_the_logits_then_their_softmax():
    kernel = jax.random.normal(jax.random.PRNGKey(3), (D, E))
    n1 = jax.random.normal(jax.random.PRNGKey(4), (40, D))
    idx, w = moe.route(n1, kernel, None, K, 1.0, scoring="softmax_topk")
    logits = np.asarray(n1, np.float64) @ np.asarray(kernel, np.float64)
    want = np.argsort(-logits, axis=-1)[:, :K]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    # softmax over the chosen == softmax over all, renormalised over them
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    picked = np.take_along_axis(p, np.asarray(idx), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    r_idx, r_w = ref.router(n1, {"kernel": kernel}, SIZES)
    assert np.array_equal(idx, r_idx)
    np.testing.assert_allclose(w, r_w, rtol=1e-6)
    # the sigmoid rule is another rule, and an unknown one is refused
    idx_s, w_s = moe.route(n1, kernel, jnp.zeros((E,)), K, 1.0)
    assert np.array_equal(np.sort(idx_s, -1), np.sort(idx, -1))
    assert not np.allclose(np.sort(w_s, -1), np.sort(w, -1), atol=1e-3)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(n1, kernel, None, K, 1.0, scoring="softmax")


def test_the_router_layer_has_no_bias_and_the_gate_is_relu():
    layer = ExpertLayer(CFG)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, D))
    shapes = init_shapes(layer, x)
    assert set(shapes["router"]) == {"kernel"}
    m = seeded(shapes, 6, 0.3)
    n1 = jax.random.normal(jax.random.PRNGKey(7), (1, 24, D))
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": m}, x, n1)[0]
        want = ref.routed(x[0], m, SIZES, ref.router(n1[0], m["router"],
                                                     SIZES))
        silu = ExpertLayer(dataclasses.replace(CFG, moe_act="silu")).apply(
            {"params": m}, x, n1)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert not np.allclose(silu, want, atol=1e-2)


@pytest.fixture(scope="module")
def one_block():
    """Block 1 (window + RoPE) at seeded weights, the reference's output
    for it, and the program's with the router on either input."""
    x = jax.random.normal(jax.random.PRNGKey(8), (1, T, D))
    block = Block(CFG, experts=True, layer=1)
    p = seeded(init_shapes(block, x), 9, 0.3)
    with jax.default_matmul_precision("highest"):
        want = ref.block(x[0], p, SIZES, 1)
        fed_n1 = block.apply({"params": p}, x)[0]
        fed_n2 = Block(dataclasses.replace(CFG, moe_router_pre_attn=False),
                       experts=True, layer=1).apply({"params": p}, x)[0]
    return want, fed_n1, fed_n2


def test_the_block_routes_from_its_attention_input(one_block):
    want, fed_n1, _ = one_block
    np.testing.assert_allclose(fed_n1, want, rtol=2e-4, atol=2e-4)


def test_a_router_fed_the_feed_forward_input_is_told(one_block):
    """The same comparison fails when the router reads ``ln2(x')``: other
    experts are chosen for most tokens."""
    want, _, fed_n2 = one_block
    worst = np.max(np.abs(fed_n2 - want), axis=-1)
    assert np.mean(worst > 1e-2) > 0.5


# ------------------------------------------------------- the expert layer


def layer_weights(seed, experts):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": {"kernel": jax.random.normal(k[0], (D, experts))},
            "experts": {
                "gate": 0.3 * jax.random.normal(k[1], (experts, D, F)),
                "up": 0.3 * jax.random.normal(k[2], (experts, D, F)),
                "down": 0.3 * jax.random.normal(k[3], (experts, F, D))}}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The deployment's cut in small: 64 experts, 6 a token, four shares
    of 16 — the parts that the shares ``(0, 16) ... (48, 16)`` give,
    added, equal the uncut reference layer (no shared expert to count
    once), every assignment served exactly once."""
    experts, top_k = 64, 6
    cfg = dataclasses.replace(CFG, moe_experts=experts, moe_top_k=top_k)
    sizes = SIZES._replace(top_k=top_k, held=experts)
    params = layer_weights(10, experts)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 40, D))
    n1 = jax.random.normal(jax.random.PRNGKey(12), (1, 40, D))
    with jax.default_matmul_precision("highest"):
        choice = ref.router(n1[0], params["router"], sizes)
        want = jax.jit(lambda p, x: ref.routed(x, p, sizes, choice))(
            params, x[0])
        total, held_total = jnp.zeros_like(x[0]), 0
        for first in range(0, experts, 16):
            share = dict(params, experts={
                n: a[first:first + 16]
                for n, a in params["experts"].items()})
            layer = ExpertLayer(dataclasses.replace(
                cfg, moe_held=(first, 16)))
            y, stats = jax.jit(lambda p, x, n1, layer=layer: layer.apply(
                {"params": p}, x, n1, mutable=["moe_stats"]))(share, x, n1)
            total = total + y[0]
            held_total += int(stats["moe_stats"]["assignments_held"])
            assert int(stats["moe_stats"]["rows_computed"]) == int(
                stats["moe_stats"]["assignments_held"])
            # the reference, given the same share, agrees share by share
            np.testing.assert_allclose(y[0], jax.jit(
                lambda p, x, c=sizes._replace(first=first, held=16):
                ref.routed(x, p, c, choice))(share, x[0]),
                rtol=1e-4, atol=1e-4)
    assert held_total == 40 * top_k      # every assignment, once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_the_ep_exchange_takes_the_new_rule_gate_and_router_input():
    """2 ranks of 4 experts over the CPU mesh, each with its own tokens
    and router inputs: outputs and the weights' gradients equal one rank
    holding all 8, under the softmax rule and the ReLU gate."""
    n = 2
    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
    w = layer_weights(13, E)
    x = jax.random.normal(jax.random.PRNGKey(14), (n * 16, D))
    n1 = jax.random.normal(jax.random.PRNGKey(15), (n * 16, D))
    rule = dict(top_k=K, scale=1.0, scoring="softmax_topk", act="relu")

    def layer(e, x, n1, **kw):
        return moe.expert_layer(x, w["router"]["kernel"], None, e["gate"],
                                e["up"], e["down"], router_x=n1, **rule,
                                **kw)

    def single(e):
        y, cnt = layer(e, x, n1)
        return jnp.sum(jnp.sin(y)), (y, cnt)

    def sharded(e):
        y, cnt = shard_map(
            lambda e, x, n1: layer(e, x, n1, axis_name="ep"), mesh,
            in_specs=(P("ep"), P("ep"), P("ep")),
            out_specs=(P("ep"), P()))(e, x, n1)
        return jnp.sum(jnp.sin(y)), (y, cnt)

    with jax.default_matmul_precision("highest"):
        (_, (y1, c1)), g1 = jax.value_and_grad(single, has_aux=True)(
            w["experts"])
        (_, (y2, c2)), g2 = jax.jit(jax.value_and_grad(
            sharded, has_aux=True))(w["experts"])
        want = ref.routed(x, w, SIZES, ref.router(n1, w["router"], SIZES))
    assert [int(c) for c in c1 + c2] == [x.shape[0] * K] * 4
    np.testing.assert_allclose(y1, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y2, y1, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g2),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


# ------------------------------------------------------- the whole model


@pytest.fixture(scope="module")
def whole():
    """Program and reference on one batch at seeded weights: logits, loss
    and gradients of all 8 layers (default grid blocks: one block a
    head at T 64, the whole-block masked path)."""
    m = Transformer(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(16), (1, T), 0, V)
    params = seeded(init_shapes(m, tokens), 17, 0.1)

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
    # sums (the flash kernel's online softmax, the row buffer's gathers)
    np.testing.assert_allclose(whole["logits"], whole["ref_logits"],
                               rtol=1e-3, atol=2e-4)
    assert whole["loss"] == pytest.approx(whole["ref_loss"], abs=2e-5)
    # the fused head is the benchmark's path
    assert whole["fused"] == pytest.approx(whole["ref_loss"], abs=2e-4)
    with jax.default_matmul_precision("highest"):
        assert ref.loss(whole["params"], whole["tokens"], HF) == (
            pytest.approx(whole["ref_loss"], abs=1e-5))
    # all 8 experts are held: every assignment of every layer is counted
    assert {k: int(v) for k, v in whole["counts"].items()} == {
        "moe_assignments_held": L * T * K, "moe_rows_computed": L * T * K}


def test_every_gradient_leaf_matches_the_reference(whole):
    """Every leaf of all 8 blocks, the table, the final norm and the head.
    rtol 5e-3 on a leaf's scale: the gradients pass through 8 softmaxes
    and 8 top-k weightings, each summed in another order than the
    reference's; a wrong mask, rotation, gate or router input moves a
    leaf by tens of per cent."""
    got = jax.tree_util.tree_leaves_with_path(whole["grads"])
    want = jax.tree_util.tree_leaves(whole["ref_grads"])
    assert len(got) == len(want) == 8 * 10 + 3
    for (path, g), w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path
        np.testing.assert_allclose(g, w, rtol=5e-3, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_a_recomputed_model_gives_the_same_loss_and_gradients(whole):
    rm = Transformer(dataclasses.replace(CFG, remat=True))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: lm_loss_fn(rm)(
            p, {}, {"tokens": whole["tokens"]})[0]))(whole["params"])
    assert float(loss) == pytest.approx(whole["loss"], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(whole["grads"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
