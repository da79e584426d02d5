"""Latent attention, the expert layer, multi-token prediction and block
recomputation, each against ``benchmark/reference/joyai_flash.py`` (plain
float32 ``jax.numpy`` from the published equations) or a plain formula,
on seeded weights at a small size on the CPU.
"""

import dataclasses
import functools
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark.reference import joyai_flash as ref
from byteps_tpu.models.transformer import (LatentAttention, Transformer,
                                           TransformerConfig, apply_rope)
from byteps_tpu.observability.metrics import get_registry
from byteps_tpu.ops.flash_attention import flash_attention
from byteps_tpu.ops.grouped_matmul import (grouped_matmul, grouped_matmul_act,
                                          row_gather, sum_gather)
from byteps_tpu.parallel import moe
from byteps_tpu.parallel.collectives import shard_map
from byteps_tpu.parallel.ring_attention import local_attention
from byteps_tpu.training import lm_loss_fn, make_data_parallel_step
from byteps_tpu.training.step import (create_train_state,
                                      flush_step_counts)

D, H, E, K, F = 64, 4, 16, 4, 32
CFG = TransformerConfig(
    vocab_size=128, num_layers=2, num_heads=H, d_model=D, d_ff=128,
    max_seq_len=64, dtype=jnp.float32, attn_impl="local", pos_emb="rope",
    mlp="swiglu", rope_theta=32e6, attn_kind="mla", q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_interleave=True, moe_experts=E, moe_top_k=K, moe_d_ff=F,
    moe_shared=1, moe_scale=2.5, dense_layers=1, mtp_layers=1)
# the reference's view of the same sizes (the configuration file's keys)
REF = {"rms_norm_eps": 1e-6, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "kv_lora_rank": 16, "rope_theta": 32e6, "num_experts_per_tok": K,
       "routed_scaling_factor": 2.5, "n_routed_experts": E,
       "num_hidden_layers": 2, "assumed": {"mtp_loss_weight": 0.3}}
SIZES = ref.sizes(REF)


def seeded(shapes, seed, std=0.3):
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    @jax.jit                       # one program, not one a leaf
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return [std * jax.random.normal(k, a.shape, jnp.float32)
                for k, a in zip(keys, leaves)]

    return jax.tree_util.tree_unflatten(
        treedef, make(jax.random.PRNGKey(seed)))


def model_params(cfg=CFG, seed=0):
    """Seeded weights large enough that every term matters (norm scales
    and the router's bias included: the reference reads them too)."""
    m = Transformer(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0), tokens)["params"]
    return m, seeded(shapes, seed, 0.1)


def layer_weights(seed=0, experts=E):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": {"kernel": jax.random.normal(k[0], (D, E)),
                       "bias": jnp.zeros((E,))},
            "experts": {"gate": 0.3 * jax.random.normal(k[1], (experts, D, F)),
                        "up": 0.3 * jax.random.normal(k[2], (experts, D, F)),
                        "down": 0.3 * jax.random.normal(k[3], (experts, F, D))}}


def both(program, reference, *args):
    """``(output, gradients)`` of each of two functions of ``args``, the
    gradients of a loss that weighs every output element differently."""
    def run(f):
        def loss(*a):
            out = f(*a)
            return jnp.sum(jnp.sin(out)), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
        return out, grads

    return run(program), run(reference)


def compare(got, want, rtol=2e-3, atol=2e-4):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-4)
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol)


def program_layer(x, w, held=None, **kw):
    e = w["experts"]
    if held is not None:
        e = {n: a[held[0]:held[0] + held[1]] for n, a in e.items()}
    return moe.expert_layer(
        x, w["router"]["kernel"], w["router"]["bias"], e["gate"], e["up"],
        e["down"], top_k=K, scale=2.5, held=held, **kw)


# ------------------------------------------------------- latent attention


def test_rope_on_adjacent_pairs_matches_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 3, 8))
    got = apply_rope(x, jnp.arange(12), 32e6, interleave=True)[0]
    np.testing.assert_allclose(got, ref.rope_pairs(x[0], 32e6), atol=1e-6)
    # and differs from the half-split convention
    assert not np.allclose(got, apply_rope(x, jnp.arange(12), 32e6)[0])


def test_latent_attention_forward_and_gradients_match_the_reference():
    attn = LatentAttention(CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    shapes = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)["params"]
    params = seeded(shapes, 2)

    def program(params, x):
        return attn.apply({"params": params}, x)

    def reference(params, x):
        return jnp.stack([ref.mla(x[b], params, SIZES) for b in range(2)])

    with jax.default_matmul_precision("highest"):
        got, want = both(program, reference, params, x)
    compare(got, want)


# --------------------------------------------- flash with a narrower v


@pytest.mark.parametrize("window", [None, 300])
def test_flash_with_v_narrower_than_q_matches_plain_attention(window):
    """Causal, two sub-tiles a side (T = 512, s = 256), D = 24 / Dv = 16;
    forward and all three gradients."""
    B, T, Hh = 1, 512, 2
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(k[0], (B, T, Hh, 24))
    kk = jax.random.normal(k[1], (B, T, Hh, 24))
    v = jax.random.normal(k[2], (B, T, Hh, 16))
    do = jax.random.normal(k[3], (B, T, Hh, 16))

    def plain(q, kk, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(24)
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        keep = (i >= j) if window is None else (i >= j) & (i - j < window)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def flash(q, kk, v):
        return flash_attention(q, kk, v, causal=True, window=window)

    out = flash(q, kk, v)
    assert out.shape == (B, T, Hh, 16)
    np.testing.assert_allclose(out, plain(q, kk, v), rtol=2e-4, atol=2e-5)
    got = jax.vjp(flash, q, kk, v)[1](do)
    want = jax.vjp(plain, q, kk, v)[1](do)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4)


def test_flash_with_equal_widths_is_unchanged():
    """Where v is as wide as q the builders are keyed as before and the
    outputs equal plain attention's (the one-width kernels' tests hold
    the rest)."""
    fa = sys.modules["byteps_tpu.ops.flash_attention"]
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    q, kk, v = (jax.random.normal(k[i], (1, 512, 2, 16)) for i in range(3))
    fa._forward_call.cache_clear()
    out = flash_attention(q, kk, v, causal=True)
    np.testing.assert_allclose(
        out, local_attention(q, kk, v, causal=True), rtol=2e-4, atol=2e-5)
    assert fa._forward_call.cache_info().currsize == 1
    flash_attention(q, kk, v[..., :8], causal=True)     # another key
    assert fa._forward_call.cache_info().currsize == 2


# ----------------------------------------------------------------- router


def test_router_sigmoid_top_k_normalised_and_scaled():
    w = layer_weights(5)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, D))
    idx, weights = moe.route(x, w["router"]["kernel"], w["router"]["bias"],
                             K, 2.5)
    s = np.asarray(jax.nn.sigmoid(
        x.astype(jnp.float64) @ w["router"]["kernel"]))
    want = np.argsort(-s, axis=-1)[:, :K]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    r_idx, r_w = ref.router(x, w["router"], SIZES)
    assert np.array_equal(idx, r_idx)
    np.testing.assert_allclose(weights, r_w, rtol=1e-5)


def test_router_bias_changes_the_choice_and_not_the_weight():
    w = layer_weights(5)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, D))
    kernel = w["router"]["kernel"]
    idx0, w0 = moe.route(x, kernel, jnp.zeros((E,)), K, 2.5)
    bias = jnp.zeros((E,)).at[3].set(10.0)      # expert 3 always chosen
    idx1, w1 = moe.route(x, kernel, bias, K, 2.5)
    assert np.all(np.any(idx1 == 3, axis=-1))
    assert not np.all(np.any(idx0 == 3, axis=-1))
    # weights are the UNCORRECTED scores of the chosen
    s = jax.nn.sigmoid(x @ kernel)
    picked = jnp.take_along_axis(s, idx1, -1)
    np.testing.assert_allclose(
        w1, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # and the bias receives no gradient
    g = jax.grad(lambda b: moe.route(x, kernel, b, K, 2.5)[1].sum())(bias)
    assert not np.any(g)


# ------------------------------------------------------- the expert layer


def test_expert_layer_matches_the_reference_forward_and_gradients():
    w = layer_weights(8)
    x = jax.random.normal(jax.random.PRNGKey(9), (48, D))

    def program(w, x):
        return program_layer(x, w)[0]

    def reference(w, x):
        return ref.routed(x, w, SIZES)

    with jax.default_matmul_precision("highest"):
        got, want = both(program, reference, w, x)
    compare(got, want)


@pytest.mark.parametrize("tile", [8, 32])
def test_no_assignment_is_dropped_when_every_token_picks_one_expert(tile):
    """A routing that sends every token to expert 2 (and K - 1 others):
    300 rows on one expert, more than any capacity factor would hold."""
    w = layer_weights(10)
    w["router"]["bias"] = jnp.zeros((E,)).at[2].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(11), (300, D))
    y, counts = jax.jit(lambda x: program_layer(x, w, tile=tile))(x)
    n, served = counts["assignments_held"], counts["rows_computed"]
    idx, p = jax.jit(lambda x: (lambda idx: (idx, moe.plan(
        idx, 0, E, tile)))(moe.route(x, w["router"]["kernel"],
                                     w["router"]["bias"], K, 2.5)[0]))(x)
    assert int(n) == idx.size == 300 * K              # the router's count
    assert int(served) == int(n)                      # each has its row
    # a plan that loses ONE row shows in the second count, not the first
    lost = p._replace(valid=p.valid.at[int(p.dest[7, 0])].set(False))
    assert [int(c) for c in moe.served(idx, 0, E, lost)] == [
        300 * K, 300 * K - 1]
    assert int(p.count[2]) == 300 and int(p.valid.sum()) == 300 * K
    # every held assignment has a row of its own, and the row knows it
    rows = np.asarray(p.dest)[np.asarray(p.held)]
    assert len(set(rows.tolist())) == 300 * K
    assert np.array_equal(np.asarray(p.src)[rows], np.flatnonzero(p.held))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            y, jax.jit(lambda x: ref.routed(x, w, SIZES))(x),
            rtol=1e-4, atol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer():
    """The held-slice results of both shares of 8 experts, the shared
    expert counted once, equal the reference's uncut layer — through the
    Flax module, as a block calls it."""
    from byteps_tpu.models.transformer import ExpertLayer

    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, D))
    whole = ExpertLayer(CFG)
    shapes = jax.eval_shape(whole.init, jax.random.PRNGKey(0), x)["params"]
    params = seeded(shapes, 13)
    flat = x.reshape(-1, D)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.routed(x, p, SIZES)
                       + ref.shared(x, p))(params, flat)
        total = jnp.zeros_like(flat)
        held_total = 0
        for first in range(0, E, 8):
            cfg = dataclasses.replace(CFG, moe_held=(first, 8), moe_shared=0)
            share = dict(params, experts={
                n: a[first:first + 8]
                for n, a in params["experts"].items()})
            share.pop("shared")
            y, stats = jax.jit(lambda p, x, cfg=cfg: ExpertLayer(cfg).apply(
                {"params": p}, x, mutable=["moe_stats"]))(share, x)
            total = total + y.reshape(-1, D)
            held_total += int(stats["moe_stats"]["assignments_held"])
            assert int(stats["moe_stats"]["rows_computed"]) == int(
                stats["moe_stats"]["assignments_held"])
            # the reference, given the same share, agrees share by share
            sizes = ref.sizes(dict(REF, n_routed_experts=8), first)
            np.testing.assert_allclose(
                y.reshape(-1, D), jax.jit(lambda p, x, c=sizes: ref.routed(
                    x, p, c))(share, flat), rtol=1e-4, atol=1e-4)
        shared_once = jax.jit(lambda p, x: whole.apply(
            {"params": p}, x) - program_layer(x.reshape(-1, D), p)[0]
            .reshape(x.shape))(params, x)
        total = total + shared_once.reshape(-1, D)
    assert held_total == flat.shape[0] * K      # every assignment, once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_the_ep_exchange_equals_the_single_rank_layer():
    """2 ranks of 8 experts over the CPU mesh, each with its own tokens:
    outputs and the weights' gradients equal one rank holding all 16."""
    n = 2
    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
    w = layer_weights(14)
    x = jax.random.normal(jax.random.PRNGKey(15), (n * 16, D))

    def single(e, x):
        y, cnt = program_layer(x, dict(w, experts=e))
        return jnp.sum(jnp.sin(y)), (y, cnt)

    def sharded(e, x):
        def rank(e, x):
            y, cnt = moe.expert_layer(
                x, w["router"]["kernel"], w["router"]["bias"], e["gate"],
                e["up"], e["down"], top_k=K, scale=2.5, axis_name="ep")
            return y, cnt

        y, cnt = shard_map(rank, mesh, in_specs=(P("ep"), P("ep")),
                           out_specs=(P("ep"), P()))(e, x)
        return jnp.sum(jnp.sin(y)), (y, cnt)

    with jax.default_matmul_precision("highest"):
        (_, (y1, c1)), g1 = jax.value_and_grad(single, has_aux=True)(
            w["experts"], x)
        (_, (y2, c2)), g2 = jax.jit(jax.value_and_grad(
            sharded, has_aux=True))(w["experts"], x)
    assert [int(c) for c in (*c1.values(), *c2.values())] == [
        x.shape[0] * K] * 4
    np.testing.assert_allclose(y2, y1, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g2),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_grouped_matmul_skips_inactive_tiles_and_matches_per_group():
    G, tm, Kd, N = 3, 8, 16, 24
    tile_group = jnp.array([0, 0, 1, 2, 2, 2], jnp.int32)   # 6 tiles
    k = jax.random.split(jax.random.PRNGKey(16), 3)
    x = jax.random.normal(k[0], (6 * tm, Kd))
    w = jax.random.normal(k[1], (G, Kd, N))
    dy = jax.random.normal(k[2], (6 * tm, N))
    active = 4                      # tiles 4 and 5 hold no real row

    def want(x, w):
        rows = [x[i * tm:(i + 1) * tm] @ w[tile_group[i]]
                for i in range(active)]
        return jnp.concatenate(rows)

    out, vjp = jax.vjp(lambda x, w: grouped_matmul(
        x, w, tile_group, jnp.int32(active)), x, w)
    np.testing.assert_allclose(out[:active * tm], want(x, w), rtol=1e-5,
                               atol=1e-5)
    live = jnp.arange(6 * tm)[:, None] < active * tm
    dx, dw = vjp(jnp.where(live, dy, 0))
    rx, rw = jax.vjp(want, x, w)[1](dy[:active * tm])
    np.testing.assert_allclose(dx[:active * tm], rx[:active * tm],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, rw, rtol=1e-5, atol=1e-5)


FIRST_FORMS = {"silu": 2, "relu": 2, "relu2": 1}     # matrices a form


@pytest.mark.parametrize("act", list(FIRST_FORMS))
def test_first_projection_pair_matches_per_group(act):
    """``grouped_matmul_act`` and its backward kernel against the
    per-group ``jnp`` products: ``h``, ``dx``, every ``dw`` and the
    stored ``dg`` / ``du`` on the active tiles — with inactive tiles, a
    group that holds no assignment (one tile, as the layout gives it)
    and a large finite value in every row that holds none."""
    gm = sys.modules["byteps_tpu.ops.grouped_matmul"]
    G, tm, Kd, N = 4, 8, 16, 24
    tile_group = jnp.array([0, 0, 1, 2, 3, 3, 3, 3], jnp.int32)
    active = 6                       # tiles 6 and 7 hold no real row
    real = np.array([8, 8, 5, 0, 8, 3, 0, 0])     # rows a tile that do
    valid = jnp.asarray((np.arange(tm)[None, :] < real[:, None]).reshape(-1))
    k = jax.random.split(jax.random.PRNGKey(24), 4)
    x = jnp.where(valid[:, None], jax.random.normal(k[0], (8 * tm, Kd)), 1e3)
    ws = tuple(0.3 * jax.random.normal(kk, (G, Kd, N))
               for kk in jax.random.split(k[1], FIRST_FORMS[act]))
    dh = jnp.where(valid[:, None], jax.random.normal(k[2], (8 * tm, N)), 0)
    fn = moe.UNGATED[act] if len(ws) == 1 else moe.GATES[act]
    live = slice(0, active * tm)

    def epilogue(*pre):
        return fn(pre[0]) * pre[1] if len(pre) == 2 else fn(pre[0])

    def want(x, ws):
        pre = [jnp.concatenate([x[i * tm:(i + 1) * tm] @ w[tile_group[i]]
                                for i in range(active)]) for w in ws]
        return epilogue(*pre), pre

    with jax.default_matmul_precision("highest"):
        h, vjp = jax.vjp(lambda x, ws: grouped_matmul_act(
            x, ws, tile_group, jnp.int32(active), fn), x, ws)
        dx, dws = vjp(dh)
        (rh, pre), rvjp = jax.vjp(want, x, ws)
        rdx, rdws = rvjp((dh[live], [jnp.zeros_like(a) for a in pre]))
        # the stored products and their gradients, as the kernels write
        # them for the weight-gradient products
        got_h, *got_pre = gm._act_fwd_pass(
            x, ws, tile_group, jnp.int32(active), fn, True, None)
        *dpre, dx2 = gm._act_bwd_pass(
            dh, got_pre, ws, tile_group, jnp.int32(active), fn, None)
        rdpre = jax.vjp(epilogue, *pre)[1](dh[live])
    close = functools.partial(np.testing.assert_allclose, rtol=1e-5,
                              atol=1e-3)       # beside rows of 1e3
    close(h[live], rh)
    close(got_h[live], rh)
    close(dx[live], rdx[live])
    close(dx2[live], rdx[live])
    assert len(dws) == len(ws) == len(dpre)
    for got, ref_ in zip(list(dws) + got_pre + dpre,
                         list(rdws) + pre + list(rdpre)):
        got = got if got.ndim == 3 else got[live]
        close(got, ref_)
    for dw in dws:                   # the group that holds no assignment
        assert np.any(dw[0]) and not np.any(dw[2])
    for d in dpre:                   # exactly zero where dh is
        assert not np.any(np.asarray(d[live])[~np.asarray(valid[live])])


def test_the_activation_is_looked_up_when_the_layer_is_traced(monkeypatch):
    """One place holds ``act``: a stand-in planted in ``moe.UNGATED``
    after a first call changes what the next computes, forward and
    backward (the call builders are keyed by the function, not by its
    name)."""
    k = jax.random.split(jax.random.PRNGKey(25), 3)
    x = jax.random.normal(k[0], (TOKENS, D))
    up = 0.3 * jax.random.normal(k[1], (4, D, F))
    down = 0.3 * jax.random.normal(k[2], (4, F, D))
    idx = jnp.tile(jnp.arange(4), (TOKENS, 1))[:, :2]
    p = moe.plan(idx, 0, 4, 8)

    def run():
        return jax.value_and_grad(lambda up: jnp.sum(jnp.sin(moe.experts_ffn(
            moe.dispatch(x, p), None, up, down, p, act="relu2")[
                p.dest.reshape(-1)])))(up)

    first = run()
    monkeypatch.setitem(moe.UNGATED, "relu2", jax.nn.relu)
    planted = run()
    assert abs(float(first[0]) - float(planted[0])) > 1e-3
    assert not np.allclose(first[1], planted[1], atol=1e-3)
    monkeypatch.setitem(moe.UNGATED, "relu2", moe.relu2)
    np.testing.assert_allclose(run()[1], first[1], rtol=1e-6)


def test_layer_counters_are_in_the_registry():
    w = layer_weights(17)
    x = jax.random.normal(jax.random.PRNGKey(18), (40, D))
    _, counts = jax.jit(
        lambda x: program_layer(x, w, held=(4, 8), tile=8))(x)
    n, served = counts["assignments_held"], counts["rows_computed"]
    reg = get_registry()
    gauges = reg.snapshot()["gauges"]
    rows = moe.buffer_rows(40, K, 8, 8)
    assert gauges["moe.experts_held"] == 8
    assert gauges["moe.rows_buffer"] == rows
    assert 0 < int(n) == int(served) < 40 * K
    # what each of the four gathers writes a call, from its shapes and
    # dtypes as traced (nothing runs): the buffer's rows for the two
    # that fill it, k rows a token for the two that read it back
    jax.eval_shape(jax.grad(lambda x: program_layer(
        x.astype(jnp.bfloat16), w, held=(4, 8), tile=8)[0].sum().astype(
            jnp.float32)), x)
    for op, n_rows in (("dispatch", rows), ("combine", 40 * K),
                       ("dispatch_bwd", 40 * K), ("combine_bwd", rows)):
        assert reg.get("moe.gathered_mb", op=op).value == pytest.approx(
            n_rows * D * 2 / 1e6), op


# ------------------------ the four gathers, by layout, routing and form

FORMS = {   # (k, held count, act, scoring): the three cells' layers
    "relu-gated-k6-of-16": (6, 16, "relu", "softmax_topk"),
    "silu-gated-k8-of-16": (8, 16, "silu", "sigmoid"),
    "relu2-ungated-k6-of-8": (6, 8, "relu2", "sigmoid"),
}
ALL, FIRST, TOKENS = 32, 8, 40      # experts routed over; first held


def form_weights(count, act):
    k = jax.random.split(jax.random.PRNGKey(20), 4)
    e = {"up": 0.3 * jax.random.normal(k[1], (count, D, F)),
         "down": 0.3 * jax.random.normal(k[2], (count, F, D))}
    if act not in moe.UNGATED:
        e["gate"] = 0.3 * jax.random.normal(k[3], (count, D, F))
    return {"kernel": jax.random.normal(k[0], (D, ALL)), "experts": e}


def routing_case(routing, count):
    """``(x, router bias, router kernel's row 0)`` that steer the choice:
    the second held expert never chosen; or the first eight tokens on
    experts held elsewhere only; or every token on the third held
    expert."""
    x = jax.random.normal(jax.random.PRNGKey(21), (TOKENS, D))
    bias, row0 = jnp.zeros((ALL,)), None
    if routing == "empty_expert":
        bias = bias.at[FIRST + 1].set(-1e3)
    elif routing == "one_expert":
        bias = bias.at[FIRST + 2].set(1e3)
    else:
        x = x.at[:8, 0].set(20.0)
        here = (jnp.arange(ALL) >= FIRST) & (jnp.arange(ALL) < FIRST + count)
        row0 = jnp.where(here, -3.0, 3.0)
    return x, bias, row0


def per_token_reference(x, w, bias, k, count, act, scoring):
    """Every held expert over every token, no buffer: a token's result
    is the sum over its chosen held experts, each by its weight."""
    idx, weights = moe.route(x, w["kernel"], bias, k, 2.5, scoring)
    e, y = w["experts"], 0.0
    for i in range(count):
        h = x @ e["up"][i]
        h = (moe.UNGATED[act](h) if "gate" not in e
             else moe.GATES[act](x @ e["gate"][i]) * h)
        coef = jnp.sum(jnp.where(idx == FIRST + i, weights, 0.0), axis=-1)
        y = y + coef[:, None] * (h @ e["down"][i])
    return y


@pytest.mark.parametrize("routing", ["empty_expert", "unheld_tokens",
                                     "one_expert"])
@pytest.mark.parametrize("form", list(FORMS))
def test_the_layer_matches_the_per_token_sum_at_every_layout(form, routing):
    """Forward value and every gradient (tokens, router kernel, gate /
    up / down) at the three cells' ``(k, held, form)``, each under a
    routing that leaves a group empty, tokens without a held expert,
    or one expert with every token."""
    k, count, act, scoring = FORMS[form]
    w = form_weights(count, act)
    x, bias, row0 = routing_case(routing, count)
    if row0 is not None:
        w["kernel"] = w["kernel"].at[0].set(row0)

    def program(w, x):
        e = w["experts"]
        return moe.expert_layer(
            x, w["kernel"], bias, e.get("gate"), e["up"], e["down"],
            top_k=k, scale=2.5, held=(FIRST, count), tile=8,
            scoring=scoring, act=act)[0]

    def reference(w, x):
        return per_token_reference(x, w, bias, k, count, act, scoring)

    idx = moe.route(x, w["kernel"], bias, k, 2.5, scoring)[0]
    p = moe.plan(idx, FIRST, count, 8)
    assert {"empty_expert": int(p.count[1]) == 0,
            "unheld_tokens": not np.any(p.held[:8]) and np.any(p.held),
            "one_expert": int(p.count[2]) == TOKENS}[routing]
    with jax.default_matmul_precision("highest"):
        got, want = both(program, reference, w, x)
    compare(got, want)


def test_weight_gradients_ignore_the_rows_that_hold_no_assignment():
    """The buffer's rows with ``valid == False`` are never masked after
    the gather: whatever lies there (a large finite value here) meets a
    zero gradient, so no expert's weight gradient moves."""
    w = layer_weights(22)
    x = jax.random.normal(jax.random.PRNGKey(23), (TOKENS, D))
    idx, weights = moe.route(x, w["router"]["kernel"], w["router"]["bias"],
                             K, 2.5)
    p = moe.plan(idx, 4, 8, 8)
    assert 0 < int(p.valid.sum()) < p.valid.size

    def loss(e, fill):
        rows = moe.dispatch(x, p)
        if fill is not None:
            rows = jnp.where(p.valid[:, None], rows, fill)
        out = moe.experts_ffn(rows, e["gate"], e["up"], e["down"], p)
        return jnp.sum(jnp.sin(moe.combine(out, weights, p)))

    held = {n: a[4:12] for n, a in w["experts"].items()}
    with jax.default_matmul_precision("highest"):
        as_built, filled = (jax.jit(jax.grad(lambda e: loss(e, fill)))(held)
                            for fill in (None, 1e3))
    for n in held:
        assert np.any(as_built[n])
        np.testing.assert_allclose(filled[n], as_built[n], rtol=1e-6,
                                   atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _calls(eqns):
    return sum(e.primitive.name == "pallas_call" for e in eqns)


def test_no_token_major_fold_and_no_split_by_tokens():
    """Abstract shapes, nothing runs.  At ``k = 6`` no intermediate of
    ``value_and_grad`` of a layer is ``[T, k, d]`` (the chip pads ``k``
    to a tile's sublanes there), and the layer lowers as many
    ``pallas_call``s at 16 384 tokens as at 512: no shape makes it run
    in token chunks, which lengthens the step program and its set-up.
    And nothing but kernels stands between the first projection's
    kernels: no equation outside a ``pallas_call`` writes an ``[R, f]``
    array (the activation and its derivative ride in the kernels), and
    no ``add_any`` an ``[R, d]`` one (``dx`` is summed over gate and up
    in the backward kernel)."""
    k, count, f = 6, 16, 32
    calls = {}
    for T in (512, 16384):
        sds = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
            shape, jnp.bfloat16)

        def loss(x, kernel, gate, up, down):
            return moe.expert_layer(
                x, kernel, None, gate, up, down, top_k=k, scale=1.0,
                scoring="softmax_topk", act="relu")[0].astype(
                    jnp.float32).sum()

        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4)))(
            sds(T, D), sds(D, count), sds(count, D, f), sds(count, D, f),
            sds(count, f, D))
        eqns = list(_eqns(jaxpr.jaxpr))
        shapes = {v.aval.shape for e in eqns for v in e.outvars}
        assert (k, T, D) in shapes and (T, k, D) not in shapes
        calls[T] = _calls(eqns)
        R = moe.buffer_rows(T, k, count, moe.ROW_TILE)
        wrote = lambda shape: sorted({  # noqa: E731
            e.primitive.name for e in eqns
            if any(v.aval.shape == shape for v in e.outvars)})
        # (a barrier orders its operands and moves nothing)
        assert set(wrote((R, f))) - {"optimization_barrier"} == {
            "pallas_call"}
        assert "add_any" not in wrote((R, D)) and "pallas_call" in wrote(
            (R, D))
    assert calls[512] == calls[16384] == 7     # 2 forward + 5 backward


@pytest.mark.parametrize("form", list(FORMS))
def test_expert_calls_gauge_is_what_a_layer_traces(form):
    """``moe.expert_calls{pass}``: the ``pallas_call``s of one layer,
    forward and backward, as its jaxprs count them (nothing runs)."""
    k, count, act, scoring = FORMS[form]
    w = form_weights(count, act)
    e = w["experts"]

    def layer(x, e):
        return moe.expert_layer(
            x, w["kernel"], None, e.get("gate"), e["up"], e["down"],
            top_k=k, scale=2.5, held=(FIRST, count), tile=8,
            scoring=scoring, act=act)[0].sum()

    x = jnp.zeros((TOKENS, D))
    reg = get_registry()
    for pass_ in ("fwd", "bwd"):
        reg.gauge("moe.expert_calls", **{"pass": pass_}).set(0)
    fwd = _calls(_eqns(jax.make_jaxpr(layer)(x, e).jaxpr))
    both_ = _calls(_eqns(jax.make_jaxpr(jax.value_and_grad(
        layer, argnums=(0, 1)))(x, e).jaxpr))
    got = {pass_: reg.get("moe.expert_calls", **{"pass": pass_}).value
           for pass_ in ("fwd", "bwd")}
    assert got == {"fwd": fwd, "bwd": both_ - fwd}
    assert got == {"fwd": 2, "bwd": 4 if "gate" not in e else 5}


# ------------------------------------- the gathers from HBM, by DMA

GD = 256            # a width whose rows move alone in bf16 and float32

# how many of token t's k assignments fall on the held experts (None: as
# the router chooses) — the cases the flat walk of a tile's held rows
# must get right, tile (8 tokens) by tile
HELD = {
    "routed": None,
    # lists of 17, 14, 16, 18 ... rows at k 4: most not a multiple of 8
    "ragged": lambda t, k: (3 * t + 1) % (k + 1),
    # the second tile moves nothing
    "empty_tile": lambda t, k: 0 if 8 <= t < 16 else (3 * t + 1) % (k + 1),
    # each tile's first token holds all k
    "full_token": lambda t, k: k if t % 8 == 0 else t % k,
    # no token holds more than one: the sum walks one slot
    "one_a_token": lambda t, k: t % 2,
}


def dma_case(k, dtype, seed=30, d=GD, held="routed"):
    """``(x, weights, plan, experts)`` at width ``d``: tokens partly held
    (FIRST .. FIRST + 8 of ALL) — as routed (one held group empty) or
    token by token as ``HELD[held]`` says — tiles past ``n_active``."""
    count, tile = 8, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (64, d)).astype(dtype)
    if HELD[held] is None:
        bias = jnp.zeros((ALL,)).at[FIRST + 1].set(-1e3)
        idx, weights = moe.route(x, jax.random.normal(ks[1], (d, ALL)),
                                 bias, k, 2.5)
    else:
        rng = np.random.default_rng(seed)
        outside = [e for e in range(ALL) if not FIRST <= e < FIRST + count]
        idx = jnp.asarray(np.stack([rng.permutation(np.concatenate([
            FIRST + rng.choice(count, n, replace=False),
            rng.choice(outside, k - n, replace=False)]))
            for n in (HELD[held](t, k) for t in range(64))]), jnp.int32)
        weights = 0.1 + jax.random.uniform(ks[1], (64, k))
    p = moe.plan(idx, FIRST, count, tile)
    assert 0 < int(p.held.sum()) < p.held.size
    assert int(p.n_active) < p.tile_group.shape[0]
    if held == "routed":
        assert int(p.count[1]) == 0
    # products of GD's size at every width
    std = 0.3 * (GD / d) ** 0.5
    e = {n: (std * jax.random.normal(kk, shape)).astype(dtype)
         for n, kk, shape in (("gate", ks[2], (count, d, F)),
                              ("up", ks[3], (count, d, F)),
                              ("down", ks[4], (count, F, d)))}
    return x, weights, p, e


def assert_same(got, want, dtype, scaled=False):
    got, want = (np.asarray(jnp.asarray(a, jnp.float32)) for a in (got, want))
    # the same float32 sums in the same order; the CPU's XLA fuses some
    # multiply-adds on one side and not the other, so a bf16 result may
    # round one place apart and a float32 one differ in its last bits
    if dtype == jnp.bfloat16 and scaled:
        # ... and through the bf16 products of the experts that place
        # moves by a few of the largest value's
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=2 ** -6 * np.max(np.abs(want)))
    elif dtype == jnp.bfloat16:
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=0)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.max(np.abs(want)))


def assert_bits(got, want):
    np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                  np.asarray(want).view(np.uint8))


DMA_CASES = [pytest.param(k, dtype, GD, "routed", id=f"{k}-{name}")
             for k in (4, 6, 8)
             for dtype, name in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32"))
             ] + [pytest.param(k, dtype, d, held, id=f"{k}-{name}-{d}-{held}")
                  for k, dtype, name, d, held in (
                      (6, jnp.bfloat16, "bf16", 2048, "ragged"),
                      (4, jnp.float32, "f32", 2048, "ragged"),
                      (8, jnp.bfloat16, "bf16", 2560, "ragged"),
                      (6, jnp.bfloat16, "bf16", 2048, "empty_tile"),
                      (6, jnp.bfloat16, "bf16", 2048, "full_token"),
                      (8, jnp.float32, "f32", 2560, "full_token"),
                      (6, jnp.bfloat16, "bf16", 2048, "one_a_token"))]


@pytest.mark.parametrize("k, dtype, d, held", DMA_CASES)
def test_the_dma_gathers_give_what_xla_gives(k, dtype, d, held):
    """``row_gather`` is ``source[tok]`` on every row of an active tile
    (the rows past ``n_active`` are left unwritten — the interpreter
    leaves NaN there); ``sum_gather`` is the XLA path's held sum bit for
    bit, without weights and with weights whose products are exact (a
    product that rounds may be fused into its add on one side only:
    ``assert_same``); and ``dispatch`` / ``combine`` around the experts
    give the XLA path's values and gradients, so no reader touches an
    unwritten row."""
    x, weights, p, e = dma_case(k, dtype, d=d, held=held)
    tile = moe._tile(p)
    live = int(p.n_active) * tile
    rows = row_gather(x, p.tok, p.n_active, tile)
    assert np.all(np.isfinite(np.asarray(rows[:live], np.float32)))
    assert_bits(rows[:live], x[p.tok][:live])
    exact = 2.0 ** -jax.random.randint(jax.random.PRNGKey(31), weights.shape,
                                       0, 4).astype(jnp.float32)
    for w in (None, exact, weights):
        got = sum_gather(rows, p.dest, p.held, p.n_active, w, tile)
        want = moe._sum_held("op", rows, p, 1 << 60, None, w)
        if w is weights:
            assert_same(got, want, dtype)
        else:
            assert_bits(got, want)

    def loss(x, e, weights, free):
        rows = moe.dispatch(x, p, free)
        out = moe.experts_ffn(rows, e["gate"], e["up"], e["down"], p)
        y = moe.combine(out, weights, p, free)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), y

    runs = [jax.jit(jax.value_and_grad(
        functools.partial(loss, free=free), argnums=(0, 1, 2),
        has_aux=True))(x, e, weights) for free in (0, 1 << 60)]
    dma, xla = (jax.tree_util.tree_leaves(r) for r in runs)
    for got, want in zip(dma, xla):
        assert np.all(np.isfinite(np.asarray(got, np.float32)))
        assert_same(got, want, dtype, scaled=True)


def test_a_tiles_list_holds_its_held_rows_and_the_step_counts_them(
        monkeypatch):
    """``moves``: a tile's list is its held rows in the order ``(t, j)``,
    each to its slot, its length ``held.sum()`` a tile and its fullest
    token's count beside it.  And a train step whose expert layer moves
    rows by DMA (here: every gather, VMEM taken as full) grows
    ``moe.dma_rows{op}`` by that many rows a step — the lists' lengths
    for the two sums, the active tiles' rows for the two row gathers."""
    gm = sys.modules["byteps_tpu.ops.grouped_matmul"]
    k, tile = 6, 8
    _, _, p, _ = dma_case(k, jnp.float32, held="ragged")
    held, dest = np.asarray(p.held), np.asarray(p.dest)
    n = held.sum(axis=1)
    order = np.argsort(~held, axis=1, kind="stable")      # held first
    packed = np.where(np.arange(k) < n[:, None],
                      np.take_along_axis(dest, order, axis=1), 0)
    at, slot, size = (np.asarray(a) for a in gm.moves(
        jnp.asarray(n, jnp.int32), k, tile))
    tiles = held.reshape(-1, tile * k)
    assert size[:, 0].tolist() == tiles.sum(axis=1).tolist()
    assert size[:, 1].tolist() == n.reshape(-1, tile).max(axis=1).tolist()
    assert any(length % 8 for length in size[:, 0])
    rows = packed.reshape(-1, tile * k)
    for i, length in enumerate(size[:, 0]):
        want = [(packed[t, q], q * tile + t % tile)
                for t in range(i * tile, (i + 1) * tile) for q in range(n[t])]
        assert list(zip(rows[i][at[i, :length]], slot[i, :length])) == want

    # one expert layer (the prediction module's) at a width whose rows
    # move alone, over one tile of 256 tokens
    cfg = dataclasses.replace(SMALL, d_model=128, max_seq_len=256,
                              moe_experts=4, moe_top_k=2)
    m, params = model_params(cfg, seed=3)
    monkeypatch.setattr(moe, "VMEM_BYTES", 0)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 256), 0, 128)
    step = make_data_parallel_step(lm_loss_fn(m), optax.adamw(1e-3),
                                   Mesh(np.array(jax.devices()[:1]), ("dp",)))
    state = create_train_state(params, step.tx)
    ops = ("dispatch", "combine", "dispatch_bwd", "combine_bwd")
    reg = get_registry()
    grown = []
    for _ in range(2):
        before = [reg.counter("moe.dma_rows", op=op).value for op in ops]
        state, metrics = step(state, {"tokens": tokens})
        flush_step_counts()
        grown.append([reg.counter("moe.dma_rows", op=op).value - v
                      for op, v in zip(ops, before)])
        rows = int(metrics["moe_rows_computed"])
        assert rows == int(metrics["moe_assignments_held"]) == 256 * 2
        n_row = int(metrics["moe_dma_rows{op=dispatch}"])
        assert n_row % 256 == 0 and rows <= n_row
        assert grown[-1] == [n_row, rows, rows, n_row]
        assert {name: int(v) for name, v in metrics.items()
                if "dma_rows" in name} == dict(zip(
                    (f"moe_dma_rows{{op={op}}}" for op in ops),
                    grown[-1]))


def _kernel_calls(text):
    """``[(kernel name, length of its Mosaic payload)]`` of a lowered
    module, call by call."""
    calls = []
    for chunk in text.split("stablehlo.custom_call @tpu_custom_call")[1:]:
        body = re.search(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", chunk)
        name = re.search(r'kernel_name = "(\w+)"', chunk)
        calls.append((name.group(1), len(body.group(1))))
    return calls


# an expert layer at LFM2-8B-A1B's per-layer shapes (4 x 8192 tokens,
# d 2048, 8 of 32 experts held, k 4): the shape rule moves all four
# gathers by DMA
LFM2 = dict(T=32768, d=2048, f=1792, k=4, count=8, experts=32)


def _lowered_step(monkeypatch, free_all: bool) -> str:
    """Two checkpointed expert layers, forward and backward, lowered for
    the chip here (nothing compiles; ``free_all``: as if VMEM held every
    source, so XLA gathers)."""
    if free_all:
        monkeypatch.setattr(moe, "VMEM_BYTES", 1 << 60)
    c = LFM2
    sds = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa: E731

    def layer(x, r, g, u, dn):
        return moe.expert_layer(x, r, None, g, u, dn, top_k=c["k"],
                                scale=1.0, held=(0, c["count"]),
                                interpret=False)[0]

    def step(x, ws):
        for w in ws:
            x = x + jax.checkpoint(layer)(x, *w)
        return x.astype(jnp.float32).sum()

    ws = [(sds(c["d"], c["experts"], dt=jnp.float32),
           sds(c["count"], c["d"], c["f"]), sds(c["count"], c["d"], c["f"]),
           sds(c["count"], c["f"], c["d"]))] * 2
    try:
        return jax.jit(jax.value_and_grad(step, argnums=(0, 1))).trace(
            sds(c["T"], c["d"]), ws).lower(
                lowering_platforms=("tpu",)).as_text()
    finally:
        monkeypatch.undo()


def test_the_dma_gathers_cost_no_set_up(monkeypatch):
    """Set-up is a requirement of the gather kernels (lowered for the
    chip on the CPU; nothing compiles or runs).  The lowered step is the
    same text after every cache is cleared — the persistent compile
    cache's key is stable from process to process; a kernel's Mosaic
    payload does not grow when its tile's rows or ``k`` double — its
    loops are rolled; and the step holds the parent layout's kernels
    plus one kernel call for each XLA gather the shape rule handed
    over, no more."""
    gm = sys.modules["byteps_tpu.ops.grouped_matmul"]
    texts = []
    for _ in range(2):      # one call site: a payload carries its stack
        jax.clear_caches()
        for f in (gm._row_gather_call, gm._sum_gather_call, gm._gmm_call,
                  gm._act_call, gm._act_bwd_call, gm._dw_call):
            f.cache_clear()
        texts.append(_lowered_step(monkeypatch, False))
    first = texts[0]
    assert texts[1] == first

    sds = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    T, d, R = 4096, 2048, 16384

    def payload(fn, *args):
        (name, size), = _kernel_calls(jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text())
        return size

    rows = [payload(lambda x, t, n, tile=tile: row_gather(
        x, t, n, tile, interpret=False), sds(T, d), sds(R, dt=jnp.int32),
        sds(dt=jnp.int32)) for tile in (256, 512)]
    sums = [payload(lambda r, dest, held, n, w, tile=tile: sum_gather(
        r, dest, held, n, w, tile, interpret=False), sds(R, d),
        sds(T, k, dt=jnp.int32), sds(T, k, dt=jnp.bool_), sds(dt=jnp.int32),
        sds(T, k, dt=jnp.float32)) for k, tile in ((4, 256), (8, 256),
                                                   (4, 512))]
    assert rows[1] <= rows[0] * 1.01 and max(sums) <= sums[0] * 1.01

    xla = _lowered_step(monkeypatch, True)
    kernels, before = ([n for n, _ in _kernel_calls(t)] for t in (first, xla))
    moved = [n for n in kernels if n in ("row_gather", "sum_gather")]
    wide = rf"stablehlo\.gather.*-> tensor<[0-9x]*x{LFM2['d']}xbf16>"
    handed = len(re.findall(wide, xla)) - len(re.findall(wide, first))
    assert sorted(set(kernels) - set(moved)) == sorted(set(before))
    assert len(kernels) == len(before) + len(moved) and len(moved) == handed
    # two layers: forward, recomputed (the dispatch alone) and backward
    assert handed == 2 * 5


SHAPE_RULE = {   # (T, d, f, k, held, experts, gated): a layer of each cell
    "lfm2": ((32768, 2048, 1792, 4, 8, 32, True), (1, 1, 1, 1)),
    "smallthinker": ((16384, 2560, 768, 6, 16, 64, True), (0, 1, 1, 0)),
    "joyai": ((8192, 2048, 768, 8, 16, 256, True), (0, 1, 1, 0)),
    # half a row of 2688 is not a whole number of lanes
    "nemotron": ((8192, 2688, 1856, 6, 8, 128, False), (0, 0, 0, 0)),
}
OPS = ("dispatch", "combine", "dispatch_bwd", "combine_bwd")


@pytest.mark.parametrize("cell", list(SHAPE_RULE))
def test_the_shape_rule_picks_the_dma_gathers_by_shape(cell):
    """``moe.gather_kernel{op}`` as a layer of each cell is traced
    (abstract shapes, nothing runs): the ``[T, d]`` sources that fit the
    VMEM beside the layer's kernels stay with XLA, the row buffer and a
    ``[T, d]`` source larger than that move by DMA."""
    (T, d, f, k, count, experts, gated), want = SHAPE_RULE[cell]
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731

    def loss(x, r, up, down, gate):
        return moe.expert_layer(
            x, r, None, gate, up, down, top_k=k, scale=1.0,
            held=(0, count), act="silu" if gated else "relu2")[0].astype(
                jnp.float32).sum()

    reg = get_registry()
    for op in OPS:
        reg.gauge("moe.gather_kernel", op=op).set(-1)
    jax.eval_shape(jax.grad(loss, argnums=(0, 2, 3)), sds(T, d),
                   sds(d, experts), sds(count, d, f), sds(count, f, d),
                   sds(count, d, f) if gated else None)
    assert tuple(reg.get("moe.gather_kernel", op=op).value
                 for op in OPS) == want
    # the bytes each writes a call keep their meaning
    R = moe.buffer_rows(T, k, count, moe.ROW_TILE)
    assert reg.get("moe.gathered_mb", op="dispatch").value == pytest.approx(
        R * d * 2 / 1e6)
    sum_rows = T if want[1] else k * T
    assert reg.get("moe.gathered_mb", op="combine").value == pytest.approx(
        sum_rows * d * 2 / 1e6)


# ------------------------------------------- the whole model and the step


# one dense block, then the multi-token-prediction module, whose block
# holds the experts: every mechanism once, one compile each
SMALL = dataclasses.replace(CFG, num_layers=1)
SMALL_REF = dict(REF, num_hidden_layers=1)


@pytest.fixture(scope="module")
def whole():
    """Program and reference, loss and gradients, on one batch."""
    m, params = model_params(SMALL, seed=1)
    tokens = jax.random.randint(jax.random.PRNGKey(19), (1, 24), 0, 128)

    def program(p):
        loss, _, counts = lm_loss_fn(m)(p, {}, {"tokens": tokens})
        return loss, counts

    def reference(p):
        a, b = ref.sequence_loss_sums(p, tokens[0], 1, SIZES)
        return a / 23 + 0.3 * b / 22

    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = jax.jit(jax.value_and_grad(
            program, has_aux=True))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference))(params)
    return dict(m=m, params=params, tokens=tokens, loss=float(loss),
                counts=counts, grads=grads, ref_loss=float(ref_loss),
                ref_grads=ref_grads)


def test_whole_loss_and_mtp_term_match_the_reference(whole):
    params, tokens = whole["params"], whole["tokens"]
    with jax.default_matmul_precision("highest"):
        want = ref.loss(params, tokens, SMALL_REF)
        main = ref.loss(params, tokens, dict(
            SMALL_REF, assumed={"mtp_loss_weight": 0.0}))
        # the fused head is the benchmark's path
        fused, _, counts = jax.jit(lm_loss_fn(whole["m"], fused_head=True))(
            params, {}, {"tokens": tokens})
    assert want == pytest.approx(whole["ref_loss"], abs=1e-5)
    assert abs(whole["loss"] - want) < 2e-4
    assert abs(float(fused) - want) < 2e-4
    assert want - main > 0.3 * 3.0            # the MTP term is in it
    for got in (counts, whole["counts"]):
        assert {k: int(v) for k, v in got.items()} == {
            "moe_assignments_held": 24 * K, "moe_rows_computed": 24 * K}


def test_mtp_gradients_match_the_reference(whole):
    got, want = whole["grads"], whole["ref_grads"]
    for name in ("mtp", "embed", "lm_head", "block_0"):
        for g, w in zip(jax.tree_util.tree_leaves(got[name]),
                        jax.tree_util.tree_leaves(want[name])):
            np.testing.assert_allclose(g, w, rtol=5e-3, atol=1e-5)
    # the router's bias took part in the choice and got no gradient
    assert not np.any(got["mtp"]["block"]["moe"]["router"]["bias"])


def test_a_recomputed_block_gives_the_same_loss_and_gradients(whole):
    params, tokens = whole["params"], whole["tokens"]
    rm = Transformer(dataclasses.replace(SMALL, remat=True))
    loss_of = lambda mod: lambda p: lm_loss_fn(mod)(  # noqa: E731
        p, {}, {"tokens": tokens})[0]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_of(rm)))(params)
    assert float(loss) == pytest.approx(whole["loss"], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(whole["grads"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    jaxprs = [str(jax.make_jaxpr(loss_of(mod))(params))
              for mod in (whole["m"], rm)]
    assert "remat2" not in jaxprs[0] and "remat2" in jaxprs[1]


def test_the_step_reports_the_loss_and_the_held_assignments(whole):
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step = make_data_parallel_step(lm_loss_fn(whole["m"]),
                                   optax.adamw(1e-3), mesh)
    state = create_train_state(whole["params"], step.tx)
    reg = get_registry()
    before = {n: reg.counter(n).value for n in (
        "moe.assignments_held", "moe.rows_computed", "train.steps_counted")}
    losses = []
    for _ in range(2):
        state, metrics = step(state, {"tokens": whole["tokens"]})
        losses.append(float(metrics["loss"]))
        assert int(metrics["moe_assignments_held"]) == 24 * K == int(
            metrics["moe_rows_computed"])
    flush_step_counts()
    # the step itself feeds the registry, step by step
    assert {n: reg.counter(n).value - v for n, v in before.items()} == {
        "moe.assignments_held": 48 * K, "moe.rows_computed": 48 * K,
        "train.steps_counted": 2}
    assert losses[0] == pytest.approx(whole["loss"], abs=1e-4)
    assert losses[-1] < losses[0]


def test_the_family_config_maps_onto_the_model_and_refuses_the_rest():
    import types

    from benchmark.harness import manifest
    from byteps_tpu.integrations.deepseek_v3 import deepseek_v3_config

    body = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "joyai-llm-flash-l5-ep16.json"))
    hf = types.SimpleNamespace(**{
        k: v for k, v in body.items() if not isinstance(v, (dict, list))})
    cfg = deepseek_v3_config(hf, moe_held=(0, 16))
    assert (cfg.attn_kind, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        "mla", 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        128, 64, 128)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.moe_top_k, cfg.moe_scale) == (
        7168, 768, 8, 2.5)
    assert cfg.dense_layers == 1 and cfg.mtp_layers == 1
    hf.n_group = 8
    with pytest.raises(ValueError, match="n_group"):
        deepseek_v3_config(hf)
