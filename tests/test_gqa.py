"""Grouped-query attention (GQA/MQA) end-to-end.

``TransformerConfig.num_kv_heads`` shares each K/V head across a group
of query heads — shrinking the KV cache (decode's second-largest HBM
stream) by ``num_heads / num_kv_heads``.  The reference has no GQA
(2019-era models); this is the TPU-first decode-bandwidth lever.  These
tests pin the contract: grouped == materialized-repeat on every path
(train local/flash, cached prefill/decode, int8 cache, generation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import make_generate_fn
from byteps_tpu.models import Transformer, TransformerConfig
from byteps_tpu.models.transformer import (
    _cached_attention,
    _cached_attention_q8,
    _quantize_kv,
    init_cache,
)

KW = dict(vocab_size=64, num_layers=2, d_model=32, d_ff=64,
          max_seq_len=64, dtype=jnp.float32)


def test_bad_group_factor_raises():
    cfg = TransformerConfig(num_heads=4, num_kv_heads=3, **KW)
    with pytest.raises(ValueError, match="divide"):
        _ = cfg.kv_heads


def test_cache_shape_carries_kv_heads():
    cfg = TransformerConfig(num_heads=8, num_kv_heads=2, **KW)
    caches = init_cache(cfg, 3, 16)
    assert caches[0]["k"].shape == (3, 16, 2, KW["d_model"] // 8)


@pytest.mark.parametrize("kv", [1, 2, 4])
def test_grouped_cached_attention_matches_repeat(kv):
    """The grouped dot against the un-repeated cache == dense attention
    against the cache with K/V heads explicitly repeated."""
    B, tq, H, D, S, pos = 2, 3, 4, 8, 12, 5
    rng = np.random.RandomState(kv)
    q = jnp.asarray(rng.randn(B, tq, H, D), jnp.float32)
    ck = jnp.asarray(rng.randn(B, S, kv, D), jnp.float32)
    cv = jnp.asarray(rng.randn(B, S, kv, D), jnp.float32)
    out = _cached_attention(q, ck, cv, pos)
    ref = _cached_attention(q, jnp.repeat(ck, H // kv, axis=2),
                            jnp.repeat(cv, H // kv, axis=2), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_grouped_q8_cached_attention_matches_repeat():
    B, tq, H, kv, D, S, pos = 2, 1, 4, 2, 8, 12, 7
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, tq, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, kv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, kv, D), jnp.float32)
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    out = _cached_attention_q8(q, kq, ks, vq, vs, pos)
    rep = lambda x: jnp.repeat(x, H // kv, axis=2)  # noqa: E731
    ref = _cached_attention_q8(q, rep(kq), rep(ks), rep(vq), rep(vs), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_groups_of_one_is_mha():
    """num_kv_heads == num_heads produces the identical parameter tree
    and identical outputs to num_kv_heads=None (pure MHA)."""
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
    cfg_a = TransformerConfig(num_heads=4, num_kv_heads=4, **KW)
    cfg_b = TransformerConfig(num_heads=4, **KW)
    va = Transformer(cfg_a).init(jax.random.PRNGKey(0), toks)
    vb = Transformer(cfg_b).init(jax.random.PRNGKey(0), toks)
    assert (jax.tree_util.tree_structure(va)
            == jax.tree_util.tree_structure(vb))
    np.testing.assert_array_equal(
        np.asarray(Transformer(cfg_a).apply(va, toks)),
        np.asarray(Transformer(cfg_b).apply(vb, toks)))


@pytest.mark.parametrize("kv", [1, 2])
def test_gqa_decode_matches_full_forward(kv):
    """Cached prefill + per-token decode reproduces the no-cache full
    forward exactly (the causal-consistency contract, now under GQA)."""
    cfg = TransformerConfig(num_heads=4, num_kv_heads=kv, **KW)
    m = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
    vs = m.init(jax.random.PRNGKey(2), toks)
    full = m.apply(vs, toks)
    caches = init_cache(cfg, 2, 16)
    lg, caches = m.apply(vs, toks[:, :6], caches, 0, False,
                         method=Transformer.decode)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :6]),
                               atol=2e-5, rtol=2e-5)
    for i in range(6, 10):
        lg, caches = m.apply(vs, toks[:, i:i + 1], caches, i, False,
                             method=Transformer.decode)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full[:, i]),
            atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # ~8s: naive reference decode loop; groups_of_one_is_mha + grouped_q8_cached stay fast
def test_gqa_generate_matches_naive_and_int8_cache():
    cfg = TransformerConfig(num_heads=4, num_kv_heads=1, **KW)
    m = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    vs = m.init(jax.random.PRNGKey(2), prompt)
    out = make_generate_fn(m, 6, temperature=0)(
        vs, prompt, jax.random.PRNGKey(0))
    toks = prompt
    for _ in range(6):
        lg = m.apply(vs, toks)
        toks = jnp.concatenate([toks, jnp.argmax(lg[:, -1:], -1)], 1)
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(toks[:, 8:]))
    outq = make_generate_fn(m, 6, temperature=0, kv_quant=True)(
        vs, prompt, jax.random.PRNGKey(0))
    # int8 cache quantization can flip a near-tie argmax; on this tiny
    # fixed seed it does not
    np.testing.assert_array_equal(np.asarray(outq["tokens"]),
                                  np.asarray(out["tokens"]))


def test_gqa_flash_training_matches_local():
    """attn_impl='flash' consumes grouped K/V natively (no repeat); the
    training forward matches the local-attention model bit-for-bit in
    fp32 interpret mode."""
    kw = dict(KW, max_seq_len=128)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)
    cfg_f = TransformerConfig(num_heads=4, num_kv_heads=2,
                              attn_impl="flash", **kw)
    cfg_l = TransformerConfig(num_heads=4, num_kv_heads=2,
                              attn_impl="local", **kw)
    vs = Transformer(cfg_l).init(jax.random.PRNGKey(0), toks)
    expected = Transformer(cfg_l).apply(vs, toks)
    got = Transformer(cfg_f).apply(vs, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=3e-5, rtol=3e-5)


def test_gqa_train_grads_flow():
    """One SGD step on the GQA model moves every parameter (k/v kernels
    included) and decreases loss on a fixed batch."""
    import optax

    from byteps_tpu.training import lm_loss_fn

    cfg = TransformerConfig(num_heads=4, num_kv_heads=2, **KW)
    m = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    vs = m.init(jax.random.PRNGKey(2), toks)
    lf = lm_loss_fn(m)
    tx = optax.sgd(0.5)

    def loss(p):
        return lf(p, {}, {"tokens": toks})[0]

    params = vs["params"]
    opt = tx.init(params)
    # jitted once: six op-by-op eager backward passes ran ~15-17 s
    vg = jax.jit(jax.value_and_grad(loss))
    l0, grads = vg(params)
    gnorms = [float(jnp.linalg.norm(g))
              for g in jax.tree_util.tree_leaves(grads)]
    assert all(n > 0 for n in gnorms)
    for _ in range(5):
        _, grads = vg(params)
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)
    assert float(loss(params)) < float(l0)
