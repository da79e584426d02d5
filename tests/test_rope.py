"""Rotary position embeddings + SwiGLU MLP (the LLaMA-family model axes,
`TransformerConfig(pos_emb="rope", mlp="swiglu")`).

RoPE's contract: scores depend only on position *deltas* (so cached
decode can store rotated keys and stay exact at any offset), and every
attention path — dense, flash, cached, GQA-grouped, int8 cache —
consumes rotated q/k identically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import make_generate_fn
from byteps_tpu.models import Transformer, TransformerConfig
from byteps_tpu.models.transformer import apply_rope, init_cache

KW = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
          d_model=32, d_ff=48, max_seq_len=64, dtype=jnp.float32,
          pos_emb="rope", mlp="swiglu")


def test_rope_relative_shift_invariance():
    """QK^T scores under RoPE are invariant to a global position shift."""
    B, T, H, D = 1, 6, 2, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)

    def scores(off):
        pos = off + jnp.arange(T)
        return jnp.einsum("bqhd,bkhd->bhqk", apply_rope(q, pos),
                          apply_rope(k, pos))

    np.testing.assert_allclose(np.asarray(scores(0)),
                               np.asarray(scores(17)),
                               atol=1e-5, rtol=1e-5)


def test_rope_swiglu_decode_matches_full_forward():
    cfg = TransformerConfig(**KW)
    m = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
    vs = m.init(jax.random.PRNGKey(2), toks)
    assert "pos" not in vs["params"]  # no learned table under rope
    assert set(vs["params"]["block_0"]["mlp"]) == {"gate", "up", "down"}
    full = m.apply(vs, toks)
    caches = init_cache(cfg, 2, 20)
    lg, caches = m.apply(vs, toks[:, :7], caches, 0, False,
                         method=Transformer.decode)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :7]),
                               atol=2e-5, rtol=2e-5)
    for i in range(7, 12):
        lg, caches = m.apply(vs, toks[:, i:i + 1], caches, i, False,
                             method=Transformer.decode)
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full[:, i]),
            atol=2e-5, rtol=2e-5)


def test_rope_flash_matches_local():
    kw = dict(KW, max_seq_len=128)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)
    cfg_f = TransformerConfig(attn_impl="flash", **kw)
    cfg_l = TransformerConfig(attn_impl="local", **kw)
    vs = Transformer(cfg_l).init(jax.random.PRNGKey(2), toks)
    np.testing.assert_allclose(
        np.asarray(Transformer(cfg_f).apply(vs, toks)),
        np.asarray(Transformer(cfg_l).apply(vs, toks)),
        atol=3e-5, rtol=3e-5)


@pytest.mark.slow  # ~10s: naive reference decode loop; rope_swiglu_decode_matches_full_forward stays fast
def test_rope_generate_matches_naive_and_int8_cache():
    cfg = TransformerConfig(**KW)
    m = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    vs = m.init(jax.random.PRNGKey(2), prompt)
    out = make_generate_fn(m, 5, temperature=0)(
        vs, prompt, jax.random.PRNGKey(0))
    toks = prompt
    for _ in range(5):
        lg = m.apply(vs, toks)
        toks = jnp.concatenate([toks, jnp.argmax(lg[:, -1:], -1)], 1)
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(toks[:, 8:]))
    outq = make_generate_fn(m, 5, temperature=0, kv_quant=True)(
        vs, prompt, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(outq["tokens"]),
                                  np.asarray(out["tokens"]))


@pytest.mark.slow  # ~11s: full train-step compile; rope decode/generate/flash/ring parity stays fast
def test_rope_swiglu_train_step_decreases_loss():
    import optax

    from byteps_tpu.training import lm_loss_fn

    cfg = TransformerConfig(**KW)
    m = Transformer(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    vs = m.init(jax.random.PRNGKey(2), toks)
    lf = lm_loss_fn(m)
    tx = optax.sgd(0.5)

    def loss(p):
        return lf(p, {}, {"tokens": toks})[0]

    params, opt = vs["params"], tx.init(vs["params"])
    l0 = float(loss(params))
    for _ in range(5):
        _, grads = jax.value_and_grad(loss)(params)
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)
    assert float(loss(params)) < l0


def test_bad_pos_emb_and_mlp_raise():
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="pos_emb"):
        Transformer(TransformerConfig(**dict(KW, pos_emb="alibi"))).init(
            jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="mlp"):
        Transformer(TransformerConfig(**dict(KW, mlp="geglu"))).init(
            jax.random.PRNGKey(0), toks)


def test_rope_ring_sp_matches_local():
    """RoPE composes with sequence parallelism: rotation happens with
    global positions before the ring shard_map splits the sequence, so
    the sp ring path equals the single-device local path."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("dp", "sp"))
    kw = dict(KW, max_seq_len=32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    cfg_r = TransformerConfig(attn_impl="ring", mesh=mesh, **kw)
    cfg_l = TransformerConfig(attn_impl="local", **kw)
    vs = Transformer(cfg_l).init(jax.random.PRNGKey(0), toks)
    expected = Transformer(cfg_l).apply(vs, toks)
    with mesh:
        got = jax.jit(
            lambda v, t: Transformer(cfg_r).apply(v, t))(vs, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=3e-5, rtol=3e-5)
