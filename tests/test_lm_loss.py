"""lm_loss_fn (incl. the fused LM-head path) and flash+tensor-parallel
composition tests."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byteps_tpu.models import Transformer, TransformerConfig
from byteps_tpu.training import lm_loss_fn


def _tiny_cfg(**kw):
    return TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                             d_model=32, d_ff=64, max_seq_len=16,
                             dtype=jnp.float32, **kw)


def test_fused_head_matches_naive_loss_and_grads():
    model = Transformer(_tiny_cfg())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((4, 16), jnp.int32))["params"]
    batch = {"tokens": tokens}

    naive = lm_loss_fn(model, fused_head=False)
    fused = lm_loss_fn(model, fused_head=True)
    # (jitted: op by op this test took 14 s)
    l_n, g_n = jax.jit(jax.value_and_grad(
        lambda p: naive(p, {}, batch)[0]))(params)
    l_f, g_f = jax.jit(jax.value_and_grad(
        lambda p: fused(p, {}, batch)[0]))(params)
    np.testing.assert_allclose(float(l_f), float(l_n), rtol=1e-5)

    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_n),
            jax.tree_util.tree_leaves_with_path(g_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5, err_msg=str(kp))


def test_param_tree_unchanged_by_setup_conversion():
    """The setup()-style Transformer must keep the compact-era tree:
    embed / pos / block_i / ln_f / lm_head (checkpoints stay loadable)."""
    model = Transformer(_tiny_cfg())
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 16), jnp.int32))["params"]
    assert set(params.keys()) == {
        "embed", "pos", "block_0", "block_1", "ln_f", "lm_head"}
    assert params["lm_head"]["kernel"].shape == (32, 64)


def test_flash_composes_with_tensor_parallel():
    """attn_impl='flash' under a tp-sharded GSPMD mesh compiles and
    matches local attention numerically."""
    import flax.linen as nn

    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(np.array(jax.devices()).reshape(n // 2, 2), ("dp", "tp"))

    def run(attn_impl):
        cfg = _tiny_cfg(attn_impl=attn_impl, mesh=mesh)
        model = Transformer(cfg)
        tokens0 = jnp.zeros((4, 16), jnp.int32)
        tvars = model.init(jax.random.PRNGKey(0), tokens0)
        specs = nn.get_partition_spec(tvars)["params"]
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            nn.meta.unbox(tvars["params"]), specs)
        tok = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64),
            NamedSharding(mesh, P("dp", None)))
        with jax.set_mesh(mesh):
            return jax.jit(
                lambda p, t: model.apply({"params": p}, t))(params, tok)

    out_flash = run("flash")
    out_local = run("local")
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_local),
                               rtol=1e-4, atol=1e-5)


def test_padded_labels_normalize_by_valid_count():
    """HF -100 ignore-index (ADVICE r2): padded positions contribute
    neither loss nor denominator, in both branches, and both branches
    agree; the non-fused branch must not feed -100 into optax."""
    model = Transformer(_tiny_cfg())
    B, T = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 1, 64)
    labels = np.asarray(tokens).copy()
    labels[:, T // 2:] = -100          # second half padded
    tokens_padded = np.asarray(tokens).copy()
    tokens_padded[:, T // 2:] = 0      # embeddable pad id
    batch = {"tokens": jnp.asarray(tokens_padded),
             "labels": jnp.asarray(labels)}
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((B, T), jnp.int32))["params"]

    naive = lm_loss_fn(model, fused_head=False)
    fused = lm_loss_fn(model, fused_head=True)
    l_n, _ = naive(params, {}, batch)
    l_f, _ = fused(params, {}, batch)
    assert np.isfinite(float(l_n)) and np.isfinite(float(l_f))
    np.testing.assert_allclose(float(l_f), float(l_n), rtol=1e-5)

    # hand-computed reference: mean CE over the valid (first-half) shifts
    logits = model.apply({"params": params}, batch["tokens"])
    tgt = np.roll(labels, -1, axis=1)
    tgt[:, -1] = -100
    per = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], jnp.asarray(np.where(tgt[:, :-1] < 0, 0,
                                             tgt[:, :-1])))
    mask = tgt[:, :-1] >= 0
    want = float((np.asarray(per) * mask).sum() / mask.sum())
    np.testing.assert_allclose(float(l_n), want, rtol=1e-5)


def test_fully_valid_stream_unchanged_vs_mean():
    """No padding -> the valid-count mean equals the old fixed-denominator
    mean (back-compat for the perplexity example)."""
    model = Transformer(_tiny_cfg())
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64)
    batch = {"tokens": tokens}
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 16), jnp.int32))["params"]
    l, _ = lm_loss_fn(model, fused_head=False)(params, {}, batch)
    logits = model.apply({"params": params}, tokens)
    targets = jnp.roll(tokens, -1, axis=1)
    want = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], targets[:, :-1]).mean()
    np.testing.assert_allclose(float(l), float(want), rtol=1e-6)


def test_early_exit_loss_equals_full_plus_weighted_truncated():
    """early_exit=(k, w) adds exactly w * CE of the first-k-layers exit
    (the truncation truncated_draft builds), in both head paths."""
    from byteps_tpu.inference import truncated_draft

    cfg = _tiny_cfg()
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((4, 16), jnp.int32))["params"]
    batch = {"tokens": tokens}

    base = lm_loss_fn(model)(params, {}, batch)[0]
    dmodel, dvars = truncated_draft(cfg, {"params": params}, 1)
    early = lm_loss_fn(dmodel)(dvars["params"], {}, batch)[0]
    got = lm_loss_fn(model, early_exit=(1, 0.5))(params, {}, batch)[0]
    np.testing.assert_allclose(float(got), float(base) + 0.5 * float(early),
                               rtol=1e-5)
    # fused-head path carries the same aux term
    got_f = lm_loss_fn(model, fused_head=True,
                       early_exit=(1, 0.5))(params, {}, batch)[0]
    np.testing.assert_allclose(float(got_f), float(got), rtol=1e-4)


@pytest.mark.slow  # ~60s on CPU: trains two models to convergence
def test_early_exit_training_makes_truncated_draft_viable():
    """The LayerSkip premise, end to end: vanilla training leaves the
    early-exit readout (ln_f + head over block_0) untrained, so the
    truncated self-draft is rejected even by a CONVERGED target; adding
    the early_exit aux term trains the exit and speculative decoding
    accepts the draft at a high rate."""
    from byteps_tpu.inference import speculative_generate, truncated_draft

    cfg = TransformerConfig(vocab_size=64, num_layers=3, num_heads=4,
                            d_model=64, d_ff=128, max_seq_len=64,
                            dtype=jnp.float32, pos_emb="rope")
    model = Transformer(cfg)

    def pattern_batch(key, B=16, T=16):
        pat = jax.random.randint(key, (B, 4), 3, 64)
        return jnp.tile(pat, (1, T // 4 + 1))[:, :T]

    def train(loss_closure, steps=250):
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 8), jnp.int32))["params"]
        tx = optax.adam(3e-3)
        opt = tx.init(params)

        @jax.jit
        def step(params, opt, toks):
            loss, grads = jax.value_and_grad(
                lambda p: loss_closure(p, {}, {"tokens": toks})[0])(params)
            upd, opt = tx.update(grads, opt)
            return optax.apply_updates(params, upd), opt, loss

        rng = jax.random.PRNGKey(7)
        for _ in range(steps):
            rng, sub = jax.random.split(rng)
            params, opt, _ = step(params, opt, pattern_batch(sub))
        return params

    def acceptance(params):
        dmodel, dvars = truncated_draft(cfg, {"params": params}, 1)
        prompt = pattern_batch(jax.random.PRNGKey(99), B=1, T=8)
        out = speculative_generate(model, {"params": params}, dmodel,
                                   dvars, prompt, 12, gamma=4)
        return float(out["acceptance"])

    acc_aux = acceptance(train(lm_loss_fn(model, early_exit=(1, 0.5))))
    acc_vanilla = acceptance(train(lm_loss_fn(model)))
    assert acc_aux > 0.5, acc_aux
    assert acc_aux > acc_vanilla + 0.2, (acc_vanilla, acc_aux)
