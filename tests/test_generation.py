"""KV-cache decode + generation loop (byteps_tpu/inference.py).

The reference has no inference path (it is a training-comm library); this
is the framework's own autoregressive story.  Ground truth for every test
is the model's full causal forward — decode must reproduce it exactly
(same params, fp32 logits head).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import generate, make_generate_fn, sample_logits
from byteps_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    init_cache,
)


def _tiny_model(**kw):
    cfg = TransformerConfig(
        vocab_size=61, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, **kw)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 61)
    variables = model.init(jax.random.PRNGKey(1), tokens)
    return cfg, model, tokens, variables


def test_prefill_matches_forward():
    cfg, model, tokens, variables = _tiny_model()
    full = model.apply(variables, tokens)
    caches = init_cache(cfg, tokens.shape[0], 24)
    logits, new_caches = model.apply(
        variables, tokens, caches, 0, method=Transformer.decode)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full), rtol=1e-5, atol=1e-5)
    # prompt K/V landed in slots [0, T); the tail stayed zero
    assert not np.allclose(np.asarray(new_caches[0]["k"][:, :16]), 0)
    np.testing.assert_array_equal(
        np.asarray(new_caches[0]["k"][:, 16:]), 0)


def test_incremental_decode_matches_forward():
    """Feeding tokens one at a time through the cache reproduces the full
    forward's logits at every position."""
    cfg, model, tokens, variables = _tiny_model()
    B, T = tokens.shape
    full = model.apply(variables, tokens)
    caches = init_cache(cfg, B, T)
    outs = []
    for t in range(T):
        logits, caches = model.apply(
            variables, tokens[:, t:t + 1], caches, t,
            method=Transformer.decode)
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full), rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # ~16s: token-by-token reference loop; incremental_decode/prefill/windowed parity stay fast
def test_greedy_generate_matches_reference_loop():
    """The scan-based generate equals a naive loop that re-runs the full
    forward on the growing sequence each step."""
    cfg, model, tokens, variables = _tiny_model()
    n = 8
    out = generate(model, variables, tokens, n, temperature=0)

    seq = tokens
    want = []
    for _ in range(n):
        logits = model.apply(variables, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(out["tokens"]), np.asarray(jnp.stack(want, axis=1)))


@pytest.mark.slow
def test_generate_windowed_flash_model():
    """Decode applies the config's sliding window: greedy generation from a
    windowed model matches the naive full-forward loop of the same model.
    Slow: the windowed flash variant pays its own Pallas compile; the
    fast flash coverage is test_flash_prefill_matches_dense_cache_path /
    test_flash_prefill_awkward_lengths_fall_back."""
    cfg, model, tokens, variables = _tiny_model(
        attn_impl="flash", attn_window=8)
    n = 6
    out = generate(model, variables, tokens, n, temperature=0)
    seq = tokens
    want = []
    for _ in range(n):
        logits = model.apply(variables, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(out["tokens"]), np.asarray(jnp.stack(want, axis=1)))


def test_flash_prefill_matches_dense_cache_path():
    """The static pos=0 prefill fast path (Pallas flash kernel) must agree
    with the dense cached-attention path it replaces."""
    cfg, model, tokens, variables = _tiny_model(attn_impl="flash")
    caches = init_cache(cfg, tokens.shape[0], 24)
    # flash fast path engages for literal pos=0 with tq>1
    fast, fast_caches = model.apply(
        variables, tokens, caches, 0, method=Transformer.decode)
    # traced pos forces the dense path on identical math
    dense, dense_caches = jax.jit(
        lambda v, t, c, p: model.apply(v, t, c, p,
                                       method=Transformer.decode)
    )(variables, tokens, caches, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(fast), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)
    for fc, dc in zip(fast_caches, dense_caches):
        np.testing.assert_allclose(np.asarray(fc["k"]), np.asarray(dc["k"]),
                                   rtol=1e-6, atol=1e-6)


def test_flash_prefill_awkward_lengths_fall_back():
    """Prompt lengths the Pallas block fitter can't serve (tiny, or odd
    T>1024) must route to the dense cache path, not crash (regression:
    T=4 raised ValueError from fit_block)."""
    cfg, model, _, _ = _tiny_model(attn_impl="flash")
    init_tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 61)
    variables = model.init(jax.random.PRNGKey(1), init_tokens)
    for T in (4, 7):
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, T), 0, 61)
        out = generate(model, variables, prompt, 3, temperature=0)
        assert out["tokens"].shape == (2, 3)


def test_eos_freezes_row():
    cfg, model, tokens, variables = _tiny_model()
    n = 8
    out = generate(model, variables, tokens, n, temperature=0)
    # pick the token the model actually emits at step 0 for row 0 as the
    # "eos" and re-generate: row 0 must freeze to pad from step 1 on
    eos = int(out["tokens"][0, 0])
    out2 = generate(model, variables, tokens, n, temperature=0,
                    eos_id=eos, pad_id=60)
    got = np.asarray(out2["tokens"][0])
    assert got[0] == eos
    after = got[1:][got[1:] != 60]
    # every surviving non-pad token can only appear before eos was hit
    assert after.size == 0 or bool(out2["done"][0]) is True
    assert bool(out2["done"][0])
    np.testing.assert_array_equal(got[1:], 60)


def test_sampling_filters():
    rng = jax.random.PRNGKey(0)
    logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
    # top_k=1 is greedy regardless of rng
    for i in range(5):
        tok = sample_logits(logits, jax.random.fold_in(rng, i),
                            temperature=1.0, top_k=1)
        assert int(tok[0]) == 0
    # top_p=0.6 keeps {0, 1} only
    seen = set()
    for i in range(64):
        tok = sample_logits(logits, jax.random.fold_in(rng, i),
                            temperature=1.0, top_p=0.6)
        seen.add(int(tok[0]))
    assert seen <= {0, 1} and 0 in seen
    # temperature=0 is argmax
    assert int(sample_logits(logits, rng, temperature=0)[0]) == 0


def test_generate_batch_and_shapes():
    cfg, model, tokens, variables = _tiny_model()
    fn = make_generate_fn(model, 5, temperature=0.7, top_k=10)
    out = fn(variables, tokens, jax.random.PRNGKey(3))
    assert out["tokens"].shape == (2, 5)
    assert out["tokens"].dtype in (jnp.int32, jnp.int64)
    assert ((out["tokens"] >= 0) & (out["tokens"] < 61)).all()
    # two rows with different prompts should (generically) diverge
    assert not np.array_equal(np.asarray(out["tokens"][0]),
                              np.asarray(out["tokens"][1]))


def test_prefill_last_only():
    """last_only prefill returns [B, 1, vocab] matching the full variant's
    final position (the generation hot path skips the other T-1 heads)."""
    cfg, model, tokens, variables = _tiny_model()
    caches = init_cache(cfg, tokens.shape[0], 20)
    full, _ = model.apply(
        variables, tokens, caches, 0, method=Transformer.decode)
    last, _ = model.apply(
        variables, tokens, caches, 0, True, method=Transformer.decode)
    assert last.shape == (2, 1, 61)
    np.testing.assert_allclose(
        np.asarray(last[:, 0]), np.asarray(full[:, -1]),
        rtol=1e-5, atol=1e-5)


def test_cache_rejects_key_mask():
    """Padded prompts must error, not silently poison the cache."""
    cfg, model, tokens, variables = _tiny_model()
    from byteps_tpu.models.transformer import Block
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 32))
    mask = jnp.ones((2, 4), jnp.int32)
    cache = init_cache(cfg, 2, 8)[0]
    blk = Block(cfg)
    v = blk.init(jax.random.PRNGKey(1), x)
    with pytest.raises(ValueError):
        blk.apply(v, x, key_mask=mask, cache=cache, pos=0)


def test_generate_requires_rng_when_sampling():
    cfg, model, tokens, variables = _tiny_model()
    with pytest.raises(ValueError):
        generate(model, variables, tokens, 4, temperature=0.8)
    # greedy stays rng-free
    generate(model, variables, tokens, 2, temperature=0)


def test_generate_dp_sharded():
    """Distributed inference: generation with the batch sharded over an
    8-device dp mesh equals the single-device result — XLA partitions the
    whole prefill+scan program (cache included) along batch."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, model, _, _ = _tiny_model()
    prompt = jax.random.randint(jax.random.PRNGKey(4), (8, 12), 0, 61)
    variables = model.init(jax.random.PRNGKey(1), prompt)
    want = generate(model, variables, prompt, 6, temperature=0)

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    sharded = jax.device_put(prompt, NamedSharding(mesh, P("dp", None)))
    repl = jax.device_put(variables, NamedSharding(mesh, P()))
    got = generate(model, repl, sharded, 6, temperature=0)
    np.testing.assert_array_equal(
        np.asarray(got["tokens"]), np.asarray(want["tokens"]))


def test_cache_len_guard():
    cfg, model, tokens, variables = _tiny_model()
    with pytest.raises(ValueError):
        init_cache(cfg, 2, cfg.max_seq_len + 1)
    noncausal = TransformerConfig(
        vocab_size=61, num_layers=1, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, causal=False)
    m2 = Transformer(noncausal)
    v2 = m2.init(jax.random.PRNGKey(0), tokens)
    c2 = init_cache(noncausal, 2, 32)
    with pytest.raises(ValueError):
        m2.apply(v2, tokens, c2, 0, method=Transformer.decode)


def test_classify_divergence_none_tie_real():
    """The divergence classifier (VERDICT r3 #8): identical decodes ->
    none; a second-best-token flip within the tie threshold -> tie; an
    injected cache-bug-style wrong token (clearly lower logit) -> real."""
    import numpy as np

    from byteps_tpu.inference import classify_divergence, generate

    cfg, model, _, variables = _tiny_model()
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0,
                                cfg.vocab_size)
    out = generate(model, variables, prompt, 8, temperature=0)
    toks = np.asarray(out["tokens"])

    res = classify_divergence(model, variables, prompt, toks, toks)
    assert res["divergence"] == "none"

    # teacher-force to find the runner-up token at a mid position
    full = jnp.concatenate([prompt, jnp.asarray(toks)], axis=1)
    logits = np.asarray(model.apply(variables, full), np.float32)
    T = prompt.shape[1]
    d = 4
    row = logits[0, T + d - 1]
    order = np.argsort(row)[::-1]
    runner_up = int(order[1] if order[0] == toks[0, d] else order[0])
    worst = int(order[-1])

    tie_b = toks.copy()
    tie_b[0, d] = runner_up
    # generous threshold -> the runner-up flip classifies as a tie
    res = classify_divergence(model, variables, prompt, toks, tie_b,
                              tie_rtol=10.0)
    assert res["divergence"] == "tie" and res["first_div_pos"] == d

    bug_b = toks.copy()
    bug_b[0, d] = worst
    # an injected wrong token (cache-bug analog) must classify as real
    res = classify_divergence(model, variables, prompt, toks, bug_b,
                              tie_rtol=0.0, tie_atol=1e-6)
    assert res["divergence"] == "real"
    assert res["first_div_pos"] == d
    assert res["delta_logit"] > 0  # path A's token scores higher


# ---------------------------------------------------------------------------
# flat [B, S, KV*D] decode-kernel cache layout (ops/decode_attention.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv", [None, 1])
def test_flat_cache_generate_matches_grouped(kv):
    """The flat decode-kernel layout must generate the same greedy tokens
    as the grouped dense layout (CPU: kernel runs in interpret mode)."""
    cfg, model, tokens, variables = _tiny_model(num_kv_heads=kv)
    fn_g = make_generate_fn(model, 6, temperature=0,
                            cache_layout="grouped")
    fn_f = make_generate_fn(model, 6, temperature=0, cache_layout="flat")
    rng = jax.random.PRNGKey(3)
    out_g = fn_g(variables, tokens, rng)
    out_f = fn_f(variables, tokens, rng)
    np.testing.assert_array_equal(np.asarray(out_g["tokens"]),
                                  np.asarray(out_f["tokens"]))


@pytest.mark.slow  # ~11s: token-by-token stepwise reference loop; flat_cache_generate_matches_grouped keeps flat-layout parity fast
def test_flat_cache_stepwise_matches_forward():
    """Per-token decode against the flat cache reproduces the full causal
    forward — including the tq>1-at-pos>0 dense fallback (speculative
    verify) and awkward-length dense prefill."""
    cfg, model, tokens, variables = _tiny_model()
    B, T = tokens.shape
    full = model.apply(variables, tokens)
    caches = init_cache(cfg, B, T, layout="flat")
    assert caches[0]["k"].ndim == 3
    # prefill the first 11 tokens (awkward length -> dense prefill on
    # fresh k/v), then one-token decode steps, then a 3-token chunk at
    # pos>0 (the speculative-verify shape)
    logits, caches = model.apply(
        variables, tokens[:, :11], caches, 0, method=Transformer.decode)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, :11]),
                               rtol=2e-4, atol=2e-4)
    outs = [logits]
    for t in range(11, 13):
        logits, caches = model.apply(
            variables, tokens[:, t:t + 1], caches, t,
            method=Transformer.decode)
        outs.append(logits)
    logits, caches = model.apply(
        variables, tokens[:, 13:16], caches, jnp.int32(13),
        method=Transformer.decode)
    outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.skipif(jax.default_backend() != "cpu",
                    reason="asserts the CPU resolution of auto")
def test_flat_cache_auto_layout_cpu_is_grouped():
    cfg, model, tokens, variables = _tiny_model()
    caches = init_cache(cfg, 2, 24, layout="auto")
    # CPU backend: auto resolves to grouped (interpret-mode Pallas per
    # decode step would crawl); the TPU resolution is covered on-chip
    assert caches[0]["k"].ndim == 4


def test_classify_divergence_position_profile():
    """The position profile separates late near-tie churn from an early
    cliff (r4 verdict: one sentence of diagnosis next to the number)."""
    from byteps_tpu.inference import classify_divergence

    cfg, model, tokens, variables = _tiny_model()
    N = 16
    base = np.asarray(
        jax.random.randint(jax.random.PRNGKey(9), (2, N), 0, 50))
    # churn: row 0 diverges late (pos 12), row 1 later (pos 14)
    churn = base.copy()
    churn[0, 12:] = (churn[0, 12:] + 1) % 50
    churn[1, 14:] = (churn[1, 14:] + 1) % 50
    res = classify_divergence(model, variables, tokens[:, :4],
                              base, churn)
    assert res["first_div_positions"] == [12, 14]
    q = res["div_frac_by_quarter"]
    assert len(q) == 4 and q[0] == 0.0 and q[1] == 0.0 and q[3] == 0.75
    # cliff: both rows diverge from pos 1
    cliff = base.copy()
    cliff[:, 1:] = (cliff[:, 1:] + 1) % 50
    res = classify_divergence(model, variables, tokens[:, :4],
                              base, cliff)
    assert res["first_div_positions"] == [1, 1]
    assert res["div_frac_by_quarter"][0] > 0.5
    # identical rows report -1
    same = base.copy()
    same[1] = base[1]
    mix = base.copy()
    mix[0, 5:] = (mix[0, 5:] + 3) % 50
    res = classify_divergence(model, variables, tokens[:, :4], base, mix)
    assert res["first_div_positions"] == [5, -1]
