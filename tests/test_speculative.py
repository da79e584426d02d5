"""Speculative decoding (inference.speculative_generate).

The algorithm's defining property: greedy speculative output is EXACTLY
the target model's own greedy output — the draft model only changes
speed, never content.  These tests pin that for agreeing drafts (draft ==
target), disagreeing drafts (independent random models), and partial
agreement, plus EOS freezing inside an accepted block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import generate, speculative_generate
from byteps_tpu.models.transformer import Transformer, TransformerConfig


def _model(layers, seed, vocab=31, max_len=96):
    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=layers, num_heads=2, d_model=32,
        d_ff=64, max_seq_len=max_len, dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (3, 8), 0, vocab)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    return model, variables, tokens


def test_spec_exact_disagreeing_draft():
    """Independent random draft: near-zero acceptance, output still equals
    target-only greedy."""
    target, tvars, tokens = _model(2, 1)
    draft, dvars, _ = _model(1, 99)
    want = generate(target, tvars, tokens, 12, temperature=0)
    got = speculative_generate(target, tvars, draft, dvars, tokens, 12,
                               gamma=3)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(want["tokens"]))


def test_spec_exact_perfect_draft():
    """Draft == target: near-total acceptance and identical output.
    Acceptance can fall a hair short of 1.0: the draft decodes tq=1
    while the verifier runs tq=G+1, so fp reduction orders differ and a
    near-tie argmax can flip — output equality is what the algorithm
    guarantees (regression guard: a draft-cache hole at pos+G once
    capped this at ~0.87)."""
    target, tvars, tokens = _model(2, 1)
    want = generate(target, tvars, tokens, 12, temperature=0)
    got = speculative_generate(target, tvars, target, tvars, tokens, 12,
                               gamma=4)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(want["tokens"]))
    # acceptance asserted on a single row: the lockstep batch-min
    # amplifies rare per-row fp flips (3 rows x 4 drafts all must agree)
    row = tokens[:1]
    got1 = speculative_generate(target, tvars, target, tvars, row, 12,
                                gamma=4)
    assert float(got1["acceptance"]) > 0.75
    assert int(got1["rounds"]) <= 4  # near-optimal: ceil(11/5)=3 rounds


def test_spec_gamma_one_and_large():
    target, tvars, tokens = _model(2, 1)
    draft, dvars, _ = _model(1, 7)
    want = generate(target, tvars, tokens, 10, temperature=0)
    for gamma in (1, 8):
        got = speculative_generate(target, tvars, draft, dvars, tokens,
                                   10, gamma=gamma)
        np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                      np.asarray(want["tokens"]))


def test_spec_eos_matches_generate():
    """EOS freezing must match generate()'s semantics even when the eos
    lands inside an accepted block."""
    target, tvars, tokens = _model(2, 1)
    ref = generate(target, tvars, tokens, 10, temperature=0)
    # pick a token that actually appears early in the greedy output
    eos = int(np.asarray(ref["tokens"])[0, 2])
    want = generate(target, tvars, tokens, 10, temperature=0,
                    eos_id=eos, pad_id=0)
    got = speculative_generate(target, tvars, target, tvars, tokens, 10,
                               gamma=4, eos_id=eos, pad_id=0)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(want["tokens"]))


def test_truncated_self_draft_exact_and_cheap():
    """LayerSkip-style self-draft (inference.truncated_draft): the
    target's own first layers as draft — output still equals target-only
    greedy (the speculative contract is draft-independent), the draft's
    param tree is a strict subset sharing the target's arrays, and bad
    layer counts raise."""
    import pytest

    from byteps_tpu.inference import truncated_draft

    target, tvars, tokens = _model(4, 1)
    dmodel, dvars = truncated_draft(target.cfg, tvars, 2)
    assert dmodel.cfg.num_layers == 2
    assert set(dvars["params"]) == {
        "embed", "pos", "block_0", "block_1", "ln_f", "lm_head"}
    # shared leaves, not copies
    assert dvars["params"]["block_0"] is tvars["params"]["block_0"]
    want = generate(target, tvars, tokens, 12, temperature=0)
    got = speculative_generate(target, tvars, dmodel, dvars, tokens, 12,
                               gamma=3)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(want["tokens"]))
    with pytest.raises(ValueError, match="num_layers"):
        truncated_draft(target.cfg, tvars, 5)


@pytest.mark.slow  # ~40s on CPU: trains the target model to convergence
def test_truncated_draft_acceptance_rises_with_training():
    """The LayerSkip premise, empirically: on RANDOM weights a truncated
    self-draft is uncorrelated with the full model (acceptance ~0), but
    once the model is TRAINED the early
    layers carry the signal and the same draft's proposals are accepted
    at a high rate.  (Output correctness is draft-independent either
    way — pinned by the other tests.)"""
    import optax

    from byteps_tpu.inference import truncated_draft

    vocab = 64
    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=2, num_heads=2, d_model=64,
        d_ff=128, max_seq_len=48, dtype=jnp.float32)
    model = Transformer(cfg)

    def batch(key, B=16, T=16):
        # repeating 4-token patterns: learnable by one layer
        pat = jax.random.randint(key, (B, 4), 0, vocab)
        return jnp.tile(pat, (1, (T + 3) // 4))[:, :T]

    toks0 = batch(jax.random.PRNGKey(0))
    variables = model.init(jax.random.PRNGKey(1), toks0)
    params = variables["params"]

    def acceptance(p):
        # single prompt row: batched speculation accepts the lockstep
        # minimum across rows, which amplifies per-row noise (see
        # test_spec_exact_perfect_draft)
        dmodel, dvars = truncated_draft(cfg, {"params": p}, 1)
        prompt = batch(jax.random.PRNGKey(99), B=1, T=8)
        out = speculative_generate(model, {"params": p}, dmodel, dvars,
                                   prompt, 12, gamma=4)
        return float(out["acceptance"])

    acc_random = acceptance(params)

    tx = optax.adam(3e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, toks):
        def loss_of(p):
            logits = model.apply({"params": p}, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], toks[:, 1:]).mean()

        loss, grads = jax.value_and_grad(loss_of)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    rng = jax.random.PRNGKey(2)
    for _ in range(300):
        rng, sub = jax.random.split(rng)
        params, opt_state, _ = step(params, opt_state,
                                    batch(sub, B=32))

    acc_trained = acceptance(params)
    # ~0.67 on this config: a vanilla-trained model's early-exit readout
    # (ln_f + head on block_0's output) was never itself trained, which
    # is why LayerSkip adds early-exit losses — the test pins the RISE,
    # not perfection
    assert acc_trained > 0.5, acc_trained
    assert acc_trained > acc_random + 0.4, (acc_random, acc_trained)
