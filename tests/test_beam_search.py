"""Beam-search decoding (inference.beam_search).

Ground truth is a naive reference implementation that re-runs the full
forward over the growing sequences each step (no cache, python loop) —
the cached scan version must reproduce its surviving beams exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.inference import beam_search, generate
from byteps_tpu.models.transformer import Transformer, TransformerConfig


def _model(vocab=23):
    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=48, dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, vocab)
    variables = model.init(jax.random.PRNGKey(1), tokens)
    return cfg, model, tokens, variables


def _reference_beam(model, variables, prompt, n, k):
    """Naive no-cache beam search: full forward per step, per batch row."""
    B = prompt.shape[0]
    out_toks, out_scores = [], []
    for b in range(B):
        seqs = [np.asarray(prompt[b])]
        scores = [0.0]
        for _ in range(n):
            cand = []
            for s, sc in zip(seqs, scores):
                logits = model.apply(
                    variables, jnp.asarray(s)[None, :])[0, -1]
                lp = np.asarray(jax.nn.log_softmax(
                    logits.astype(jnp.float32)))
                for v in range(len(lp)):
                    cand.append((np.append(s, v), sc + lp[v]))
            cand.sort(key=lambda t: -t[1])
            seqs = [c[0] for c in cand[:k]]
            scores = [c[1] for c in cand[:k]]
        out_toks.append(seqs[0][prompt.shape[1]:])
        out_scores.append(scores[0])
    return np.stack(out_toks), np.array(out_scores)


@pytest.mark.slow  # ~21s: brute-force all-path reference enumeration; beam1_is_greedy/eos/length_penalty keep fast coverage
def test_beam_matches_reference():
    cfg, model, tokens, variables = _model()
    n, k = 4, 3
    got = beam_search(model, variables, tokens, n, k)
    want_toks, want_scores = _reference_beam(model, variables, tokens, n, k)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), want_toks)
    # scores are length-normalized with penalty 1.0 => score / n
    np.testing.assert_allclose(np.asarray(got["scores"]), want_scores / n,
                               rtol=1e-4, atol=1e-4)


def test_beam1_is_greedy():
    cfg, model, tokens, variables = _model()
    beam = beam_search(model, variables, tokens, 6, 1)
    greedy = generate(model, variables, tokens, 6, temperature=0)
    np.testing.assert_array_equal(np.asarray(beam["tokens"]),
                                  np.asarray(greedy["tokens"]))


def test_beam_improves_on_greedy():
    cfg, model, tokens, variables = _model()
    n = 5

    def seq_logprob(toks):
        full = jnp.concatenate([tokens, jnp.asarray(toks)], axis=1)
        logits = model.apply(variables, full).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        total = []
        T = tokens.shape[1]
        for b in range(full.shape[0]):
            s = 0.0
            for i in range(n):
                s += float(lp[b, T + i - 1, int(full[b, T + i])])
            total.append(s)
        return np.array(total)

    greedy = generate(model, variables, tokens, n, temperature=0)
    beam = beam_search(model, variables, tokens, n, 4)
    g = seq_logprob(np.asarray(greedy["tokens"]))
    b = seq_logprob(np.asarray(beam["tokens"]))
    assert (b >= g - 1e-5).all(), f"beam {b} worse than greedy {g}"


def test_beam_eos_freezes():
    cfg, model, tokens, variables = _model()
    first = beam_search(model, variables, tokens, 5, 2)
    eos = int(first["tokens"][0, 1])  # make the 2nd emitted token the eos
    out = beam_search(model, variables, tokens, 5, 2, eos_id=eos, pad_id=0)
    row = np.asarray(out["beam_tokens"][0])  # [K, N]
    for beam_row in row:
        if eos in beam_row.tolist():
            i = beam_row.tolist().index(eos)
            assert (beam_row[i + 1:] == 0).all()
    assert out["beam_scores"].shape == (2, 2)


def test_beam_length_penalty_ranks():
    cfg, model, tokens, variables = _model()
    out1 = beam_search(model, variables, tokens, 4, 3, length_penalty=1.0)
    out2 = beam_search(model, variables, tokens, 4, 3, length_penalty=2.0)
    # same beams, different normalization: scores differ, shapes agree
    assert out1["tokens"].shape == out2["tokens"].shape == (2, 4)
    assert not np.allclose(np.asarray(out1["scores"]),
                           np.asarray(out2["scores"]))
