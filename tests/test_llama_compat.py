"""LLaMA architecture compatibility (integrations/llama.py).

Ground truth is HF's torch ``LlamaForCausalLM`` itself, randomly
initialized (no network access needed): converted weights must reproduce
its logits, and the whole inference stack — RoPE cached decode, GQA
grouping, beam, speculative, int8 — must run on the converted model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from byteps_tpu.inference import (  # noqa: E402
    beam_search,
    generate,
    quantize_params,
)
from byteps_tpu.integrations.llama import (  # noqa: E402
    llama_config,
    load_llama,
)

VOCAB = 97


def _hf_model(layers=2, heads=4, kv_heads=2, d=64, d_ff=128, seed=0,
              **kw):
    torch.manual_seed(seed)
    cfg = transformers.LlamaConfig(
        hidden_size=d, intermediate_size=d_ff, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        vocab_size=VOCAB, max_position_embeddings=64, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        attention_dropout=0.0, **kw)
    return transformers.LlamaForCausalLM(cfg).eval()


def test_logits_match_torch():
    hf = _hf_model()
    model, variables = load_llama(hf)
    assert model.cfg.pos_emb == "rope"
    assert model.cfg.mlp == "swiglu"
    assert model.cfg.kv_heads == 2
    tokens = np.random.RandomState(0).randint(0, VOCAB, size=(2, 12))
    with torch.no_grad():
        want = hf(torch.tensor(tokens)).logits.numpy()
    got = np.asarray(model.apply(variables, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_mha_llama_matches_torch():
    """num_key_value_heads == num_attention_heads (original LLaMA-1/2-7B
    layout) converts and matches too."""
    hf = _hf_model(kv_heads=4, seed=3)
    model, variables = load_llama(hf)
    tokens = np.random.RandomState(1).randint(0, VOCAB, size=(1, 9))
    with torch.no_grad():
        want = hf(torch.tensor(tokens)).logits.numpy()
    got = np.asarray(model.apply(variables, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_cached_decode_matches_hf_greedy():
    """Greedy generation through the RoPE/GQA KV-cache decode equals
    HF's own greedy continuation."""
    hf = _hf_model(seed=1)
    model, variables = load_llama(hf)
    prompt = np.random.RandomState(2).randint(0, VOCAB, size=(2, 8))
    N = 8
    with torch.no_grad():
        hf_out = hf.generate(
            torch.tensor(prompt), max_new_tokens=N, do_sample=False,
            num_beams=1, pad_token_id=0)
    want = hf_out.numpy()[:, 8:]
    out = generate(model, variables, jnp.asarray(prompt), N,
                   temperature=0)
    np.testing.assert_array_equal(np.asarray(out["tokens"]), want)


@pytest.mark.slow  # ~11s; logits_match_torch + cached_decode_matches_hf_greedy keep fast llama parity
def test_inference_stack_runs_on_llama():
    """Beam search, speculative (truncated self-draft), and int8
    weight-only quantization all run on converted LLaMA weights."""
    from byteps_tpu.inference import speculative_generate, truncated_draft

    hf = _hf_model(seed=2)
    model, variables = load_llama(hf)
    prompt = jnp.asarray(
        np.random.RandomState(3).randint(0, VOCAB, size=(2, 6)))
    want = generate(model, variables, prompt, 6, temperature=0)["tokens"]

    bm = beam_search(model, variables, prompt, 6, num_beams=3)
    assert bm["tokens"].shape == (2, 6)

    dmodel, dvars = truncated_draft(model.cfg, variables, 1)
    sp = speculative_generate(model, variables, dmodel, dvars, prompt, 6,
                              gamma=3)
    np.testing.assert_array_equal(np.asarray(sp["tokens"]),
                                  np.asarray(want))

    qvars = {"params": quantize_params(variables["params"])}
    qout = generate(model, qvars, prompt, 6, temperature=0)
    assert qout["tokens"].shape == (2, 6)


def test_unsupported_axes_raise():
    hf = _hf_model()
    with pytest.raises(ValueError, match="hidden_act"):
        llama_config(type("C", (), dict(
            vars(hf.config), hidden_act="gelu"))())
    bad = _hf_model()
    bad.config.rope_scaling = {"rope_type": "yarn", "factor": 2.0}
    with pytest.raises(ValueError, match="rope_scaling"):
        llama_config(bad.config)


# ---------------------------------------------------------------------------
# Llama-3.x axes: rope_scaling (llama3 / linear) + explicit head_dim
# ---------------------------------------------------------------------------


def test_llama3_rope_scaling_and_head_dim_match_torch():
    """The Llama-3 frequency-rescale schedule and an explicit
    head_dim != hidden_size/num_heads must reproduce HF logits — these
    are the axes every 2024+ LLaMA checkpoint sets (r4 verdict #4)."""
    hf = _hf_model(
        seed=7, head_dim=24,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 16})
    model, variables = load_llama(hf)
    assert model.cfg.head_dim == 24
    assert dict(model.cfg.rope_scaling)["rope_type"] == "llama3"
    tokens = np.random.RandomState(2).randint(0, VOCAB, size=(2, 20))
    with torch.no_grad():
        want = hf(torch.tensor(tokens)).logits.numpy()
    got = np.asarray(model.apply(variables, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.slow  # ~18s: stepwise HF forward per token; cached_decode_matches_hf_greedy keeps fast parity
def test_llama3_cached_decode_matches_hf_forward_stepwise():
    """Cached decode under llama3 scaling + explicit head_dim must
    reproduce HF's forward logits at every step (teacher-forced).  NOT
    compared against ``hf.generate`` token chains: HF's own cached
    generate flips near-tie argmaxes vs its forward (measured: a 0.04
    logit gap flipped at step 1 on this random model), and chain
    equality amplifies one flip into total divergence."""
    hf = _hf_model(
        seed=11, head_dim=24,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 16})
    model, variables = load_llama(hf)
    from byteps_tpu.models.transformer import (
        Transformer as _T,
        init_cache,
    )

    rs = np.random.RandomState(3)
    prompt = rs.randint(0, VOCAB, size=(2, 8))
    cont = rs.randint(0, VOCAB, size=(2, 6))
    full = np.concatenate([prompt, cont], axis=1)
    with torch.no_grad():
        want = hf(torch.tensor(full)).logits.numpy()
    caches = init_cache(model.cfg, 2, 16)
    lg, caches = model.apply(variables, jnp.asarray(prompt), caches, 0,
                             method=_T.decode)
    got = [np.asarray(lg)]
    for t in range(cont.shape[1]):
        lg, caches = model.apply(
            variables, jnp.asarray(full[:, 8 + t:9 + t]), caches, 8 + t,
            method=_T.decode)
        got.append(np.asarray(lg))
    got = np.concatenate(got, axis=1)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)

    # self-consistency: our generate is exactly our forward's argmax
    # chain (greedy), llama3 scaling active in both paths
    N = 6
    toks = np.asarray(generate(model, variables, jnp.asarray(prompt), N,
                               temperature=0)["tokens"])
    seq = prompt.copy()
    for i in range(N):
        nxt = np.asarray(
            model.apply(variables, jnp.asarray(seq)))[:, -1].argmax(-1)
        np.testing.assert_array_equal(toks[:, i], nxt)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)


def test_linear_rope_scaling_matches_torch():
    hf = _hf_model(
        seed=13,
        rope_scaling={"rope_type": "linear", "factor": 4.0})
    model, variables = load_llama(hf)
    tokens = np.random.RandomState(4).randint(0, VOCAB, size=(1, 16))
    with torch.no_grad():
        want = hf(torch.tensor(tokens)).logits.numpy()
    got = np.asarray(model.apply(variables, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_redundant_head_dim_is_derived():
    hf = _hf_model(seed=17, head_dim=16)  # == hidden/heads: redundant
    model, variables = load_llama(hf)
    assert model.cfg.head_dim is None
