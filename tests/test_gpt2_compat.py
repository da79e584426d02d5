"""GPT-2 architecture compatibility (integrations/gpt2.py).

Ground truth is HF's torch ``GPT2LMHeadModel`` itself, randomly
initialized (no network access needed): converted weights must reproduce
its logits, and the whole inference stack must run on the converted
model.
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
    speculative_generate,
)
from byteps_tpu.integrations.gpt2 import gpt2_config, load_gpt2  # noqa: E402


def _hf_model(n_layer=2, n_head=2, n_embd=32, vocab=97, n_positions=64,
              seed=0):
    torch.manual_seed(seed)
    cfg = transformers.GPT2Config(
        n_layer=n_layer, n_head=n_head, n_embd=n_embd, vocab_size=vocab,
        n_positions=n_positions, resid_pdrop=0.0, embd_pdrop=0.0,
        attn_pdrop=0.0)
    return transformers.GPT2LMHeadModel(cfg).eval()


@pytest.mark.slow  # ~12s: HF torch forward; inference_stack_on_gpt2 + gpt2_arch_trains stay fast, llama keeps a fast torch-logits parity
def test_logits_match_torch():
    hf = _hf_model()
    model, variables = load_gpt2(hf)
    tokens = np.random.RandomState(0).randint(0, 97, size=(2, 12))
    with torch.no_grad():
        want = hf(torch.tensor(tokens)).logits.numpy()
    got = np.asarray(model.apply(variables, jnp.asarray(tokens)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # ~11s: HF torch generation loop; gpt2_arch_trains_with_fused_loss + config mapping stay fast
def test_greedy_generation_matches_torch():
    hf = _hf_model(seed=3)
    model, variables = load_gpt2(hf)
    prompt = np.random.RandomState(1).randint(0, 97, size=(2, 8))
    with torch.no_grad():
        want = hf.generate(
            torch.tensor(prompt), max_new_tokens=6, do_sample=False,
            pad_token_id=0).numpy()[:, 8:]
    got = np.asarray(
        generate(model, variables, jnp.asarray(prompt), 6,
                 temperature=0)["tokens"])
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_inference_stack_on_gpt2():
    """Beam search, speculative decoding, int8 quantization, and the KV
    cache all run on converted GPT-2 weights.  Slow: four inference
    modes x compile on the GPT-2 arch;
    test_greedy_generation_matches_torch keeps the fast conversion
    parity coverage."""
    hf = _hf_model(seed=5)
    model, variables = load_gpt2(hf)
    prompt = jnp.asarray(
        np.random.RandomState(2).randint(0, 97, size=(2, 8)))
    greedy = generate(model, variables, prompt, 5, temperature=0)
    beam = beam_search(model, variables, prompt, 5, 1)
    np.testing.assert_array_equal(np.asarray(beam["tokens"]),
                                  np.asarray(greedy["tokens"]))
    draft_hf = _hf_model(n_layer=1, seed=9)
    draft, dvars = load_gpt2(draft_hf)
    spec = speculative_generate(model, variables, draft, dvars, prompt, 5,
                                gamma=2)
    np.testing.assert_array_equal(np.asarray(spec["tokens"]),
                                  np.asarray(greedy["tokens"]))
    q = {"params": quantize_params(variables["params"])}
    qout = generate(model, q, prompt, 5, temperature=0)
    assert qout["tokens"].shape == (2, 5)


def test_gpt2_arch_trains_with_fused_loss():
    """The tied-embedding GPT-2 architecture trains through the framework
    loss path — the fused LM head reads the embedding transpose when no
    lm_head exists (regression: KeyError 'lm_head')."""
    import optax
    from jax.sharding import Mesh

    from byteps_tpu.training import make_data_parallel_step, shard_batch
    from byteps_tpu.training.step import lm_loss_fn

    hf = _hf_model(vocab=128)
    model, variables = load_gpt2(hf)
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    step = make_data_parallel_step(
        lm_loss_fn(model, fused_head=True), optax.adam(1e-3), mesh)
    state = step.init_state(variables["params"])
    tokens = jnp.asarray(
        np.random.RandomState(3).randint(0, 128, size=(16, 16)))
    batch = shard_batch({"tokens": tokens}, mesh)
    first = None
    for _ in range(8):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first


def test_gpt2_config_mapping():
    hf = _hf_model()
    cfg = gpt2_config(hf.config)
    assert cfg.norm == "layernorm" and cfg.use_bias and cfg.tie_embeddings
    assert cfg.norm_eps == hf.config.layer_norm_epsilon
    assert cfg.d_ff == 4 * hf.config.n_embd
    # no lm_head in the tied tree
    _, variables = load_gpt2(hf)
    assert "lm_head" not in variables["params"]
