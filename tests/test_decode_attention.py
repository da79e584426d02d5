"""Decode-attention kernel v2 vs the dense cached path (exact-match).

The kernel must be a drop-in for ``_cached_attention`` at tq=1 —
byte-level agreement is not expected (online softmax reassociates the
f32 reductions) but bf16-tight agreement is.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.transformer import _cached_attention
from byteps_tpu.ops.decode_attention import (
    decode_attention,
    decode_attention_usable,
)


def _mk(B, S, H, KV, D, pos, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    ck = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    cv = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    # unwritten tail: garbage beyond pos must not leak into the output
    tail = jnp.arange(S)[None, :, None, None] > pos
    ck = jnp.where(tail, jnp.float32(37.0).astype(dtype), ck)
    cv = jnp.where(tail, jnp.float32(-53.0).astype(dtype), cv)
    return q, ck, cv


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("pos", [0, 63, 64, 200, 255])
def test_matches_dense(H, KV, pos):
    B, S, D = 2, 256, 64
    q, ck, cv = _mk(B, S, H, KV, D, pos)
    want = _cached_attention(q, ck, cv, pos)
    got = decode_attention(q, ck, cv, pos, block_s=64, interpret=True)
    assert got.shape == want.shape == (B, 1, H, D)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("pos", [10, 100, 190])
def test_matches_dense_window(pos):
    B, S, H, KV, D = 1, 192, 4, 2, 64
    q, ck, cv = _mk(B, S, H, KV, D, pos, seed=3)
    want = _cached_attention(q, ck, cv, pos, window=48)
    got = decode_attention(q, ck, cv, pos, window=48, block_s=64,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_traced_pos_one_program():
    """pos may be a traced scalar (the generate scan carry): one compiled
    program must serve every step."""
    B, S, H, KV, D = 1, 128, 4, 4, 64
    q, ck, cv = _mk(B, S, H, KV, D, 127, seed=5)

    traces = []

    @jax.jit
    def step(q, ck, cv, pos):
        traces.append(None)
        return decode_attention(q, ck, cv, pos, block_s=64,
                                interpret=True)

    for pos in (0, 31, 64, 127):
        want = _cached_attention(q, ck, cv, pos)
        got = step(q, ck, cv, jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)
    assert len(traces) == 1


def test_usable_gate():
    assert decode_attention_usable((8, 1, 12, 64), 1280, False)
    assert not decode_attention_usable((8, 4, 12, 64), 1280, False)
    # s8 auto: MHA only (where the flat-s8 kernel won — GQA's shrunken
    # cache no longer pays for the in-VMEM dequant)
    assert decode_attention_usable((8, 1, 12, 64), 1280, True,
                                   kv_heads=12)
    assert not decode_attention_usable((8, 1, 12, 64), 1280, True,
                                       kv_heads=2)
    assert not decode_attention_usable((8, 1, 12, 64), 1280, True)
    # awkward cache lengths are fine: the grid is ceil(S/block) with the
    # tail masked
    assert decode_attention_usable((8, 1, 12, 64), 1021, False)


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("pos", [0, 33, 200])
def test_int8_matches_grouped_q8_path(H, KV, pos):
    """The flat-int8 kernel (s8 stream + in-VMEM dequant, scales folded
    into scores/probabilities) must match the dense grouped mixed-dot
    path on the SAME quantized values."""
    from byteps_tpu.models.transformer import (
        _cached_attention_q8,
        _quantize_kv,
    )

    B, S, D = 2, 256, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    kfull = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    vfull = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    kq, kscale = _quantize_kv(kfull)
    vq, vscale = _quantize_kv(vfull)
    want = _cached_attention_q8(q, kq, kscale, vq, vscale, pos)
    got = decode_attention(
        q, kq.reshape(B, S, KV * D), vq.reshape(B, S, KV * D), pos,
        k_scale=kscale, v_scale=vscale, block_s=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow  # ~10s: two full generates; int8_matches_grouped_q8_path/window/tail-chunk parity stays fast
def test_flat_int8_generate_matches_grouped_int8():
    """End to end: generate() on a flat int8 cache (layout='flat',
    kv_quant) produces the same tokens as the grouped int8 cache — the
    write-time quantization is identical, only the decode data path
    differs."""
    from byteps_tpu.inference import make_generate_fn
    from byteps_tpu.models.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=61, num_layers=2, num_heads=4, num_kv_heads=2,
        d_model=32, d_ff=64, max_seq_len=64, dtype=jnp.float32,
        pos_emb="rope")
    model = Transformer(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 9), 0, 61)
    variables = model.init(jax.random.PRNGKey(1), prompt)
    grouped = make_generate_fn(model, 8, temperature=0, kv_quant=True,
                               cache_layout="grouped")(
        variables, prompt, jax.random.PRNGKey(0))
    flat = make_generate_fn(model, 8, temperature=0, kv_quant=True,
                            cache_layout="flat")(
        variables, prompt, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(grouped["tokens"]),
                                  np.asarray(flat["tokens"]))


@pytest.mark.parametrize("pos", [100, 150])
def test_int8_tail_chunk_padding(pos):
    """Regression: a cache length that does NOT divide the chunk makes
    the last chunk's out-of-range SCALE rows padding (NaN in interpret
    mode, arbitrary bits on hardware); p's zero columns do not survive
    0 * NaN, so the kernel must mask the scale rows before folding them
    into p.  (Caught on hardware as 'real' divergence at B=8/S=576.)"""
    from byteps_tpu.models.transformer import (
        _cached_attention_q8,
        _quantize_kv,
    )

    B, S, H, KV, D = 2, 160, 4, 4, 16   # S=160, block 64 -> tail of 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    kfull = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    vfull = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    kq, kscale = _quantize_kv(kfull)
    vq, vscale = _quantize_kv(vfull)
    want = _cached_attention_q8(q, kq, kscale, vq, vscale, pos)
    got = decode_attention(
        q, kq.reshape(B, S, KV * D), vq.reshape(B, S, KV * D), pos,
        k_scale=kscale, v_scale=vscale, block_s=64, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("pos", [60, 150])
def test_int8_window_matches_grouped_q8(pos):
    """Sliding-window attention through the quant kernel: the window
    band mask composes with the scale folding (both sides of the valid
    mask) and matches the dense mixed-dot path."""
    from byteps_tpu.models.transformer import (
        _cached_attention_q8,
        _quantize_kv,
    )

    B, S, H, KV, D, W = 1, 192, 4, 2, 16, 48
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    kfull = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    vfull = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    kq, kscale = _quantize_kv(kfull)
    vq, vscale = _quantize_kv(vfull)
    want = _cached_attention_q8(q, kq, kscale, vq, vscale, pos, window=W)
    got = decode_attention(
        q, kq.reshape(B, S, KV * D), vq.reshape(B, S, KV * D), pos,
        k_scale=kscale, v_scale=vscale, window=W, block_s=64,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
