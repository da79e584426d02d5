"""Fused linear+cross-entropy kernel tests: forward and both gradients
match the naive x@W → softmax-CE path (which materializes [N, V]
logits); odd sizes exercise the gcd block clamping; integer targets
never receive a gradient.  The block chooser is held to its rule at the
shapes the benchmark's cells run, and the three kernels to the naive
path where each takes blocks of its own."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.observability.metrics import get_registry
from byteps_tpu.ops.fused_cross_entropy import fused_linear_cross_entropy

# (``ops/__init__`` shadows the submodule with the function of its name)
fce = sys.modules["byteps_tpu.ops.fused_cross_entropy"]
KERNELS = ("fwd", "dx", "dw")


def _naive(x, w, targets):
    logits = (x @ w).astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(logits, targets)


@pytest.mark.parametrize("N,H,V", [(32, 16, 64), (64, 32, 128), (40, 24, 96)])
def test_forward_matches_naive(N, H, V):
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (N, H), jnp.float32)
    w = jax.random.normal(kw, (H, V), jnp.float32) * 0.1
    t = jax.random.randint(kt, (N,), 0, V)
    got = fused_linear_cross_entropy(x, w, t, 16, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_naive(x, w, t)),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_naive():
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(1), 3)
    N, H, V = 32, 16, 64
    x = jax.random.normal(kx, (N, H), jnp.float32)
    w = jax.random.normal(kw, (H, V), jnp.float32) * 0.1
    t = jax.random.randint(kt, (N,), 0, V)

    gx_f, gw_f = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, t, 16, 32).mean(),
        argnums=(0, 1))(x, w)
    gx_n, gw_n = jax.grad(
        lambda x, w: _naive(x, w, t).mean(), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_n),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_n),
                               rtol=1e-4, atol=1e-5)


def test_bf16_inputs():
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(2), 3)
    N, H, V = 32, 32, 128
    x = jax.random.normal(kx, (N, H), jnp.bfloat16)
    w = (jax.random.normal(kw, (H, V)) * 0.1).astype(jnp.bfloat16)
    t = jax.random.randint(kt, (N,), 0, V)
    got = fused_linear_cross_entropy(x, w, t, 16, 32)
    want = _naive(x.astype(jnp.float32), w.astype(jnp.float32), t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)
    gx, gw = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, t, 16, 32).mean(),
        argnums=(0, 1))(x, w)
    assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(gx, np.float32)).all()


def test_weighted_dloss_flows():
    """Non-uniform loss cotangent (e.g. masked-token weighting) is
    respected by both backward kernels."""
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(3), 3)
    N, H, V = 16, 8, 32
    x = jax.random.normal(kx, (N, H), jnp.float32)
    w = jax.random.normal(kw, (H, V), jnp.float32) * 0.1
    t = jax.random.randint(kt, (N,), 0, V)
    wgt = jnp.linspace(0.0, 1.0, N)

    gx_f = jax.grad(lambda x: jnp.sum(
        fused_linear_cross_entropy(x, w, t, 8, 16) * wgt))(x)
    gx_n = jax.grad(lambda x: jnp.sum(_naive(x, w, t) * wgt))(x)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_n),
                               rtol=1e-4, atol=1e-5)
    # zero-weight rows get exactly zero gradient
    np.testing.assert_allclose(np.asarray(gx_f[0]), 0.0, atol=1e-7)


def test_ignore_index_rows_masked():
    """HF-style -100 (or any out-of-range) targets: loss 0, zero grad —
    matching the masked naive reduction."""
    kx, kw = jax.random.split(jax.random.PRNGKey(4), 2)
    N, H, V = 16, 8, 32
    x = jax.random.normal(kx, (N, H), jnp.float32)
    w = jax.random.normal(kw, (H, V), jnp.float32) * 0.1
    t = np.arange(N) % V
    t[::4] = -100  # every 4th row padded
    t = jnp.asarray(t)

    loss = fused_linear_cross_entropy(x, w, t, 8, 16)
    np.testing.assert_allclose(np.asarray(loss[::4]), 0.0)
    valid = np.asarray(t) >= 0
    naive = np.asarray(_naive(x, w, jnp.where(t < 0, 0, t)))
    np.testing.assert_allclose(np.asarray(loss)[valid], naive[valid],
                               rtol=1e-5, atol=1e-5)

    gx = jax.grad(lambda x: fused_linear_cross_entropy(x, w, t, 8, 16).sum())(x)
    np.testing.assert_allclose(np.asarray(gx[::4]), 0.0, atol=1e-7)
    gx_naive = jax.grad(
        lambda x: jnp.sum(_naive(x, w, jnp.where(t < 0, 0, t))
                          * valid.astype(np.float32)))(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_naive),
                               rtol=1e-4, atol=1e-6)


def test_training_reduces_loss():
    """End-to-end: a linear classifier trained through the fused kernel
    fits a separable toy problem."""
    rng = np.random.RandomState(0)
    N, H, V = 64, 16, 32
    w_true = rng.randn(H, V).astype(np.float32)
    x = rng.randn(N, H).astype(np.float32)
    t = jnp.asarray(np.argmax(x @ w_true, -1))
    x = jnp.asarray(x)

    w = jnp.zeros((H, V), jnp.float32)
    lossf = jax.jit(jax.value_and_grad(
        lambda w: fused_linear_cross_entropy(x, w, t, 16, 16).mean()))
    l0 = None
    for _ in range(200):
        loss, g = lossf(w)
        l0 = l0 if l0 is not None else float(loss)
        w = w - 0.5 * g
    assert float(loss) < 0.1 * l0, (l0, float(loss))


# (N, H, V): gpt2-medium, smallthinker, joyai at the cells' sizes; a
# shape no cell has; a row count under every preferred block
CHOOSER_SHAPES = [(8192, 1024, 50304), (16384, 2560, 19456),
                  (8192, 2048, 16384), (8192, 768, 32000),
                  (384, 1024, 50304)]


def _widest_divisor(V):
    return max(b for b in range(128, fce._VOCAB_CAP + 1, 128) if V % b == 0)


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("N,H,V", CHOOSER_SHAPES)
def test_chooser_blocks_divide_clear_the_ridge_and_fit(N, H, V, kernel):
    bn, bv = fce.choose_blocks(kernel, N, H, V)
    assert N % bn == 0 and bn % 8 == 0 and bv % 128 == 0, (bn, bv)
    # a masked last block is the forward's alone, and only for a table
    # whose own divisors stop short: 50 304 = 2^7 x 3 x 131 stops at 384
    ragged = kernel == "fwd" and V == 50304
    assert (V % bv != 0) == ragged, (bn, bv)
    # the operand that stays while the other is re-read: rows for fwd /
    # dx (the head streams once a row block), vocabulary for dw (x
    # streams once a vocabulary block)
    if kernel == "dw":
        assert bv >= min(512, _widest_divisor(V)), bv
    else:
        assert bn >= min(512, N), bn
        assert bv >= (512 if ragged else min(512, _widest_divisor(V))), bv
    est = fce.vmem_bytes(kernel, bn, bv, H)
    assert est <= fce._VMEM_BUDGET < fce._VMEM_LIMIT, est


@pytest.mark.parametrize("kernel", KERNELS)
def test_chooser_refuses_a_table_coprime_to_128(kernel):
    with pytest.raises(ValueError, match="multiple of 128"):
        fce.choose_blocks(kernel, 8192, 1024, 50257)


def test_chooser_keeps_an_explicit_block():
    # one given: kept (fitted as before), the other chosen around it;
    # both given: used as they are, whatever they cost in VMEM
    assert fce.choose_blocks("fwd", 8192, 1024, 50304, block_n=64)[0] == 64
    assert fce.choose_blocks("dw", 8192, 1024, 50304, block_v=128)[1] == 128
    assert fce.choose_blocks("dx", 8192, 2560, 19456,
                             block_n=4096, block_v=2432) == (4096, 2432)


# A size the interpreter runs, with the caps that do to it what the
# module's do to the cells': a table whose divisors stop at 384 under
# the vocabulary cap (so the forward takes a masked last block, 4.5
# blocks of 256) and a budget under which the three kernels part
OWN_N, OWN_H, OWN_V = 512, 128, 1152


@pytest.fixture
def own_blocks(monkeypatch):
    monkeypatch.setattr(fce, "_VOCAB_CAP", 512)
    monkeypatch.setattr(fce, "_VMEM_BUDGET", 3 << 20)
    blocks = {k: fce.choose_blocks(k, OWN_N, OWN_H, OWN_V, 4, 4)
              for k in KERNELS}
    assert len(set(blocks.values())) == 3, blocks
    assert OWN_V % blocks["fwd"][1] != 0, blocks
    return blocks


def test_kernels_with_blocks_of_their_own_match_naive(own_blocks):
    """Forward value and both gradients where each kernel takes its own
    blocks and the forward's last vocabulary block is partly past V
    (the interpreter fills what lies past an array with NaN): ignored
    rows in the last row block, targets in the last vocabulary block."""
    N, H, V = OWN_N, OWN_H, OWN_V
    kx, kw, kt, kd = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(kx, (N, H), jnp.float32)
    w = jax.random.normal(kw, (H, V), jnp.float32) * 0.1
    t = np.array(jax.random.randint(kt, (N,), 0, V))
    t[1::5] = V - 1 - np.arange(N)[1::5] % 64     # last vocabulary block
    t[N - 40::3] = -100                           # last row block
    t = jnp.asarray(t)
    wgt = jax.random.uniform(kd, (N,), jnp.float32, 0.5, 1.5)
    valid = (np.asarray(t) >= 0).astype(np.float32)

    def fused(x, w):
        return jnp.sum(fused_linear_cross_entropy(x, w, t) * wgt)

    def naive(x, w):
        return jnp.sum(_naive(x, w, jnp.where(t < 0, 0, t)) * wgt * valid)

    lf, (gxf, gwf) = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    ln, (gxn, gwn) = jax.value_and_grad(naive, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gxf), np.asarray(gxn),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gwf), np.asarray(gwn),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gxf)[valid == 0], 0.0, atol=1e-7)


@pytest.mark.parametrize("kernel", KERNELS)
def test_gauges_hold_what_the_chooser_returned(kernel):
    """Set when a kernel is traced (nothing runs here), at the shape of
    the PR 31 cell: the re-read operand's bytes a call are the number
    the blocks are chosen to hold down."""
    N, H, V = 16384, 2560, 19456
    x = jax.ShapeDtypeStruct((N, H), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((H, V), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((N,), jnp.int32)
    jax.eval_shape(jax.grad(
        lambda x, w, t: fused_linear_cross_entropy(x, w, t).sum(),
        argnums=(0, 1)), x, w, t)
    bn, bv = fce.choose_blocks(kernel, N, H, V)
    reg = get_registry()
    assert reg.get("fused_ce.block_n", kernel=kernel).value == bn
    assert reg.get("fused_ce.block_v", kernel=kernel).value == bv
    reread = V // bv * N * H * 2 if kernel == "dw" else N // bn * H * V * 2
    gb = reg.get("fused_ce.streamed_gb", kernel=kernel).value
    assert gb == pytest.approx(reread / 1e9) and gb <= 3.2, gb
