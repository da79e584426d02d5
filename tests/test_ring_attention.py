"""Ring/Ulysses sequence-parallel attention vs the local reference.

Contract: sharding the sequence over a mesh axis and running ring or
Ulysses attention must reproduce plain full-sequence attention exactly
(up to fp tolerance).  Runs on the virtual 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.parallel.collectives import shard_map
from byteps_tpu.parallel.ring_attention import (
    local_attention,
    ring_attention,
    ring_flash_attention,
    ulysses_attention,
)

B, T, H, D = 2, 32, 4, 8


def _qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def _fa():
    import sys

    # (``byteps_tpu.ops`` shadows the submodule with the function)
    return sys.modules["byteps_tpu.ops.flash_attention"]


@pytest.fixture(params=["fused", "split"])
def backward(request, monkeypatch):
    """The flash backward: the single kernel the shape picks here, or —
    no head's dq fits — the dq and the dk/dv kernel."""
    if request.param == "split":
        monkeypatch.setattr(_fa(), "_FUSED_BWD_DQ_BYTES", 0)
    return request.param


@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("nshards", [2, 4])
def test_sequence_parallel_matches_local(impl, causal, nshards):
    q, k, v = _qkv()
    expected = local_attention(q, k, v, causal=causal)

    mesh = _mesh(nshards)
    fn = shard_map(
        lambda a, b, c: impl(a, b, c, axis_name="sp", causal=causal),
        mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grad_matches_local():
    q, k, v = _qkv(1)
    mesh = _mesh(4)

    def loss_local(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    fn = shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", causal=True),
        mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )

    def loss_ring(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g_local = jax.grad(loss_local)(q, k, v)
    g_ring = jax.grad(jax.jit(loss_ring))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_local),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("nshards", [2, 4])
def test_ring_flash_matches_local(causal, nshards):
    """flash (x) sp composition (VERDICT item 9): the ring schedule with the
    Pallas kernel per block reproduces full local attention."""
    q, k, v = _qkv(3)
    expected = local_attention(q, k, v, causal=causal)

    mesh = _mesh(nshards)
    fn = shard_map(
        lambda a, b, c: ring_flash_attention(
            a, b, c, axis_name="sp", causal=causal),
        mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_flash_grad_matches_local(backward):
    """End-to-end differentiability of flash (x) sp — the lse cotangent
    path through the Pallas backward: the single kernel, and the dq +
    dk/dv pair a local sequence too long for it takes."""
    q, k, v = _qkv(4)
    mesh = _mesh(4)

    def loss_local(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    fn = shard_map(
        lambda a, b, c: ring_flash_attention(
            a, b, c, axis_name="sp", causal=True),
        mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )

    def loss_ring(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g_local = jax.grad(loss_local, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(jax.jit(loss_ring), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_local):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_with_lse_grads():
    """flash_attention_with_lse is differentiable in BOTH outputs: compare
    against the dense (o, logsumexp) computation."""
    _check_with_lse_grads(False, 16)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block,dv", [(T, D), (T // 2, D), (T // 4, 2 * D)])
def test_flash_with_lse_grads_across_sub_tiles(monkeypatch, block, dv,
                                               causal, backward):
    """The same with a non-zero ``dlse`` through a block of 4 x 4
    sub-tiles, a 2 x 2 grid of 2 x 2 and a 4 x 4 grid whose v / o heads
    are wider than q / k — ring attention's diagonal block is the causal
    case, its off-diagonal blocks the non-causal one."""
    monkeypatch.setattr(_fa(), "_SUB_TILE", 8)
    _check_with_lse_grads(causal, block, dv)


def _check_with_lse_grads(causal, block, dv=D):
    from byteps_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(5)
    v = jax.random.normal(jax.random.PRNGKey(6), (B, T, H, dv), jnp.float32)
    scale = D ** -0.5

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bqhk", q * scale, k)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, :, None, :],
                          s, -1e30)
        o = jnp.einsum("bqhk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B, Tq, H]
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal, None, block,
                                          block)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    np.testing.assert_allclose(float(flash(q, k, v)), float(dense(q, k, v)),
                               rtol=1e-5)
    g_d = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    g_f = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ulysses_requires_divisible_heads():
    # H=4 shards=8 -> all_to_all cannot split 4 heads 8 ways
    q, k, v = _qkv(2)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    fn = shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp"),
        mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    with pytest.raises(Exception):
        jax.jit(fn)(q, k, v)
