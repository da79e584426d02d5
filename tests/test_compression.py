"""Compression subsystem — jit domain: registry roundtrip invariants,
seeded determinism under jit, the error-feedback optax transformation
(contraction on a quadratic — timing-independent), and the
training-entry-point integration (world==1 parity, registry names).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.compression import (SCHEMES, CompressionPolicy,
                                    compression_roundtrip, derive_seed,
                                    error_feedback_compress, get_scheme)

ALL_SCHEMES = sorted(SCHEMES)


def _x(n=512, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(n).astype(np.float32))


# ------------------------------------------------------------------ registry


def test_registry_has_the_advertised_schemes():
    assert {"none", "bf16", "fp16", "int8", "topk", "randomk",
            "onebit"} <= set(SCHEMES)


def test_unknown_scheme_raises_with_available_list():
    with pytest.raises(KeyError, match="onebit"):
        get_scheme("snappy")


def test_derive_seed_is_stable_and_name_sensitive():
    assert derive_seed(0, "w", 3) == derive_seed(0, "w", 3)
    assert derive_seed(0, "w", 3) != derive_seed(0, "w", 4)
    assert derive_seed(0, "w", 3) != derive_seed(0, "b", 3)
    assert derive_seed(1, "w", 3) != derive_seed(0, "w", 3)


@pytest.mark.parametrize("name", ALL_SCHEMES)
def test_roundtrip_shape_dtype_finite(name):
    s = get_scheme(name)
    x = _x().reshape(16, 32)
    key = jax.random.PRNGKey(7) if s.seeded else None
    out = s.roundtrip(x, key=key, ratio=0.05)
    assert out.shape == x.shape
    assert out.dtype == x.dtype
    assert bool(jnp.isfinite(out).all())
    # jit traces to the same values as eager — to a few f32 ulps, not
    # bit-for-bit: eager runs each op as its own program while jit fuses
    # them, and XLA is free to order a fused reduction (onebit's
    # mean|x| scale) differently.  Selection and signs are exact in
    # every scheme, so 4 ulps bounds everything a scale can move.
    jout = jax.jit(lambda v: s.roundtrip(v, key=key, ratio=0.05))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jout),
                               rtol=4 * np.finfo(np.float32).eps, atol=0)


def test_onebit_is_sign_times_mean_abs():
    x = _x()
    out = np.asarray(get_scheme("onebit").roundtrip(x))
    scale = float(jnp.mean(jnp.abs(x)))
    np.testing.assert_allclose(
        out, np.where(np.asarray(x) >= 0, scale, -scale), rtol=1e-6)


def test_topk_keeps_exactly_the_largest_coordinates():
    x = _x(100)
    out = np.asarray(get_scheme("topk").roundtrip(x, ratio=0.1))
    kept = np.nonzero(out)[0]
    assert len(kept) == 10
    top = np.argsort(-np.abs(np.asarray(x)))[:10]
    assert set(kept) == set(top)
    np.testing.assert_array_equal(out[kept], np.asarray(x)[kept])


def test_randomk_seeded_determinism_under_jit():
    s = get_scheme("randomk")
    x = _x(200)
    f = jax.jit(lambda v, k: s.roundtrip(v, key=k, ratio=0.1))
    a = f(x, jax.random.PRNGKey(3))
    b = f(x, jax.random.PRNGKey(3))
    c = f(x, jax.random.PRNGKey(4))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert int(jnp.sum(a != 0)) == 20


# ------------------------------------------------------------ error feedback


def test_ef_compress_contracts_on_quadratic():
    """EF-onebit SGD on 0.5||x - t||^2 must contract the error by >=4x
    over a fixed step count — deterministic, no timing, the PR-2 deflake
    style bound (plain signSGD without EF stalls at the scale floor)."""
    target = _x(64, seed=1)
    tx = optax.chain(error_feedback_compress("onebit"), optax.sgd(0.05))
    params = jnp.zeros(64)
    state = tx.init(params)

    @jax.jit
    def step(p, s):
        g = p - target
        up, s = tx.update(g, s, p)
        return optax.apply_updates(p, up), s

    e0 = float(jnp.linalg.norm(params - target))
    for _ in range(80):
        params, state = step(params, state)
    e1 = float(jnp.linalg.norm(params - target))
    assert e1 < e0 / 4, (e0, e1)


def test_ef_residual_tracks_unsent_mass():
    tx = error_feedback_compress("topk", ratio=0.1)
    g = {"w": _x(100)}
    state = tx.init(g)
    up, new_state = tx.update(g, state)
    # corrected == g on step 0; residual must be exactly g - compressed
    np.testing.assert_allclose(np.asarray(new_state.error["w"]),
                               np.asarray(g["w"]) - np.asarray(up["w"]),
                               rtol=1e-6)
    assert int(new_state.count) == 1


def test_ef_state_is_donatable_and_checkpoint_shaped():
    """The residual lives in the optimizer state as a plain pytree: jit
    with donation must accept it (the TrainState donation contract) and
    flatten to arrays only (what training/checkpoint.py serializes)."""
    tx = optax.chain(error_feedback_compress("randomk", ratio=0.1, seed=5),
                     optax.sgd(0.1))
    params = {"a": _x(32), "b": _x(16, seed=2)}
    state = tx.init(params)
    leaves = jax.tree_util.tree_leaves(state)
    assert leaves and all(hasattr(l, "dtype") for l in leaves)

    def step(p, s):
        up, s2 = tx.update(p, s, p)
        return optax.apply_updates(p, up), s2

    donating = jax.jit(step, donate_argnums=(1,))
    p1, s1 = donating(params, state)
    jax.block_until_ready(jax.tree_util.tree_leaves(s1))


def test_ef_seeded_scheme_replays_identically_from_same_state():
    """Re-executing update from the same state (recomputation / replay)
    must pick the same randomk coordinates — seeds derive from the state
    counter, not from ambient randomness."""
    tx = error_feedback_compress("randomk", ratio=0.1, seed=9)
    g = {"w": _x(200)}
    state = tx.init(g)
    up1, _ = tx.update(g, state)
    up2, _ = tx.update(g, state)
    np.testing.assert_array_equal(np.asarray(up1["w"]),
                                  np.asarray(up2["w"]))


def test_compression_roundtrip_tx_matches_scheme():
    tx = compression_roundtrip("bf16")
    g = {"w": _x(64)}
    up, _ = tx.update(g, tx.init(g))
    np.testing.assert_array_equal(
        np.asarray(up["w"]),
        np.asarray(g["w"].astype(jnp.bfloat16).astype(jnp.float32)))


# ------------------------------------------------------------------- policy


def test_policy_threshold_overrides_and_nonfloat():
    p = CompressionPolicy(default="onebit", min_bytes=1024,
                          overrides="embed=topk,head=none", ratio=0.02)
    assert p.scheme_for("w", 4096, np.float32).name == "onebit"
    assert p.scheme_for("w", 512, np.float32) is None         # too small
    assert p.scheme_for("w", 4096, np.int32) is None          # not float
    assert p.scheme_for("embed.kernel", 4096, np.float32).name == "topk"
    assert p.scheme_for("head.kernel#p3", 4096, np.float32) is None
    # partition suffixes inherit the parent's override (substring match)
    assert p.scheme_for("embed.kernel#p3", 4096, np.float32).name == "topk"


def test_policy_rejects_unknown_schemes_eagerly():
    with pytest.raises(KeyError):
        CompressionPolicy(default="bogus")
    with pytest.raises(KeyError):
        CompressionPolicy(overrides="w=bogus")
    with pytest.raises(ValueError):
        CompressionPolicy(overrides="just-a-name")


# ------------------------------------------------- training entry points


def _quadratic_setup():
    rng = np.random.default_rng(0)
    w_true = rng.standard_normal((8, 4)).astype(np.float32)
    X = rng.standard_normal((16, 8)).astype(np.float32)
    return w_true, X, X @ w_true


def _loss_fn(params, mstate, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), mstate


def test_world1_honors_cast_compression():
    """Satellite fix for training/step.py: at world==1 the bf16 wire cast
    is applied locally (same numerics as a multi-worker run), not dropped
    with a warning."""
    from byteps_tpu.ops.compression import Compression
    from byteps_tpu.parallel.mesh import build_mesh
    from byteps_tpu.training import make_data_parallel_step, shard_batch

    mesh = build_mesh(devices=jax.devices()[:1])
    _, X, Y = _quadratic_setup()
    batch = {"x": X, "y": Y}

    def run(compression):
        step = make_data_parallel_step(_loss_fn, optax.sgd(0.1), mesh,
                                       compression=compression)
        state = step.init_state({"w": jnp.full((8, 4), 0.3)})
        state, _ = step(state, shard_batch(batch, mesh))
        return np.asarray(state.params["w"])

    w_bf16 = run(Compression.bf16)
    w_name = run("bf16")
    w_none = run(Compression.none)
    # the cast visibly changes the update, identically for both spellings
    assert not np.array_equal(w_bf16, w_none)
    np.testing.assert_array_equal(w_bf16, w_name)


def test_world1_ef_scheme_engages_and_inapplicable_warns(monkeypatch):
    from byteps_tpu.parallel.mesh import build_mesh
    from byteps_tpu.training import make_data_parallel_step, shard_batch

    mesh = build_mesh(devices=jax.devices()[:1])
    _, X, Y = _quadratic_setup()
    step = make_data_parallel_step(_loss_fn, optax.sgd(0.1), mesh,
                                   compression="onebit")
    state = step.init_state({"w": jnp.zeros((8, 4))})
    batch = shard_batch({"x": X, "y": Y}, mesh)
    for _ in range(40):
        state, m = step(state, batch)
    assert float(m["loss"]) < 1.0  # EF makes signSGD converge
    # EF residual state exists in the chain
    assert len(jax.tree_util.tree_leaves(state.opt_state)) >= 2

    # byteps_tpu's logger has propagate=False, so capture at the source
    warned = []
    from byteps_tpu.common import logging as bps_logging

    real = bps_logging.get_logger()
    monkeypatch.setattr(
        real, "warning", lambda msg, *a: warned.append(msg % a if a else msg))
    make_data_parallel_step(_loss_fn, optax.sgd(0.1), mesh,
                            compression=object())
    assert any("cannot be applied locally" in w for w in warned)


def test_distributed_optimizer_accepts_registry_names():
    from byteps_tpu.training.optimizer import (DistributedOptimizer,
                                               push_pull_gradients)

    tx = DistributedOptimizer(optax.sgd(0.1), compression="onebit",
                              axis_name=None)
    params = {"w": _x(32)}
    state = tx.init(params)
    up, _ = tx.update(params, state, params)
    # sgd(0.1) of the onebit-dequantized gradient: every |update| is
    # exactly lr * mean|g|
    scale = float(jnp.mean(jnp.abs(params["w"])))
    np.testing.assert_allclose(np.abs(np.asarray(up["w"])), 0.1 * scale,
                               rtol=1e-5)

    with pytest.raises(ValueError, match="error-feedback state"):
        push_pull_gradients(compression="onebit")


def test_distributed_optimizer_biased_class_spelling_matches_string():
    """A biased registry *adapter class* (Compression.resolve("onebit"))
    must get the same EF treatment as the string spelling — not silently
    fall through the cast path with wire_dtype=None."""
    from byteps_tpu.ops.compression import Compression
    from byteps_tpu.training.optimizer import DistributedOptimizer

    params = {"w": _x(32)}
    by_name = DistributedOptimizer(optax.sgd(0.1), compression="onebit",
                                   axis_name=None)
    by_class = DistributedOptimizer(
        optax.sgd(0.1), compression=Compression.resolve("onebit"),
        axis_name=None)
    un = by_name.update(params, by_name.init(params), params)[0]
    uc = by_class.update(params, by_class.init(params), params)[0]
    np.testing.assert_array_equal(np.asarray(un["w"]), np.asarray(uc["w"]))
    # and it is genuinely compressed (two distinct |values| only)
    assert len(np.unique(np.abs(np.asarray(uc["w"])))) == 1


def test_multiworker_ef_compression_converges():
    """DistributedOptimizer(compression="onebit") inside the real dp=8
    shard_mapped step: per-worker EF + allreduce of the dequantized
    gradients drives the quadratic down."""
    from byteps_tpu.parallel.mesh import build_mesh
    from byteps_tpu.training import make_data_parallel_step, shard_batch

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU harness")
    mesh = build_mesh(devices=jax.devices()[:8])
    rng = np.random.default_rng(3)
    w_true = rng.standard_normal((8, 4)).astype(np.float32)
    X = rng.standard_normal((64, 8)).astype(np.float32)
    batchd = {"x": X, "y": X @ w_true}
    step = make_data_parallel_step(_loss_fn, optax.sgd(0.05), mesh,
                                   compression="onebit")
    state = step.init_state({"w": jnp.zeros((8, 4))})
    batch = shard_batch(batchd, mesh)
    state, m0 = step(state, batch)
    for _ in range(60):
        state, m = step(state, batch)
        # wait each step: 60 queued 8-device programs can starve XLA:CPU's
        # in-process collective rendezvous on a loaded host (SIGABRT)
        jax.block_until_ready(m)
    assert float(m["loss"]) < float(m0["loss"]) / 4
