"""Model zoo shape/numerics smoke tests (tiny shapes, CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import ResNet18, ResNet50, VGG11, Transformer, TransformerConfig


@pytest.mark.slow  # ~14s: full ResNet-50 compile; resnet_train_mode_updates_stats keeps fast resnet coverage
def test_resnet50_forward_shapes():
    model = ResNet50(num_classes=10, num_filters=8)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert "params" in variables and "batch_stats" in variables
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_resnet_train_mode_updates_stats():
    model = ResNet18(num_classes=4, num_filters=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    # jitted: op-by-op eager init/apply is ~1000 tiny compiles (~15 s,
    # over the tier-1 per-test budget under host load)
    variables = jax.jit(
        lambda k, x: model.init(k, x, train=False))(jax.random.PRNGKey(0), x)
    logits, new_state = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables, x)
    assert logits.shape == (2, 4)
    old = jax.tree_util.tree_leaves(variables["batch_stats"])
    new = jax.tree_util.tree_leaves(new_state["batch_stats"])
    assert any(not np.allclose(a, b) for a, b in zip(old, new))


@pytest.mark.slow  # ~18s: 11-layer VGG compile; resnet_train_mode_updates_stats keeps fast conv coverage
def test_vgg_forward():
    model = VGG11(num_classes=10, channels=(8, 8, 16, 16, 16))
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)


def test_transformer_forward_local():
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=16, dtype=jnp.float32,
    )
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 16, 64)


def test_transformer_causality():
    """Changing a future token must not change past logits."""
    cfg = TransformerConfig(
        vocab_size=32, num_layers=1, num_heads=2, d_model=16, d_ff=32,
        max_seq_len=8, dtype=jnp.float32,
    )
    model = Transformer(cfg)
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, 7].set(9)
    variables = model.init(jax.random.PRNGKey(0), t1)
    l1 = model.apply(variables, t1)
    l2 = model.apply(variables, t2)
    np.testing.assert_allclose(l1[0, :7], l2[0, :7], atol=1e-5)
    assert not np.allclose(l1[0, 7], l2[0, 7])


def test_transformer_flash_sp_composes():
    """attn_impl='flash' with an sp mesh axis routes through
    ring_flash_attention and matches the local-attention model exactly."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("dp", "sp"))
    kwargs = dict(vocab_size=64, num_layers=2, num_heads=4, d_model=32,
                  d_ff=64, max_seq_len=32, dtype=jnp.float32)
    cfg_flash = TransformerConfig(attn_impl="flash", mesh=mesh, **kwargs)
    cfg_local = TransformerConfig(attn_impl="local", **kwargs)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    variables = Transformer(cfg_local).init(jax.random.PRNGKey(0), tokens)
    expected = Transformer(cfg_local).apply(variables, tokens)
    with mesh:
        got = jax.jit(
            lambda v, t: Transformer(cfg_flash).apply(v, t)
        )(variables, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.slow  # ~65s on CPU: full MobileNetV2 compile + train step
def test_mobilenet_v2_forward_and_train_step():
    from byteps_tpu.models import MobileNetV2
    from byteps_tpu.training import (
        classification_loss_fn, make_data_parallel_step, shard_batch)
    from jax.sharding import Mesh
    import optax

    model = MobileNetV2(num_classes=10, width_mult=0.25, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(1), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    n = 2 * len(jax.devices())
    step = make_data_parallel_step(
        classification_loss_fn(model), optax.sgd(0.05), mesh)
    state = step.init_state(
        variables["params"],
        model_state={"batch_stats": variables["batch_stats"]})
    batch = shard_batch(
        {"image": jax.random.normal(jax.random.PRNGKey(2), (n, 32, 32, 3)),
         "label": jax.random.randint(jax.random.PRNGKey(3), (n,), 0, 10)},
        mesh)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow  # ~9s; resnet18/transformer forwards keep fast classic-model coverage
def test_lenet_alexnet_forward():
    from byteps_tpu.models import AlexNet, LeNet

    lenet = LeNet(num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 28, 28, 1))
    v = lenet.init(jax.random.PRNGKey(1), x)
    assert lenet.apply(v, x).shape == (2, 10)

    alex = AlexNet(num_classes=100, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64, 3))
    v = alex.init({"params": jax.random.PRNGKey(1),
                   "dropout": jax.random.PRNGKey(2)}, x)
    out = alex.apply(v, x, train=False)
    assert out.shape == (2, 100)
