"""Pipeline (pp) parallelism tests on the CPU mesh (the expert layer's
are in test_expert_layer.py).

Contract: a 4-stage GPipe pipeline must equal sequential application of
the 4 stages (forward AND gradients).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.parallel.collectives import shard_map
from byteps_tpu.parallel.pipeline import pipeline_apply, pipeline_loss


# ---------------------------------------------------------------- pipeline

N_STAGES, N_MICRO, MB, D = 4, 8, 2, 16


def stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stacked_params(key):
    ks = jax.random.split(key, N_STAGES)
    return {
        "w": jnp.stack(
            [jax.random.normal(k, (D, D)) * 0.5 for k in ks]
        ),
        "b": jnp.stack([jnp.full((D,), 0.01 * i) for i in range(N_STAGES)]),
    }


def _sequential(params, micro):
    x = micro
    for s in range(N_STAGES):
        x = stage_fn({"w": params["w"][s], "b": params["b"][s]}, x)
    return x


def _pp_mesh():
    return Mesh(np.array(jax.devices()[:N_STAGES]), ("pp",))


def test_pipeline_forward_matches_sequential():
    params = _stacked_params(jax.random.PRNGKey(0))
    micros = jax.random.normal(jax.random.PRNGKey(1), (N_MICRO, MB, D))
    expected = jax.vmap(lambda m: _sequential(params, m))(micros)

    mesh = _pp_mesh()

    def run(p, m):
        local = jax.tree_util.tree_map(lambda a: a[0], p)  # my stage
        return pipeline_apply(stage_fn, local, m, axis_name="pp")

    fn = jax.jit(shard_map(
        run, mesh, in_specs=(P("pp"), P()), out_specs=P("pp"),
    ))
    # out_specs P("pp") concatenates per-stage outputs along axis 0
    out = fn(params, micros).reshape(N_STAGES, N_MICRO, MB, D)
    np.testing.assert_allclose(
        np.asarray(out[-1]), np.asarray(expected), atol=1e-5
    )


@pytest.mark.slow  # ~19s: pipeline bwd compile; forward/remat/ep parity stay fast
def test_pipeline_grads_match_sequential():
    params = _stacked_params(jax.random.PRNGKey(2))
    micros = jax.random.normal(jax.random.PRNGKey(3), (N_MICRO, MB, D))
    targets = jax.random.normal(jax.random.PRNGKey(4), (N_MICRO, MB, D))

    def seq_loss(p):
        outs = jax.vmap(lambda m: _sequential(p, m))(micros)
        return jnp.mean(jax.vmap(
            lambda o, t: jnp.mean((o - t) ** 2))(outs, targets))

    g_seq = jax.grad(seq_loss)(params)

    mesh = _pp_mesh()

    def pp_loss(p, m, t):
        local = jax.tree_util.tree_map(lambda a: a[0], p)
        loss = pipeline_loss(
            stage_fn,
            lambda o, tt: jnp.mean((o - tt) ** 2),
            local, m, t, axis_name="pp",
        )
        return loss

    def outer(p):
        fn = shard_map(
            pp_loss, mesh, in_specs=(P("pp"), P(), P()), out_specs=P(),
        )
        return fn(p, micros, targets)

    loss_pp = jax.jit(outer)(params)
    np.testing.assert_allclose(float(loss_pp), float(seq_loss(params)),
                               atol=1e-5)
    g_pp = jax.grad(outer)(params)
    for k in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(g_pp[k]), np.asarray(g_seq[k]), atol=1e-4, rtol=1e-4
        )


def test_pipeline_remat_matches():
    params = _stacked_params(jax.random.PRNGKey(5))
    micros = jax.random.normal(jax.random.PRNGKey(6), (N_MICRO, MB, D))
    mesh = _pp_mesh()

    def run(p, m, remat):
        local = jax.tree_util.tree_map(lambda a: a[0], p)
        return pipeline_apply(stage_fn, local, m, axis_name="pp", remat=remat)

    f1 = jax.jit(shard_map(lambda p, m: run(p, m, False), mesh,
                           in_specs=(P("pp"), P()), out_specs=P("pp")))
    f2 = jax.jit(shard_map(lambda p, m: run(p, m, True), mesh,
                           in_specs=(P("pp"), P()), out_specs=P("pp")))
    np.testing.assert_allclose(np.asarray(f1(params, micros)),
                               np.asarray(f2(params, micros)), atol=1e-5)
