"""Weights from the seed, on the device, in one jitted call.

Every run is a new process and pays set-up in full, so parameters are
never initialised leaf by leaf or on the host: one program takes the
seed and returns the whole tree in the type it is used in.  The rule is
the published initialiser of both families the benchmark runs
(``initializer_range`` 0.02): matrices and embeddings ~ N(0, 0.02),
biases 0, norm scales 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def param_shapes(model, seq_len: int = 8):
    """The parameter tree's shapes without building it
    (``model.init`` in float32 would not fit for the larger model)."""
    import flax.linen as nn

    tokens = jax.ShapeDtypeStruct((1, seq_len), jnp.int32)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    return nn.meta.unbox(tree["params"])


def make_tree(shapes, key, dtype, std: float = 0.02):
    """Traceable: the parameter tree for ``shapes`` from ``key``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = getattr(path[-1], "key", str(path[-1]))
        if name == "bias":
            out.append(jnp.zeros(leaf.shape, dtype))
        elif name == "scale":
            out.append(jnp.ones(leaf.shape, dtype))
        else:
            out.append(std * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def make_params(shapes, seed: int, dtype, sharding=None):
    fn = jax.jit(lambda key: make_tree(shapes, key, dtype),
                 out_shardings=sharding)
    return fn(jax.random.PRNGKey(seed))
