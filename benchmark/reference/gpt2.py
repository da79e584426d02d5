"""Plain reference for GPT-2-family configurations: the forward pass and
the next-token loss in float32 ``jax.numpy``, written from the published
architecture (Radford et al. 2019; HF ``modeling_gpt2.py``) — no kernel,
no cache, no fusion, one sequence at a time.

It reads the program's parameter tree (the weights under test), nothing
else of the program.  On a TPU a float32 matmul runs in reduced
precision unless asked otherwise, so everything runs under
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, eps):
    """One pre-norm block on ``x [T, d]``."""
    T = x.shape[0]
    a = p["attn"]
    h = layer_norm(x, p["ln1"], eps)
    q = jnp.einsum("td,dhk->thk", h, a["q"]["kernel"]) + a["q"]["bias"]
    k = jnp.einsum("td,dhk->thk", h, a["k"]["kernel"]) + a["k"]["bias"]
    v = jnp.einsum("td,dhk->thk", h, a["v"]["kernel"]) + a["v"]["bias"]
    s = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("qhk,hkd->qd", o, a["o"]["kernel"]) + a["o"]["bias"]
    h = layer_norm(x, p["ln2"], eps)
    m = p["mlp"]
    h = gelu_new(h @ m["up"]["kernel"] + m["up"]["bias"])
    return x + h @ m["down"]["kernel"] + m["down"]["bias"]


def head_loss_sum(x, ln_f, embedding, tokens, eps):
    x = layer_norm(x, ln_f, eps)
    logits = x @ embedding.T                            # tied head
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=1))


_block = jax.jit(block, static_argnums=2)
_head_loss_sum = jax.jit(head_loss_sum, static_argnums=4)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def sequence_loss_sum(params, tokens, n_layer, eps):
    """Sum over positions of the next-token cross-entropy of one
    sequence ``tokens [T]`` (``T - 1`` targets)."""
    emb = _f32(params["embed"]["embedding"])
    x = emb[tokens] + _f32(params["pos"]["embedding"])[:tokens.shape[0]]
    for i in range(n_layer):
        x = _block(x, _f32(params[f"block_{i}"]), eps)
    return _head_loss_sum(x, _f32(params["ln_f"]), emb, tokens, eps)


def loss(params, tokens, cfg: dict) -> float:
    """Mean next-token cross-entropy over ``tokens [B, T]`` — what the
    train step reports for its first batch on the same weights."""
    B, T = tokens.shape
    with jax.default_matmul_precision("highest"):
        sums = [sequence_loss_sum(params, tokens[b], cfg["n_layer"],
                                  cfg["layer_norm_epsilon"])
                for b in range(B)]
    return float(sum(float(s) for s in sums)) / (B * (T - 1))
