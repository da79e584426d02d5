"""Plain reference for Mistral-family dense configurations: the full
forward pass of one sequence in float32 ``jax.numpy``, written from the
published architecture (Jiang et al. 2023; HF ``modeling_mistral.py``):
RMSNorm, rotary positions on the half-split pairs, grouped-query
attention (query head ``h`` reads K/V head ``h // (H / KV)``), SwiGLU,
untied head — no kernel, no cache, no paging, no chunking.

It reads the program's parameter tree (the weights under test) layer by
layer, upcasting that layer's bf16 weights to float32, and runs under
``default_matmul_precision("highest")`` (a TPU's float32 matmul is
otherwise reduced).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """``x [T, H, D]`` rotated by position: pair ``(x[i], x[i + D/2])``
    turns by ``pos / theta^(2i/D)``."""
    T, _, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, p, eps, theta):
    """One pre-norm block on ``x [T, d]``; ``p`` arrives in the stored
    type and is upcast here."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    T = x.shape[0]
    a = p["attn"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    q = rope(jnp.einsum("td,dhk->thk", h, a["q"]["kernel"]), theta)
    k = rope(jnp.einsum("td,dhk->thk", h, a["k"]["kernel"]), theta)
    v = jnp.einsum("td,dhk->thk", h, a["v"]["kernel"])
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("qhk,hkd->qd", o, a["o"]["kernel"])
    h = rms_norm(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    h = jax.nn.silu(h @ m["gate"]["kernel"]) * (h @ m["up"]["kernel"])
    return x + h @ m["down"]["kernel"]


def head(x, ln_f, lm_head, eps):
    return rms_norm(x, ln_f["scale"].astype(jnp.float32), eps) @ (
        lm_head["kernel"].astype(jnp.float32))


_block = jax.jit(block, static_argnums=(2, 3))
_head = jax.jit(head, static_argnums=3)


def logits(params, tokens, cfg: dict):
    """``[T, vocab]`` float32 logits of the sequence ``tokens [T]``."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = _block(x, params[f"block_{i}"], eps, theta)
        return _head(x, params["ln_f"], params["lm_head"], eps)
