"""Plain reference for Ouro configurations as
``configs/ouro-2.6b-l7.json`` states one: the looped forward pass, the
readout of every pass, the exit distribution and the loss in float32
``jax.numpy``, written from the family's published description (the
Ouro paper, "Scaling Latent Reasoning via Looped Language Models",
2025) and its ``config.json`` keys — no kernel, no scan, no
recomputation.  Attention runs in blocks of query rows, the head's
cross-entropy in blocks of rows, one sequence at a time, so that 4096
positions fit beside the train state.

A layer on ``x [T, d]``, each ``N`` an RMS norm with a scale of its own
(sandwich norms: on each sublayer's input and on its output):

    h   = x + N2(attn(N1(x)))
    out = h + N4(ffn(N3(h)))
    attn  q, k, v = n W_q, n W_k, n W_v [T, H, D] (as many key-value
          heads as query heads); rotate-half RoPE over the whole head
          at rope_theta; softmax(q k^T / sqrt(D)) under the causal mask;
          out = concat(a) W_o
    ffn   (silu(n W_gate) * n W_up) W_down

The loop, with ``R = total_ut_steps`` passes over the same L layers:

    s_0 = Embed(tokens);   s_t = NormF(Stack(s_{t-1})),  t = 1 .. R
    lam_t = sigmoid(s_t . w_g + b_g)
    p_t = lam_t prod_{j<t} (1 - lam_j)   (t < R);   p_R = prod_{j<R} (1 - lam_j)
    L = mean over targets [ sum_t p_t CE(s_t W_head, y) - beta H(p) ]

``beta`` is the configuration's ``assumed.entropy_weight``.  Departures
from the published description, each under ``assumed`` in the file: the
gate and the exit distribution in float32; the entropy term averaged
over the same targets as the cross-entropy (the last position, which has
no target, counts in neither).  The exit distribution is computed here
as the products it is written as; the program computes it in logs.  It
reads the program's parameter tree (the weights under test), nothing
else of the program.  On a TPU a float32 matmul runs in reduced
precision unless asked otherwise, so everything runs under
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

QUERY_BLOCK, ROW_BLOCK = 512, 2048


class Sizes(NamedTuple):
    eps: float
    theta: float
    layers: int         # L, the layers of the shared stack
    steps: int          # R, the passes over it
    beta: float         # the entropy's weight


def sizes(cfg: dict) -> Sizes:
    return Sizes(cfg["rms_norm_eps"], float(cfg["rope_theta"]),
                 cfg["num_hidden_layers"], cfg["total_ut_steps"],
                 float(cfg["assumed"]["entropy_weight"]))


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rope(x, theta: float):
    """Rotate-half RoPE over the whole head: ``x [T, heads, D]``,
    position ``t`` at angle ``t / theta^(2i / D)`` for the channel pair
    ``(i, i + D/2)``."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + half * sin


def attention(n, a, c: Sizes):
    """The attention sublayer's output on ``n [T, d]``: RoPE MHA, causal,
    in blocks of query rows (all heads of a block at once)."""
    q = rope(jnp.einsum("td,dhk->thk", n, a["q"]["kernel"]), c.theta)
    k = rope(jnp.einsum("td,dhk->thk", n, a["k"]["kernel"]), c.theta)
    v = jnp.einsum("td,dhk->thk", n, a["v"]["kernel"])
    T, H, D = q.shape
    rows = []
    for q0 in range(0, T, QUERY_BLOCK):
        q1 = min(T, q0 + QUERY_BLOCK)
        keep = jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :]
        s = jnp.einsum("qhk,shk->hqs", q[q0:q1], k[:q1]) / math.sqrt(D)
        s = jnp.where(keep[None], s, -jnp.inf)
        rows.append(jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1),
                               v[:q1]))
    return jnp.einsum("qhk,hkd->qd", jnp.concatenate(rows, axis=0),
                      a["o"]["kernel"])


def ffn(n, m):
    return (jax.nn.silu(n @ m["gate"]["kernel"])
            * (n @ m["up"]["kernel"])) @ m["down"]["kernel"]


def layer(x, p, c: Sizes):
    """One layer on ``x [T, d]``, sandwich-normed."""
    h = x + rms_norm(attention(rms_norm(x, p["ln1"], c.eps), p["attn"], c),
                     p["ln1_out"], c.eps)
    return h + rms_norm(ffn(rms_norm(h, p["ln2"], c.eps), p["mlp"]),
                        p["ln2_out"], c.eps)


def exit_probs(gate_logits):
    """``p [R, ...]`` from the gates' logits ``[R, ...]``, as the
    products are written: the last pass takes the remaining mass."""
    lam = jax.nn.sigmoid(gate_logits)
    reach, out = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        out.append(lam[t] * reach)
        reach = reach * (1.0 - lam[t])
    return jnp.stack(out + [reach])


def ce_rows(h, head, targets):
    """Each row's cross-entropy of ``h @ head`` against ``targets``, in
    blocks of rows."""
    out = []
    for r0 in range(0, h.shape[0], ROW_BLOCK):
        logp = jax.nn.log_softmax(h[r0:r0 + ROW_BLOCK] @ head, axis=-1)
        out.append(-jnp.take_along_axis(
            logp, targets[r0:r0 + ROW_BLOCK, None], axis=1)[:, 0])
    return jnp.concatenate(out)


def readout(s, p):
    """``(s, gate logit [T])`` of one pass: its normed state and the
    exit gate on it."""
    g = p["exit_gate"]
    return s, (s @ g["kernel"])[:, 0] + g["bias"][0]


def loss_terms(ce, probs, beta):
    """Each target's ``sum_t p_t ce_t - beta H(p)`` from ``ce, probs
    [R, n]``."""
    entropy = -jnp.sum(jax.scipy.special.xlogy(probs, probs), axis=0)
    return jnp.sum(probs * ce, axis=0) - beta * entropy


class BlockState(NamedTuple):
    """One layer application, as the reference computed it."""

    step: int           # the pass, from 0
    layer: int
    params: dict
    x: jax.Array        # the state the layer read
    y: jax.Array        # the state it wrote


class PassState(NamedTuple):
    """The readout of one pass."""

    step: int
    s: jax.Array        # the normed state, the next pass's input
    gate: jax.Array     # the exit gate's logit at every position


# compiled once, not once a layer or a pass
_layer = jax.jit(layer, static_argnums=2)
_norm = jax.jit(rms_norm, static_argnums=2)
_readout = jax.jit(readout)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def by_sequence(fn, x, *args):
    """``fn(x, *args)`` for ``x [T, d]``; for ``x [B, T, d]`` one
    sequence at a time, the results stacked."""
    if x.ndim == 2:
        return fn(x, *args)
    return jax.tree_util.tree_map(lambda *r: jnp.stack(r),
                                  *[fn(row, *args) for row in x])


def states(params, tokens, c: Sizes):
    """Every layer application of the forward pass on ``tokens [T]`` (or
    ``[B, T]``, one sequence at a time), in order, each a
    ``BlockState``, and after each pass's last layer its ``PassState``.
    A generator: one application's states are live at a time."""
    x = _f32(params["embed"]["embedding"])[tokens]
    blocks = [_f32(params[f"block_{i}"]) for i in range(c.layers)]
    top = {"ln_f": _f32(params["ln_f"]),
           "exit_gate": _f32(params["exit_gate"])}
    for t in range(c.steps):
        for i, p in enumerate(blocks):
            y = by_sequence(_layer, x, p, c)
            yield BlockState(t, i, p, x, y)
            x = y
        s, gate = by_sequence(
            _readout, by_sequence(_norm, x, top["ln_f"], c.eps), top)
        yield PassState(t, s, gate)
        x = s


def forward(params, tokens, c: Sizes):
    """``(s [R, T, d], gate logits [R, T])`` of one sequence ``tokens
    [T]``, traceable (small sizes: the tests differentiate it)."""
    x = _f32(params["embed"]["embedding"])[tokens]
    gate = {"exit_gate": _f32(params["exit_gate"])}
    out_s, out_g = [], []
    for _ in range(c.steps):
        for i in range(c.layers):
            x = layer(x, _f32(params[f"block_{i}"]), c)
        s, g = readout(rms_norm(x, _f32(params["ln_f"]), c.eps), gate)
        out_s.append(s)
        out_g.append(g)
        x = s
    return jnp.stack(out_s), jnp.stack(out_g)


def head(params):
    return _f32(params["lm_head"]["kernel"])


def logits(params, tokens, c: Sizes):
    """``[R, T, vocabulary]`` of every pass for one sequence (small
    sizes only: the tests compare them)."""
    s, _ = forward(params, tokens, c)
    return s @ head(params)


def sequence_loss_sum(params, tokens, c: Sizes):
    """Sum over the ``T - 1`` targets of one sequence ``tokens [T]`` of
    the exit-weighted cross-entropy less beta times the entropy;
    traceable (small sizes)."""
    s, g = forward(params, tokens, c)
    ce = jnp.stack([ce_rows(s_t[:-1], head(params), tokens[1:])
                    for s_t in s])
    return jnp.sum(loss_terms(ce, exit_probs(g[:, :-1]), c.beta))


_ce_rows = jax.jit(ce_rows)


def pass_readouts(params, tokens, c: Sizes):
    """``(ce [R, T - 1], p [R, T - 1])`` of one sequence ``tokens [T]``
    at the timed sizes: each pass's cross-entropy against the next
    token and the exit distribution, the passes' states one at a
    time."""
    ce, gates = [], []
    for st in states(params, tokens, c):
        if isinstance(st, PassState):
            ce.append(_ce_rows(st.s[:-1], head(params), tokens[1:]))
            gates.append(st.gate[:-1])
    return jnp.stack(ce), exit_probs(jnp.stack(gates))


def loss(params, tokens, cfg: dict) -> float:
    """The loss over ``tokens [B, T]`` — what the train step reports for
    its first batch on the same weights."""
    B, T = tokens.shape
    c = sizes(cfg)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            ce, probs = pass_readouts(params, tokens[b], c)
            total += float(jnp.sum(loss_terms(ce, probs, c.beta)))
    return total / (B * (T - 1))
