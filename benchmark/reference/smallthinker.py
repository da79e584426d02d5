"""Plain reference for SmallThinker-family configurations as
``configs/smallthinker-21b-l4-ep4.json`` states one: the forward pass and
the loss in float32 ``jax.numpy``, written from the family's report
(arXiv:2507.20984) and its config keys — no kernel, no sort, no buffer,
no recomputation; attention in blocks of query rows and of one key-value
group's heads, the experts one at a time over ALL tokens, so that 16 384
positions fit beside the train state.

For block ``l`` with input ``x [T, d]``, ``n1 = norm_1(x)``:

    attention  q = n1 W_q [T, H, D];  k = n1 W_k, v = n1 W_v [T, KV, D];
               no bias, no q/k norm.  rope_layout[l] == 1: half-split
               RoPE on q and k (channel i pairs with i + D/2, angle
               t * theta^(-2i/D)); 0: nothing.  Query head h reads
               key-value head h // (H / KV).  Scores q k^T / sqrt(D)
               under the causal mask, and where sliding_window_layout[l]
               == 1 also i - j < window.  x' = x + concat(softmax v) W_o
    router     logits = n1 W_r in float32 — the block's normalised
               ATTENTION input; the k largest logits are chosen, weights
               = softmax over the chosen.  No bias, no scale.
    experts    n2 = norm_2(x');  E_e(n2) = (relu(n2 W_gate,e) * (n2
               W_up,e)) W_down,e;  y = x' + sum_chosen w_e E_e(n2)
    loss       final norm, untied head, mean next-token cross-entropy

Departures from the published config, each under ``assumed`` in the
configuration's file: the ReLU gate and the router's input (the config
has no key for either; the report says "sparse ReGLU" and "router placed
before attention"), and the window counting the query's own position.

It is given the same share as the program: the experts held here
(``moe_num_primary_experts`` of ``reduced_from``'s, from expert 0), the
first ``num_hidden_layers`` entries of both layouts, and the table as
built.  It reads the program's parameter tree (the weights under test),
nothing else of the program.  On a TPU a float32 matmul runs in reduced
precision unless asked otherwise, so everything runs under
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

QUERY_BLOCK, ROW_BLOCK = 1024, 2048


class Sizes(NamedTuple):
    eps: float
    theta: float
    window: int
    windowed: Tuple[bool, ...]   # per layer: the sliding window applies
    rotated: Tuple[bool, ...]    # per layer: RoPE applies
    top_k: int
    first: int          # first expert held
    held: int           # experts held


def sizes(cfg: dict, first: int = 0) -> Sizes:
    n = cfg["num_hidden_layers"]
    return Sizes(cfg["rms_norm_eps"], float(cfg["rope_theta"]),
                 cfg["sliding_window_size"],
                 tuple(bool(w) for w in cfg["sliding_window_layout"][:n]),
                 tuple(bool(r) for r in cfg["rope_layout"][:n]),
                 cfg["moe_num_active_primary_experts"], first,
                 cfg["moe_num_primary_experts"])


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rope_halves(x, theta):
    """Rotate the channel pairs (i, i + d/2) of ``x [T, heads, d]`` by
    ``t * theta^(-2i/d)``."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(n1, a, rotated: bool, window: Optional[int], theta: float):
    """The attention sublayer's output (before the residual) on the
    normalised input ``n1 [T, d]``: grouped-query heads, causal, the
    last ``window`` positions (the query's own included) where there is
    a window."""
    q = jnp.einsum("td,dhk->thk", n1, a["q"]["kernel"])
    k = jnp.einsum("td,dhk->thk", n1, a["k"]["kernel"])
    v = jnp.einsum("td,dhk->thk", n1, a["v"]["kernel"])
    if rotated:
        q, k = rope_halves(q, theta), rope_halves(k, theta)
    T, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    rows = []
    for q0 in range(0, T, QUERY_BLOCK):
        q1 = min(T, q0 + QUERY_BLOCK)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        i = jnp.arange(q0, q1)[:, None]
        j = jnp.arange(k0, q1)[None, :]
        keep = i >= j
        if window is not None:
            keep = keep & (i - j < window)
        heads = []
        for g in range(KV):                  # one key-value head's group
            s = jnp.einsum("qhk,sk->hqs", q[q0:q1, g * G:(g + 1) * G],
                           k[k0:q1, g])
            s = jnp.where(keep[None], s / math.sqrt(D), -jnp.inf)
            heads.append(jnp.einsum("hqs,sk->qhk",
                                    jax.nn.softmax(s, axis=-1), v[k0:q1, g]))
        rows.append(jnp.concatenate(heads, axis=1))
    return jnp.einsum("qhk,hkd->qd", jnp.concatenate(rows, axis=0),
                      a["o"]["kernel"])


def reglu(x, gate, up, down):
    return (jax.nn.relu(x @ gate) * (x @ up)) @ down


def router(n1, p, c: Sizes):
    """``(idx [T, k], weights [T, k])`` from the block's normalised
    attention input: the top k of the logits, softmax over the chosen."""
    logits = n1 @ p["kernel"]
    picked, idx = jax.lax.top_k(logits, c.top_k)
    return idx, jax.nn.softmax(picked, axis=-1)


def routed(n2, m, c: Sizes, choice):
    """The held experts' part on the feed-forward input ``n2``: expert
    ``first + e`` is ``experts[e]``; ``choice = (idx, weights)`` is the
    router's (it read another input, so it is always handed in)."""
    idx, w = choice

    def add(y, expert):
        i, gate, up, down = expert
        w_i = jnp.sum(jnp.where(idx == c.first + i, w, 0.0), axis=-1)
        return y + w_i[:, None] * reglu(n2, gate, up, down), None

    e = m["experts"]
    return jax.lax.scan(add, jnp.zeros_like(n2), (
        jnp.arange(c.held), e["gate"], e["up"], e["down"]))[0]


class BlockState(NamedTuple):
    """One block of the forward pass, as the reference computed it."""

    params: dict
    x: jax.Array        # the state the block read
    y: jax.Array        # the state it wrote
    n1: jax.Array       # its normalised attention (and router) input
    attn: jax.Array     # the attention sublayer's output, no residual
    n2: jax.Array       # its normalised feed-forward input


def block_of_kind(x, p, c: Sizes, rotated: bool, window: Optional[int]):
    """``(y, n1, attn, n2)`` of one block on ``x [T, d]``, its kind
    given: RoPE or none, a window or the whole causal prefix."""
    n1 = rms_norm(x, p["ln1"], c.eps)
    choice = router(n1, p["moe"]["router"], c)
    a = attention(n1, p["attn"], rotated, window, c.theta)
    x = x + a
    n2 = rms_norm(x, p["ln2"], c.eps)
    return x + routed(n2, p["moe"], c, choice), n1, a, n2


def kind(c: Sizes, layer: int):
    """``(rotated, window)`` of layer ``layer``."""
    return c.rotated[layer], c.window if c.windowed[layer] else None


def block(x, p, c: Sizes, layer: int):
    return block_of_kind(x, p, c, *kind(c, layer))[0]


def ce_sum(h, head, targets):
    """Sum over ``h``'s rows of the cross-entropy of ``h @ head`` against
    ``targets``, in blocks of rows."""
    total = jnp.zeros((), jnp.float32)
    for r0 in range(0, h.shape[0], ROW_BLOCK):
        logp = jax.nn.log_softmax(h[r0:r0 + ROW_BLOCK] @ head, axis=-1)
        total = total - jnp.sum(jnp.take_along_axis(
            logp, targets[r0:r0 + ROW_BLOCK, None], axis=1))
    return total


# compiled once a KIND of layer, not once a layer
_block = jax.jit(block_of_kind, static_argnums=(2, 3, 4))
_ce_sum = jax.jit(ce_sum)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def block_states(params, tokens, n_layer, c: Sizes):
    """Every block of the forward pass on one sequence ``tokens [T]``, in
    order, as a ``BlockState``; then the state the head reads.  A
    generator: one block's states are live at a time."""
    x = _f32(params["embed"]["embedding"])[tokens]
    for i in range(n_layer):
        p = _f32(params[f"block_{i}"])
        y, n1, a, n2 = _block(x, p, c, *kind(c, i))
        yield BlockState(p, x, y, n1, a, n2)
        x = y
    yield jax.jit(rms_norm, static_argnums=2)(x, _f32(params["ln_f"]), c.eps)


def logits(params, tokens, n_layer, c: Sizes):
    """``[T, vocabulary rows]`` for one sequence (small sizes only: the
    tests compare them)."""
    *_, h = block_states(params, tokens, n_layer, c)
    return h @ _f32(params["lm_head"]["kernel"])


def sequence_loss_sum(params, tokens, n_layer, c: Sizes):
    """Sum over the ``T - 1`` targets of one sequence ``tokens [T]`` of
    the next-token cross-entropy."""
    *_, h = block_states(params, tokens, n_layer, c)
    return _ce_sum(h[:-1], _f32(params["lm_head"]["kernel"]), tokens[1:])


def loss(params, tokens, cfg: dict) -> float:
    """Mean next-token cross-entropy over ``tokens [B, T]`` — what the
    train step reports for its first batch on the same weights."""
    B, T = tokens.shape
    c = sizes(cfg)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            total += float(sequence_loss_sum(
                params, tokens[b], cfg["num_hidden_layers"], c))
    return total / (B * (T - 1))
