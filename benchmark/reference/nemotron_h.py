"""Plain reference for Nemotron-H-family configurations as
``configs/nemotron3-nano-30b-l9-ep16.json`` states one: the forward pass
and the loss in float32 ``jax.numpy``, written from the family's report
(arXiv:2504.03624), the Mamba-2 paper (arXiv:2405.21060) and the config's
keys — no kernel, no chunk, no sort, no buffer, no recomputation.  **The
Mamba-2 layer runs by its recurrence**, one position a step of a
``lax.scan`` over a state ``[H, P, N]``, so that it shares nothing with
the chunked algorithm it judges; attention runs in blocks of query rows
and of one key-value group's heads, the experts one at a time over ALL
tokens, so that 8192 positions fit beside the train state.

Block ``l`` with input ``x [T, d]`` is ``x + sublayer_l(n)``, ``n =
RMSNorm_l(x)``, the sublayer's kind letter ``l`` of
``hybrid_override_pattern``:

    M  mixer   [z | xBC | dt] = n W_in, widths H P | H P + 2 G N | H
               xBC = silu(conv(xBC) + b): depthwise, causal, K taps,
               conv(u)[t, c] = sum_k w[k, c] u[t - (K - 1) + k, c]
               [X | B | C] = xBC: X [T, H, P], B, C [T, G, N]; head h
               reads group h // (H / G)
               dt = softplus(dt + dt_bias) (no clamp);  A = -exp(A_log)
               S_t = exp(dt_t A) S_{t-1} + dt_t X_t (x) B_t,  S_0 = 0
               Y_t = S_t C_t + D X_t
               Y = Y * silu(z), then RMS over each of the G groups of
               H P / G channels, times a scale;  out = Y W_out
    *  attn    q = n W_q [T, H_q, D]; k, v = n W_k, n W_v [T, KV, D]; no
               bias, NO position encoding; query head h reads key-value
               head h // (H_q / KV); scores / sqrt(D) under the causal
               mask;  out = concat(softmax v) W_o
    E  experts logits = n W_r in float32; s = sigmoid(logits); the k
               largest of s + bias are chosen; weights = s_chosen /
               (sum s_chosen + 1e-20) x scale.  Expert e: relu(n
               W_up,e)^2 W_down,e; the shared expert the same form on
               every token;  out = sum_chosen w_e E_e(n) + shared(n)
    loss       final norm, untied head, mean next-token cross-entropy

Departures from the published config are under ``assumed`` in the
configuration's file.  It is given the same share as the program: the
experts held here (``n_routed_experts`` of ``reduced_from``'s, from
expert 0), the first ``num_hidden_layers`` letters of the pattern, and
the table as built.  It reads the program's parameter tree (the weights
under test), nothing else of the program.  On a TPU a float32 matmul
runs in reduced precision unless asked otherwise, so everything runs
under ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

QUERY_BLOCK, ROW_BLOCK = 512, 2048
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


class Sizes(NamedTuple):
    eps: float
    kinds: Tuple[str, ...]   # per layer: mamba | moe | attn
    heads: int          # mixer heads H
    head_dim: int       # P
    groups: int         # G
    state: int          # N
    top_k: int
    scale: float
    first: int          # first expert held
    held: int           # experts held


def sizes(cfg: dict, first: int = 0) -> Sizes:
    n = cfg["num_hidden_layers"]
    return Sizes(cfg["layer_norm_epsilon"],
                 tuple(KINDS[c] for c in cfg["hybrid_override_pattern"][:n]),
                 cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                 cfg["n_groups"], cfg["ssm_state_size"],
                 cfg["num_experts_per_tok"],
                 float(cfg["routed_scaling_factor"]), first,
                 cfg["n_routed_experts"])


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


# ------------------------------------------------------------ the mixer


class MixerParts(NamedTuple):
    """What the mixer's scan reads, as the reference made it."""

    x: jax.Array        # [T, H, P]
    dt: jax.Array       # [T, H], after its softplus
    a: jax.Array        # [H], negative
    b: jax.Array        # [T, G, N]
    c: jax.Array        # [T, G, N]
    d: jax.Array        # [H]
    z: jax.Array        # [T, H P], the gate


def mixer_parts(n, m, c: Sizes) -> MixerParts:
    """In-projection, convolution and activations of the mixer on the
    normalised input ``n [T, d]``."""
    H, P, G, N = c.heads, c.head_dim, c.groups, c.state
    d_in, gn = H * P, G * N
    proj = n @ m["in_proj"]["kernel"]
    z, u, dt = proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * gn], proj[
        :, 2 * d_in + 2 * gn:]
    w = m["conv"]["kernel"]                        # [K, channels]
    K, T = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    u = jax.nn.silu(sum(padded[k:k + T] * w[k] for k in range(K))
                    + m["conv"]["bias"])
    return MixerParts(
        u[:, :d_in].reshape(T, H, P),
        jax.nn.softplus(dt + m["ssd"]["dt_bias"]),
        -jnp.exp(m["ssd"]["A_log"]),
        u[:, d_in:d_in + gn].reshape(T, G, N),
        u[:, d_in + gn:].reshape(T, G, N), m["ssd"]["D"], z)


def recurrence(p: MixerParts):
    """``(Y [T, H, P], S [H, P, N])``: the selective state-space
    recurrence one position a step, and the state after the last."""
    H, G = p.x.shape[1], p.b.shape[1]

    def step(S, inp):
        x, dt, b, cc = inp                  # [H, P], [H], [G, N], [G, N]
        b, cc = (jnp.repeat(v, H // G, axis=0) for v in (b, cc))
        S = (jnp.exp(dt * p.a)[:, None, None] * S
             + (dt[:, None] * x)[:, :, None] * b[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, cc) + p.d[:, None] * x

    S0 = jnp.zeros(p.x.shape[1:] + (p.b.shape[2],), jnp.float32)
    S, y = jax.lax.scan(step, S0, (p.x, p.dt, p.b, p.c))
    return y, S


def carried_state(p: MixerParts, upto: int, channels: int):
    """``S [H, channels, N]`` after position ``upto - 1`` for the first
    ``channels`` channels of every head, in float64 ``numpy`` on the
    host.  Why not ``recurrence``: the chip's float32 ``exp`` is exact
    to 5e-6 only (measured, PR 33), and a state that a slow head carries
    over a thousand steps is a product of a thousand of them — 1e-4 to
    1e-3 off, more than the chunked scan's own error (a few ``exp`` a
    position) that this is there to judge."""
    import numpy as np

    x = np.asarray(p.x[:upto, :, :channels], np.float64)
    dt, a = np.asarray(p.dt[:upto], np.float64), np.asarray(p.a, np.float64)
    b = np.asarray(p.b[:upto], np.float64)
    group = np.arange(x.shape[1]) // (x.shape[1] // b.shape[1])
    S = np.zeros((x.shape[1], channels, b.shape[2]))
    for t in range(upto):
        S = (np.exp(dt[t] * a)[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * b[t][group][:, None, :])
    return S


def gated_norm(y, z, scale, groups: int, eps: float):
    """The gate first, then RMS over each group's channels, times the
    scale: ``y, z [T, H P]``."""
    g = (y * jax.nn.silu(z)).reshape(y.shape[0], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    return g.reshape(y.shape) * scale


def mixer(n, m, c: Sizes):
    """The mixer's output (before the residual) on ``n [T, d]``."""
    parts = mixer_parts(n, m, c)
    y, _ = recurrence(parts)
    y = gated_norm(y.reshape(n.shape[0], -1), parts.z, m["norm"]["scale"],
                   c.groups, c.eps)
    return y @ m["out_proj"]["kernel"]


# --------------------------------------------------------- attention


def attention(n, a):
    """The attention sublayer's output on ``n [T, d]``: grouped-query
    heads, causal, no position encoding."""
    q = jnp.einsum("td,dhk->thk", n, a["q"]["kernel"])
    k = jnp.einsum("td,dhk->thk", n, a["k"]["kernel"])
    v = jnp.einsum("td,dhk->thk", n, a["v"]["kernel"])
    T, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    rows = []
    for q0 in range(0, T, QUERY_BLOCK):
        q1 = min(T, q0 + QUERY_BLOCK)
        keep = jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :]
        heads = []
        for g in range(KV):                  # one key-value head's group
            s = jnp.einsum("qhk,sk->hqs", q[q0:q1, g * G:(g + 1) * G],
                           k[:q1, g])
            s = jnp.where(keep[None], s / math.sqrt(D), -jnp.inf)
            heads.append(jnp.einsum("hqs,sk->qhk",
                                    jax.nn.softmax(s, axis=-1), v[:q1, g]))
        rows.append(jnp.concatenate(heads, axis=1))
    return jnp.einsum("qhk,hkd->qd", jnp.concatenate(rows, axis=0),
                      a["o"]["kernel"])


# ------------------------------------------------------- the experts


def relu2_ffn(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def router(n, p, c: Sizes):
    """``(idx [T, k], weights [T, k])``: the k largest of ``sigmoid +
    bias``, weights the chosen sigmoids over their sum, times the
    scale."""
    s = jax.nn.sigmoid(n @ p["kernel"])
    _, idx = jax.lax.top_k(s + p["bias"], c.top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, picked / (jnp.sum(picked, axis=-1, keepdims=True)
                          + 1e-20) * c.scale


def routed(n, m, c: Sizes, choice):
    """The held experts' part on ``n``: expert ``first + e`` is
    ``experts[e]``; ``choice = (idx, weights)`` is the router's."""
    idx, w = choice

    def add(y, expert):
        i, up, down = expert
        w_i = jnp.sum(jnp.where(idx == c.first + i, w, 0.0), axis=-1)
        return y + w_i[:, None] * relu2_ffn(n, up, down), None

    e = m["experts"]
    return jax.lax.scan(add, jnp.zeros_like(n), (
        jnp.arange(c.held), e["up"], e["down"]))[0]


def shared(n, m):
    s = m["shared"]
    return relu2_ffn(n, s["up"]["kernel"], s["down"]["kernel"])


def expert_layer(n, m, c: Sizes):
    return routed(n, m, c, router(n, m["router"], c)) + shared(n, m)


# ------------------------------------------------------------ the model


class BlockState(NamedTuple):
    """One block of the forward pass, as the reference computed it."""

    kind: str
    params: dict
    x: jax.Array        # the state the block read
    y: jax.Array        # the state it wrote
    n: jax.Array        # its sublayer's normalised input


def sublayer(n, p, c: Sizes, kind: str):
    if kind == "mamba":
        return mixer(n, p["mamba"], c)
    if kind == "attn":
        return attention(n, p["attn"])
    return expert_layer(n, p["moe"], c)


def block_of_kind(x, p, c: Sizes, kind: str):
    """``(y, n)`` of one block on ``x [T, d]``, its kind given."""
    n = rms_norm(x, p["norm"], c.eps)
    return x + sublayer(n, p, c, kind), n


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
_block = jax.jit(block_of_kind, static_argnums=(2, 3))
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
        y, n = _block(x, p, c, c.kinds[i])
        yield BlockState(c.kinds[i], p, x, y, n)
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
