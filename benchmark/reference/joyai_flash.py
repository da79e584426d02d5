"""Plain reference for DeepSeek-V3-family configurations as
``configs/joyai-llm-flash-l5-ep16.json`` states one: the forward pass and
both loss terms in float32 ``jax.numpy``, written from the published
equations (DeepSeek-V2 report section 2.1: latent attention; DeepSeek-V3
report section 2.1.2: the router; section 2.2: multi-token prediction) —
no kernel, no sort, no buffer, no recomputation; attention in blocks of
heads and queries and the experts one at a time over ALL tokens, so that
it fits beside the train state.

    h = x + MLA(norm(x));  y = h + FFN(norm(h))          (RMSNorm, no bias)
    MLA: c_q = norm(x W_qa); [q_nope | q_rope] = c_q W_qb per head;
         [c_kv | k_rope] = x W_kva; c_kv = norm(c_kv);
         [k_nope | v] = c_kv W_kvb per head; RoPE on adjacent channel
         pairs of q_rope and of the one k_rope every head shares;
         softmax_causal(q k^T / sqrt(d_qk)) v, then W_o
    FFN of the first ``first_k_dense_replace`` layers: SwiGLU
    FFN after: s = sigmoid(x W_g); top k of s + b; weights s_i / (sum +
         1e-20) * routed_scaling_factor; sum_i w_i E_i(x) + E_shared(x)
    MTP: W_eh [norm(h_t) | norm(Emb(tok_{t+1}))], one more block, a norm,
         the shared head; predicts tok_{t+2}
    loss = CE_main + mtp_loss_weight * CE_mtp

It is given the same share as the program: the experts held here
(``n_routed_experts`` of ``reduced_from.n_routed_experts``, from expert
0) and the table as built.  It reads the program's parameter tree (the
weights under test), nothing else of the program.  On a TPU a float32
matmul runs in reduced precision unless asked otherwise, so everything
runs under ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HEAD_BLOCK, QUERY_BLOCK, ROW_BLOCK = 8, 1024, 2048


class Sizes(NamedTuple):
    eps: float
    d_nope: int
    d_rope: int
    kv_rank: int
    theta: float
    top_k: int
    scale: float
    first: int          # first expert held
    held: int           # experts held


def sizes(cfg: dict, first: int = 0) -> Sizes:
    return Sizes(cfg["rms_norm_eps"], cfg["qk_nope_head_dim"],
                 cfg["qk_rope_head_dim"], cfg["kv_lora_rank"],
                 float(cfg["rope_theta"]), cfg["num_experts_per_tok"],
                 float(cfg["routed_scaling_factor"]), first,
                 cfg["n_routed_experts"])


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rope_pairs(x, theta):
    """Rotate the adjacent channel pairs (2i, 2i+1) of ``x [T, ..., d]``
    by ``t * theta^(-2i/d)``."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def mla(x, a, c: Sizes):
    T = x.shape[0]
    c_q = rms_norm(x @ a["q_a"]["kernel"], a["q_norm"], c.eps)
    q = jnp.einsum("tr,rhk->thk", c_q, a["q_b"]["kernel"])
    kv_a = x @ a["kv_a"]["kernel"]
    c_kv = rms_norm(kv_a[:, :c.kv_rank], a["kv_norm"], c.eps)
    kv = jnp.einsum("tr,rhk->thk", c_kv, a["kv_b"]["kernel"])
    H = q.shape[1]
    k_rope = rope_pairs(kv_a[:, c.kv_rank:], c.theta)
    q = jnp.concatenate(
        [q[..., :c.d_nope], rope_pairs(q[..., c.d_nope:], c.theta)], -1)
    k = jnp.concatenate(
        [kv[..., :c.d_nope],
         jnp.broadcast_to(k_rope[:, None, :], (T, H, c.d_rope))], -1)
    v = kv[..., c.d_nope:]
    rows = []
    for q0 in range(0, T, QUERY_BLOCK):
        q1 = min(T, q0 + QUERY_BLOCK)
        keep = (jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :])
        heads = []
        for h0 in range(0, H, HEAD_BLOCK):
            hs = slice(h0, h0 + HEAD_BLOCK)
            s = jnp.einsum("qhk,shk->hqs", q[q0:q1, hs], k[:q1, hs])
            s = jnp.where(keep[None], s / math.sqrt(q.shape[-1]), -jnp.inf)
            heads.append(jnp.einsum("hqs,shk->qhk",
                                    jax.nn.softmax(s, axis=-1), v[:q1, hs]))
        rows.append(jnp.concatenate(heads, axis=1))
    return jnp.einsum("qhk,hkd->qd", jnp.concatenate(rows, axis=0),
                      a["o"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router(x, p, c: Sizes):
    """``(idx [T, k], weights [T, k])``."""
    s = jax.nn.sigmoid(x @ p["kernel"])
    _, idx = jax.lax.top_k(s + p["bias"], c.top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, picked / (picked.sum(-1, keepdims=True) + 1e-20) * c.scale


def routed(x, m, c: Sizes, choice=None):
    """The held experts' part: expert ``first + e`` is ``experts[e]``.
    ``choice = (idx, weights)`` stands in for the router's own (a
    comparison that holds the choice fixed)."""
    idx, w = router(x, m["router"], c) if choice is None else choice

    def add(y, expert):
        i, gate, up, down = expert
        w_i = jnp.sum(jnp.where(idx == c.first + i, w, 0.0), axis=-1)
        return y + w_i[:, None] * swiglu(x, gate, up, down), None

    e = m["experts"]
    return jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(c.held), e["gate"], e["up"], e["down"]))[0]


def shared(x, m):
    s = m["shared"]
    return swiglu(x, s["gate"]["kernel"], s["up"]["kernel"],
                  s["down"]["kernel"])


def block_parts(x, p, c: Sizes):
    """``(y, h)``: one pre-norm block on ``x [T, d]`` — an expert layer
    where the parameters hold one — and the normalised state ``h`` its
    feed-forward read."""
    x = x + mla(rms_norm(x, p["ln1"], c.eps), p["attn"], c)
    h = rms_norm(x, p["ln2"], c.eps)
    if "moe" in p:
        return x + routed(h, p["moe"], c) + shared(h, p["moe"]), h
    m = p["mlp"]
    return x + swiglu(h, m["gate"]["kernel"], m["up"]["kernel"],
                      m["down"]["kernel"]), h


def block(x, p, c: Sizes):
    return block_parts(x, p, c)[0]


def ce_sum(h, head, targets):
    """Sum over ``h``'s rows of the cross-entropy of ``h @ head`` against
    ``targets``, in blocks of rows."""
    total = jnp.zeros((), jnp.float32)
    for r0 in range(0, h.shape[0], ROW_BLOCK):
        logp = jax.nn.log_softmax(h[r0:r0 + ROW_BLOCK] @ head, axis=-1)
        total = total - jnp.sum(jnp.take_along_axis(
            logp, targets[r0:r0 + ROW_BLOCK, None], axis=1))
    return total


_block = jax.jit(block_parts, static_argnums=2)
_ce_sum = jax.jit(ce_sum)


@functools.partial(jax.jit, static_argnums=4)
def _mtp_input(h, emb, tokens, m, eps):
    nxt = emb[jnp.roll(tokens, -1)]
    return jnp.concatenate([rms_norm(h, m["norm_h"], eps),
                            rms_norm(nxt, m["norm_e"], eps)],
                           axis=-1) @ m["proj"]["kernel"]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def block_states(params, tokens, n_layer, c: Sizes):
    """Every block of the forward pass on one sequence ``tokens [T]``, in
    order, as ``(parameters, x, y, h)`` — its float32 parameters, the
    state it read, the state it wrote and the normalised state its
    feed-forward read — the multi-token-prediction module's block last
    (where there is one); then the two states the head reads.  A
    generator: one block's states are live at a time."""
    emb = _f32(params["embed"]["embedding"])
    norm = jax.jit(rms_norm, static_argnums=2)
    x = emb[tokens]
    for i in range(n_layer):
        p = _f32(params[f"block_{i}"])
        y, h = _block(x, p, c)
        yield p, x, y, h
        x = y
    final = norm(x, _f32(params["ln_f"]), c.eps)
    final_mtp = None
    if "mtp" in params:
        m = _f32(params["mtp"])
        x = _mtp_input(final, emb, tokens, m, c.eps)
        y, h = _block(x, m["block"], c)
        yield m["block"], x, y, h
        final_mtp = norm(y, m["norm"], c.eps)
    yield final, final_mtp


def sequence_loss_sums(params, tokens, n_layer, c: Sizes):
    """``(main, mtp)``: sums over positions of one sequence
    ``tokens [T]`` of the next-token cross-entropy (``T - 1`` targets)
    and of the module's on the token after next (``T - 2``; 0.0 without
    a module)."""
    head = _f32(params["lm_head"]["kernel"])
    *_, (h, h2) = block_states(params, tokens, n_layer, c)
    main = _ce_sum(h[:-1], head, tokens[1:])
    if h2 is None:
        return main, 0.0
    return main, _ce_sum(h2[:-2], head, tokens[2:])


def loss(params, tokens, cfg: dict) -> float:
    """Mean next-token cross-entropy over ``tokens [B, T]`` plus the
    weighted multi-token-prediction term — what the train step reports
    for its first batch on the same weights."""
    B, T = tokens.shape
    c = sizes(cfg)
    weight = cfg["assumed"]["mtp_loss_weight"]
    main = mtp = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            a, m = sequence_loss_sums(params, tokens[b],
                                      cfg["num_hidden_layers"], c)
            main, mtp = main + float(a), mtp + float(m)
    return main / (B * (T - 1)) + weight * mtp / (B * (T - 2))
