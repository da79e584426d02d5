"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no model-dimension parallelism at all (SURVEY.md §5
"Long-context / sequence parallelism: Absent") — its only long-tensor story
is byte-partitioning for the wire.  This module supplies the TPU-idiomatic
counterpart that the rebuild treats as first-class: shard the *sequence*
dimension over a mesh axis and compute exact attention with ICI-neighbor
communication.

Two interchangeable strategies, both called inside ``shard_map`` with the
sequence axis sharded over ``axis_name``:

* **Ring attention** (`ring_attention`): K/V blocks rotate around the ring
  with ``lax.ppermute`` while each step's partial attention is folded into a
  numerically-stable online softmax (running max / denominator).  Comm is
  neighbor-only — exactly the ICI torus's strength — and overlaps with the
  per-block matmuls under XLA's latency-hiding scheduler.
* **Ulysses** (`ulysses_attention`): ``lax.all_to_all`` re-shards
  [seq-sharded, all heads] -> [full seq, head-sharded], runs ordinary local
  attention per head group, and all-to-alls back.  Cheaper at moderate
  sequence lengths (2 collectives instead of S-1 permutes) but requires
  num_heads % axis_size == 0.

Shapes follow the TPU-native convention ``[batch, seq, heads, head_dim]``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _online_softmax_step(o, m, l, s, v, mask=None):
    """Fold one score block into the running (output, max, denom) triple.

    o: [B, Tq, H, D] accumulator;  m, l: [B, Tq, H] running max / denominator
    s: [B, Tq, H, Tk] scores;      v: [B, Tk, H, D]
    """
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # exp(-inf - -inf) guard: where m_new is -inf nothing has been seen yet
    alpha = jnp.exp(jnp.where(m == -jnp.inf, -jnp.inf, m - m_new))
    alpha = jnp.nan_to_num(alpha)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.nan_to_num(p)  # fully-masked rows: exp(-inf - -inf)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bqhk,bkhd->bqhd", p, v, preferred_element_type=o.dtype
    )
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    q, k, v: ``[B, T_local, H, D]`` — the local sequence shard.  Returns the
    local shard of the attention output, same shape as ``q``.

    Each of the ``axis_size`` scan steps attends the local queries against
    the currently-held K/V block, then rotates K/V one hop around the ring
    (``ppermute`` rides a single ICI link per step).  With ``causal=True``
    blocks entirely in the future are masked via global position indices;
    the compute for those blocks still runs (static shapes — XLA requires
    it) but contributes nothing.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    qf = (q * scale).astype(jnp.float32)

    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    m0 = jnp.full((B, T, H), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, T, H), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        o, m, l, kc, vc = carry
        src = (my - step) % n  # whose K/V block we hold this step
        s = jnp.einsum(
            "bqhd,bkhd->bqhk", qf, kc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        mask = None
        if causal:
            q_pos = my * T + jnp.arange(T)[:, None]
            k_pos = src * T + jnp.arange(T)[None, :]
            mask = (q_pos >= k_pos)[None, :, None, :]
        o, m, l = _online_softmax_step(o, m, l, s, vc.astype(jnp.float32), mask)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (o, m, l, kc, vc), None

    (o, m, l, _, _), _ = lax.scan(body, (o0, m0, l0, k, v), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ring attention whose per-block compute is the Pallas flash kernel —
    ``attn_impl="flash"`` composed with the ``sp`` axis.

    Same ring schedule as :func:`ring_attention` (K/V rotate by ``ppermute``,
    one ICI hop per step), but each held block is attended with
    ``flash_attention_with_lse`` (MXU kernel, O(T_local) memory) and the
    per-block normalized results are folded with log-sum-exp weights:

        lse' = logaddexp(lse, lse_blk)
        o'   = o * e^(lse-lse') + o_blk * e^(lse_blk-lse')

    Causality: past blocks attend fully, the diagonal block runs the causal
    kernel (local positions == global on the diagonal), future blocks are
    nulled at the combine (lse_blk = -inf).  Differentiable end to end —
    the lse cotangent of the combine flows into the flash backward kernel
    (ops/flash_attention.py::_flash_backward).
    """
    from ..ops.flash_attention import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]

    o0 = jnp.zeros((B, T, H, D), jnp.float32)
    lse0 = jnp.full((B, T, H), -jnp.inf, jnp.float32)

    def body(carry, step):
        o, lse, kc, vc = carry
        src = (my - step) % n  # whose K/V block we hold this step
        if causal:
            o_blk, lse_blk = lax.cond(
                src == my,
                lambda: flash_attention_with_lse(
                    q, kc, vc, True, scale, block_q, block_k, interpret),
                lambda: flash_attention_with_lse(
                    q, kc, vc, False, scale, block_q, block_k, interpret),
            )
            # block-level causality: strictly-future blocks contribute 0
            lse_blk = jnp.where(src <= my, lse_blk, -jnp.inf)
        else:
            o_blk, lse_blk = flash_attention_with_lse(
                q, kc, vc, False, scale, block_q, block_k, interpret)
        lse_new = jnp.logaddexp(lse, lse_blk)
        w_old = jnp.nan_to_num(jnp.exp(lse - lse_new))
        w_blk = jnp.nan_to_num(jnp.exp(lse_blk - lse_new))
        o = o * w_old[..., None] + o_blk.astype(jnp.float32) * w_blk[..., None]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (o, lse_new, kc, vc), None

    (o, _, _, _), _ = lax.scan(body, (o0, lse0, k, v), jnp.arange(n))
    return o.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Re-shards seq->heads with one ``all_to_all``, computes ordinary full-
    sequence attention on the local head group, and re-shards back.  Requires
    ``H % axis_size == 0``.  q, k, v: ``[B, T_local, H, D]``.
    """
    n = lax.psum(1, axis_name)
    B, T, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5

    def to_heads(x):  # [B, T, H, D] -> [B, T*n, H//n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):  # inverse
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    s = jnp.einsum(
        "bqhd,bkhd->bqhk", (qh * scale).astype(jnp.float32),
        kh.astype(jnp.float32), preferred_element_type=jnp.float32,
    )
    if causal:
        S = T * n
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bqhk,bkhd->bqhd", p, vh.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return to_seq(out)


def local_attention(q, k, v, causal=False, scale=None, key_mask=None):
    """Plain (non-parallel) reference attention, same convention.

    ``key_mask``: optional ``[B, S]`` keep-mask (1 = attend, 0 = ignore) —
    padded keys are excluded from the softmax (standard BERT padding
    semantics)."""
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bqhk", (q * scale).astype(jnp.float32),
        k.astype(jnp.float32), preferred_element_type=jnp.float32,
    )
    if causal:
        T, S = q.shape[1], k.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :].astype(bool), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.nan_to_num(p)  # rows with every key masked
    return jnp.einsum(
        "bqhk,bkhd->bqhd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
