"""Pallas TPU flash attention (forward + backward kernels).

The hot op of the transformer stack, written for the MXU/VMEM rather than
translated from any CUDA kernel.  All three kernels share one structure:
a 3-D grid ``(batch*heads, outer blocks, inner blocks)`` whose innermost
dim is declared "arbitrary" so Mosaic pipelines the inner-operand
HBM→VMEM copies against compute, with the accumulator (online-softmax
carry, or the dq/dk/dv partials) living in VMEM scratch across inner
steps — no [T, T] score matrix ever materializes in HBM.  Causal masking
prunes above-diagonal blocks: ``pl.when`` skips their compute and a
clamped BlockSpec index map elides their DMAs (an unchanged block index
between consecutive grid steps performs no copy).

Backward is a custom_vjp with residuals (q, k, v, o, lse, segment_ids)
and **two Pallas kernels** (the standard flash-attention-2 split, designed
for the MXU's preference for large stationary operands over atomics):

  * ``_bwd_dq_kernel`` — grid (batch*heads, q blocks, k blocks):
    recomputes one [BQ, BK] score slice per step and accumulates dq;
  * ``_bwd_dkv_kernel`` — grid (batch*heads, k blocks, q blocks):
    accumulates dk/dv for its k block across the q-block dim.

Peak memory stays O(T * block) like the forward.  Combined with
``parallel/ring_attention.py`` (which shards T across chips and calls this
kernel per ring block — ``attn_impl="flash"`` composes with the ``sp``
axis) this covers both the single-chip memory story and the multi-chip
long-context story.

Layout convention matches the rest of the stack: ``[B, T, H, D]``.
``D`` should be a multiple of the 128-lane width for full MXU utilization
(64 works; the compiler pads).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_utils import fit_block as _fit_block_impl, resolve_interpret

# Tuned on TPU v5e at T=4096 bf16 (D=64 and D=128): (1024, 1024) beats
# (512, 1024) by ~3-4% fwd+bwd and (128, 128) by >4x — big blocks amortize
# grid-step overhead and keep the MXU fed; the 4 MB f32 score block plus
# double-buffered operands still fits VMEM at D=128.  Both clamp to T for
# short sequences.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# The two backward kernels tune independently of the forward (r4 verdict
# #6) — each carries three live [BQ, BK] fp32 temps (s, dp, ds) where the
# fwd holds one, so a different optimum was plausible.  An on-chip
# per-kernel sweep (round 4, TPU v5e, an earlier toolchain) found
# 1024x1024 optimal for BOTH anyway (every smaller/rectangular shape
# loses 2-70%, larger VMEM-fails) — the machinery stays so a future
# chip can retune per kernel.  Applied only when the caller left
# block_q/block_k at the fwd defaults (an explicit caller choice is
# respected for all three kernels).
DEFAULT_BWD_DQ_BLOCKS = (1024, 1024)   # (block_q, block_k) of _bwd_dq
DEFAULT_BWD_DKV_BLOCKS = (1024, 1024)  # (block_q, block_k) of _bwd_dkv
_NEG_INF = -1e30


def _fwd_blocks(block_q, block_k):
    """Resolve the public ``None`` block defaults to the fwd-tuned
    shapes.  The public API defaults are ``None`` (not the tuned ints)
    so the backward can tell an explicit caller choice of 1024x1024
    apart from "caller didn't care" — only the latter may be overridden
    by the independently swept bwd defaults."""
    return (DEFAULT_BLOCK_Q if block_q is None else block_q,
            DEFAULT_BLOCK_K if block_k is None else block_k)


def _fit_block(block: int, T: int) -> int:
    return _fit_block_impl(block, T, what="seq len")


def _causal_last_k(qi, block_q: int, block_k: int, nk: int):
    """Last k-block index that intersects the causal lower triangle of q
    block ``qi``: floor(((qi+1)*BQ - 1) / BK), clamped to the grid."""
    return jnp.minimum((qi * block_q + block_q - 1) // block_k, nk - 1)


def _seg_mask(sq_ref, sk_ref, s):
    """Mask scores where q and k segment ids differ (HF attention-mask /
    packed-sequence semantics): sq [BQ, 1] int32, sk [BK, 1] int32."""
    valid = sq_ref[0] == sk_ref[0][:, 0][None, :]   # [BQ, BK]
    return jnp.where(valid, s, _NEG_INF)


def _window_first_k(qi, block_q: int, block_k: int, window: int):
    """First k-block index that intersects the sliding-window band of q
    block ``qi``: floor((qi*BQ - (W-1)) / BK), clamped to 0."""
    return jnp.maximum((qi * block_q - (window - 1)) // block_k, 0)


def _band_mask(s, row, col, causal: bool, window):
    """Apply causal and/or sliding-window masking to a score block."""
    if causal:
        valid = row >= col
        if window is not None:
            valid = valid & (row - col < window)
        s = jnp.where(valid, s, _NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, nk: int, causal: bool,
                scale: float, has_seg: bool, has_alibi: bool = False,
                window=None):
    idx = 0
    if has_seg:
        sq_ref, sk_ref = rest[0], rest[1]
        idx = 2
    else:
        sq_ref = sk_ref = None
    if has_alibi:
        slope_ref = rest[idx]
        idx += 1
    else:
        slope_ref = None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest[idx:]
    # grid (BH, nq, nk), k innermost ("arbitrary"): Mosaic pipelines the
    # K/V HBM→VMEM copies against compute; the online-softmax carry lives
    # in VMEM scratch across k steps.  q/o blocks: [1, BQ, D]; k/v block:
    # [1, BK, D]; lse: [1, BQ, 1].
    #
    # MXU dtype discipline: the dots run in the INPUT dtype (bf16 inputs →
    # bf16 MXU passes at full rate) with fp32 accumulation via
    # preferred_element_type; only the softmax bookkeeping is fp32 —
    # the standard flash-attention-2 arrangement (p cast back to the value
    # dtype for the second dot).
    qi = pl.program_id(1)
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: k blocks strictly above the diagonal contribute nothing —
    # skip compute entirely (their DMA was also elided by the clamped
    # index map in _flash_forward); sliding window additionally prunes
    # blocks entirely left of the band
    compute = (j * block_k <= qi * block_q + block_q - 1) if causal else True
    if window is not None:
        compute = compute & (
            j * block_k + block_k - 1 >= qi * block_q - (window - 1))

    @pl.when(compute)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK] fp32
        if causal or has_alibi:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if has_alibi:
                # ALiBi: slope_h * (j - i), 0 on the diagonal, more
                # negative with distance — computed in-kernel, no bias
                # tensor ever exists in HBM
                s = s + slope_ref[0, 0, 0] * (col - row).astype(jnp.float32)
            s = _band_mask(s, row, col, causal, window)
        if has_seg:
            s = _seg_mask(sq_ref, sk_ref, s)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)  # [BQ, 1]


def _gqa_group(q, k):
    """Validate shapes; returns (H, Hkv, group).  GQA/MQA: k/v carry Hkv
    heads with H % Hkv == 0; each group of H/Hkv query heads reads the
    same kv head (no materialized repeat — the kv BlockSpec index map
    points grid row b at its group's kv row)."""
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    return H, Hkv, H // Hkv


def _check_band_args(causal, window, alibi_slopes, H):
    if window is not None:
        if not causal:
            raise ValueError("sliding window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if alibi_slopes is not None:
        if not causal:
            raise ValueError("alibi_slopes requires causal=True")
        if alibi_slopes.shape != (H,):
            raise ValueError(
                f"alibi_slopes must be [H]={H}, got {alibi_slopes.shape}")


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   segment_ids=None, window=None, alibi_slopes=None):
    interpret = resolve_interpret(interpret, "flash_attention forward")
    B, T, H, D = q.shape
    H, Hkv, group = _gqa_group(q, k)
    _check_band_args(causal, window, alibi_slopes, H)
    bq = _fit_block(block_q, T)
    bk = _fit_block(block_k, T)
    nk = T // bk
    # fold heads into the batch grid dim; [B, T, H, D] -> [B*H, T, D]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)

    def kv_row(b):
        return (b // H) * Hkv + (b % H) // group

    if causal:
        # clamp skipped blocks into the useful range: consecutive grid
        # steps with an unchanged index skip the DMA (above-diagonal
        # blocks clamp down; left-of-window blocks clamp up)
        def clamp_j(i, j):
            jj = jnp.minimum(j, _causal_last_k(i, bq, bk, nk))
            if window is not None:
                jj = jnp.maximum(jj, _window_first_k(i, bq, bk, window))
            return jj

        def kv_idx(b, i, j):
            return (kv_row(b), clamp_j(i, j), 0)

        def sk_idx(b, i, j):
            return (b // H, clamp_j(i, j), 0)
    else:
        def kv_idx(b, i, j):
            return (kv_row(b), j, 0)

        def sk_idx(b, i, j):
            return (b // H, j, 0)

    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), kv_idx),
        pl.BlockSpec((1, bk, D), kv_idx),
    ]
    operands = [qf, kf, vf]
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)[..., None]   # [B, T, 1]
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b // H, i, 0)),
            pl.BlockSpec((1, bk, 1), sk_idx),
        ]
        operands += [seg, seg]
    if alibi_slopes is not None:
        slopes_f = jnp.tile(alibi_slopes.astype(jnp.float32),
                            B)[:, None, None]            # [B*H, 1, 1]
        in_specs += [pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0))]
        operands += [slopes_f]

    kernel = functools.partial(
        _fwd_kernel, nk=nk, causal=causal, scale=scale,
        has_seg=segment_ids is not None,
        has_alibi=alibi_slopes is not None, window=window)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B * H, T // bq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # lse kept 3-D: TPU requires the last two block dims divisible
            # by (8, 128) or equal to the full array dims — (bq, 1) is
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3), lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 9))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    alibi_slopes: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention, O(T) memory forward.  q: ``[B, T, H, D]``;
    k/v: ``[B, T, Hkv, D]`` with ``H % Hkv == 0`` (GQA/MQA: each group of
    ``H/Hkv`` query heads shares one kv head, read via the BlockSpec index
    map — no materialized repeat in the forward).

    ``segment_ids`` (``[B, T]`` int, optional) masks attention across
    segment boundaries — packed sequences use distinct ids per document;
    an HF-style padding mask works as-is (1 = valid, 0 = pad: pads only
    see pads, so valid positions match the masked-softmax result exactly,
    see models/bert.py).  Every query position shares its own segment id
    at the diagonal, so no row is ever fully masked.

    ``window`` (int, optional; requires ``causal=True``) restricts each
    query to the last ``window`` positions (Mistral-style sliding-window
    attention): position i attends to j in [i-window+1, i].  Blocks
    entirely outside the band skip both compute and DMA (the index map
    clamps from both sides), so the effective cost is O(T * window).

    ``alibi_slopes`` (``[H]`` fp32, optional; requires ``causal=True``)
    adds the ALiBi position bias ``slope_h * (j - i)`` to the scores —
    computed from iotas inside the kernel, so no [T, T] bias tensor ever
    exists.  Slopes are treated as constants (zero cotangent): ALiBi
    slopes are fixed by the head-count formula in practice, not learned.

    ``block_q``/``block_k`` default to ``None`` = the tuned defaults
    (``DEFAULT_BLOCK_Q/K`` forward, the independently swept
    ``DEFAULT_BWD_*`` shapes backward).  Passing explicit values binds
    all three kernels to that choice — including an explicit 1024x1024,
    e.g. when a VMEM budget forces the shape."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    bq, bk = _fwd_blocks(block_q, block_k)
    o, _ = _flash_forward(q, k, v, causal, scale, bq, bk,
                          interpret, segment_ids, window, alibi_slopes)
    return o


def _fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret,
              segment_ids, window, alibi_slopes):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    bq, bk = _fwd_blocks(block_q, block_k)
    o, lse = _flash_forward(q, k, v, causal, scale, bq, bk,
                            interpret, segment_ids, window, alibi_slopes)
    return o, (q, k, v, o, lse, segment_ids, alibi_slopes)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   nk: int, causal: bool, scale: float, has_seg: bool,
                   has_alibi: bool = False, window=None):
    """dq accumulation over the k-block grid dim (innermost): recompute
    the [BQ, BK] score slice, accumulate dq = scale * sum_j ds_j @ k_j in
    VMEM scratch; same 3-D-grid pipelining as the forward."""
    idx = 0
    if has_seg:
        sq_ref, sk_ref = rest[0], rest[1]
        idx = 2
    else:
        sq_ref = sk_ref = None
    if has_alibi:
        slope_ref = rest[idx]
        idx += 1
    else:
        slope_ref = None
    dq_ref, dq_acc_ref = rest[idx:]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    compute = (j * block_k <= qi * block_q + block_q - 1) if causal else True
    if window is not None:
        compute = compute & (
            j * block_k + block_k - 1 >= qi * block_q - (window - 1))

    @pl.when(compute)
    def _step():
        q = q_ref[0]                                  # [BQ, D], input dtype
        do = do_ref[0]                                # [BQ, D], input dtype
        lse = lse_ref[0].astype(jnp.float32)          # [BQ, 1]
        delta = delta_ref[0].astype(jnp.float32)      # [BQ, 1]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK] fp32
        if causal or has_alibi:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if has_alibi:
                s = s + slope_ref[0, 0, 0] * (col - row).astype(jnp.float32)
            s = _band_mask(s, row, col, causal, window)
        if has_seg:
            s = _seg_mask(sq_ref, sk_ref, s)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK] fp32
        ds = p * (dp - delta)
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, *rest,
                    nq: int, causal: bool, scale: float, has_seg: bool,
                    has_alibi: bool = False, window=None):
    """dk/dv accumulation over the q-block grid dim (innermost; causal
    pruning skips q blocks above the diagonal): dv = sum_i p_i^T @ do_i,
    dk = scale * sum_i ds_i^T @ q_i, accumulated in VMEM scratch."""
    idx = 0
    if has_seg:
        sk_ref, sq_ref = rest[0], rest[1]
        idx = 2
    else:
        sq_ref = sk_ref = None
    if has_alibi:
        slope_ref = rest[idx]
        idx += 1
    else:
        slope_ref = None
    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = rest[idx:]
    ki = pl.program_id(1)
    i = pl.program_id(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # causal: q blocks entirely above the diagonal see only masked
    # scores; sliding window additionally prunes q blocks entirely
    # below/right of the band
    compute = (i * block_q + block_q - 1 >= ki * block_k) if causal else True
    if window is not None:
        compute = compute & (
            i * block_q <= ki * block_k + block_k - 1 + (window - 1))

    @pl.when(compute)
    def _step():
        k = k_ref[0]                                  # [BK, D], input dtype
        v = v_ref[0]                                  # [BK, D], input dtype
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0].astype(jnp.float32)
        delta = delta_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BQ, BK] fp32
        if causal or has_alibi:
            row = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if has_alibi:
                s = s + slope_ref[0, 0, 0] * (col - row).astype(jnp.float32)
            s = _band_mask(s, row, col, causal, window)
        if has_seg:
            s = _seg_mask(sq_ref, sk_ref, s)
        p = jnp.exp(s - lse)                       # [BQ, BK] fp32
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BK, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK] fp32
        ds = p * (dp - delta)
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BK, D]

    @pl.when(i == nq - 1)
    def _finish():
        # s was scaled after the q·k dot, so dL/dk = scale * sum ds^T @ q
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, dlse, causal, scale, block_q,
                    block_k, interpret, segment_ids=None, window=None,
                    alibi_slopes=None, dq_blocks=None, dkv_blocks=None):
    """Shared Pallas backward.  ``dlse`` (``[BH, T, 1]`` or None) is the
    cotangent of the log-sum-exp output: since d(lse)/d(s) = softmax(s),
    it folds into the kernels as ``ds = p * (dp - (delta - dlse))`` — the
    same two kernels serve both ``flash_attention`` and the
    lse-returning variant ring attention differentiates through.

    ``dq_blocks``/``dkv_blocks`` override (block_q, block_k) per kernel —
    the two kernels' VMEM pressure differs (3 live [BQ, BK] fp32 temps
    each, but different stationary operands), so they tune independently.

    GQA backward materializes per-q-head k/v (one [B, T, H, D] transient
    each — the forward stays repeat-free) and group-sums dk/dv back to
    the Hkv heads; the dkv kernel's grid row owns its k block exclusively,
    which a shared kv row would break."""
    interpret = resolve_interpret(interpret, "flash_attention backward")
    B, T, H, D = q.shape
    H, Hkv, group = _gqa_group(q, k)
    _check_band_args(causal, window, alibi_slopes, H)
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = scale if scale is not None else D ** -0.5
    bq1, bk1 = dq_blocks if dq_blocks is not None else (block_q, block_k)
    bq2, bk2 = dkv_blocks if dkv_blocks is not None else (block_q, block_k)
    bq1, bk1 = _fit_block(bq1, T), _fit_block(bk1, T)
    bq2, bk2 = _fit_block(bq2, T), _fit_block(bk2, T)

    # fold batch & heads: [B, T, H, D] -> [BH, T, D]
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, x.shape[-1])

    qf, kf, vf, dof = fold(q), fold(k), fold(v), fold(do)
    # delta = rowsum(do * o), the softmax-jacobian correction term
    delta = jnp.sum(fold(do).astype(jnp.float32) * fold(o).astype(jnp.float32),
                    axis=-1, keepdims=True)          # [BH, T, 1]
    if dlse is not None:
        delta = delta - dlse
    lse3 = lse[..., None]                            # [BH, T, 1]

    nk1, nq1 = T // bk1, T // bq1
    nk2, nq2 = T // bk2, T // bq2
    arb = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    if causal:
        def kv_idx(b, i, j):
            jj = jnp.minimum(j, _causal_last_k(i, bq1, bk1, nk1))
            if window is not None:
                jj = jnp.maximum(jj, _window_first_k(i, bq1, bk1, window))
            return (b, jj, 0)

        def q_idx(b, ki, i):  # clamp from below: first useful q block
            ii = jnp.maximum(i, (ki * bk2) // bq2)
            if window is not None:
                # clamp from above: last q block inside the band
                ii = jnp.minimum(
                    ii, jnp.minimum(
                        (ki * bk2 + bk2 - 1 + window - 1) // bq2, nq2 - 1))
            return (b, ii, 0)
    else:
        def kv_idx(b, i, j):
            return (b, j, 0)

        def q_idx(b, ki, i):
            return (b, i, 0)

    has_seg = segment_ids is not None
    has_alibi = alibi_slopes is not None
    if has_seg:
        seg = segment_ids.astype(jnp.int32)[..., None]   # [B, T, 1]
    if has_alibi:
        slopes_f = jnp.tile(alibi_slopes.astype(jnp.float32),
                            B)[:, None, None]            # [B*H, 1, 1]

    dq_specs = [
        pl.BlockSpec((1, bq1, D), lambda b, i, j: (b, i, 0)),  # q block
        pl.BlockSpec((1, bk1, D), kv_idx),                     # k block
        pl.BlockSpec((1, bk1, D), kv_idx),                     # v block
        pl.BlockSpec((1, bq1, D), lambda b, i, j: (b, i, 0)),  # do block
        pl.BlockSpec((1, bq1, 1), lambda b, i, j: (b, i, 0)),  # lse block
        pl.BlockSpec((1, bq1, 1), lambda b, i, j: (b, i, 0)),  # delta
    ]
    dq_ops = [qf, kf, vf, dof, lse3, delta]
    if has_seg:
        def skv_idx(b, i, j):
            bi, ji, _ = kv_idx(b, i, j)
            return (b // H, ji, 0)

        dq_specs += [
            pl.BlockSpec((1, bq1, 1), lambda b, i, j: (b // H, i, 0)),
            pl.BlockSpec((1, bk1, 1), skv_idx),
        ]
        dq_ops += [seg, seg]
    if has_alibi:
        dq_specs += [pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0))]
        dq_ops += [slopes_f]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk1, causal=causal, scale=scale,
                          has_seg=has_seg, has_alibi=has_alibi,
                          window=window),
        name="flash_bwd_dq",
        grid=(B * H, nq1, nk1),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, bq1, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq1, D), jnp.float32)],
        compiler_params=arb,
        interpret=interpret,
    )(*dq_ops)

    dkv_specs = [
        pl.BlockSpec((1, bk2, D), lambda b, ki, i: (b, ki, 0)),  # k block
        pl.BlockSpec((1, bk2, D), lambda b, ki, i: (b, ki, 0)),  # v block
        pl.BlockSpec((1, bq2, D), q_idx),                        # q block
        pl.BlockSpec((1, bq2, D), q_idx),                        # do block
        pl.BlockSpec((1, bq2, 1), q_idx),                        # lse
        pl.BlockSpec((1, bq2, 1), q_idx),                        # delta
    ]
    dkv_ops = [kf, vf, qf, dof, lse3, delta]
    if has_seg:
        def sq_idx(b, ki, i):
            bi, ii, _ = q_idx(b, ki, i)
            return (b // H, ii, 0)

        dkv_specs += [
            pl.BlockSpec((1, bk2, 1), lambda b, ki, i: (b // H, ki, 0)),
            pl.BlockSpec((1, bq2, 1), sq_idx),
        ]
        dkv_ops += [seg, seg]
    if has_alibi:
        dkv_specs += [pl.BlockSpec((1, 1, 1), lambda b, ki, i: (b, 0, 0))]
        dkv_ops += [slopes_f]

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq2, causal=causal, scale=scale,
                          has_seg=has_seg, has_alibi=has_alibi,
                          window=window),
        name="flash_bwd_dkv",
        grid=(B * H, nk2, nq2),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, bk2, D), lambda b, ki, i: (b, ki, 0)),
            pl.BlockSpec((1, bk2, D), lambda b, ki, i: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk2, D), jnp.float32),
            pltpu.VMEM((bk2, D), jnp.float32),
        ],
        compiler_params=arb,
        interpret=interpret,
    )(*dkv_ops)

    def unfold(x, dtype):
        return x.reshape(B, H, T, D).transpose(0, 2, 1, 3).astype(dtype)

    dq_out = unfold(dq, q.dtype)
    dk_out = unfold(dk, k.dtype)
    dv_out = unfold(dv, v.dtype)
    if group > 1:  # fold per-q-head kv grads back onto the shared kv heads
        dk_out = dk_out.reshape(B, T, Hkv, group, D).sum(3).astype(k.dtype)
        dv_out = dv_out.reshape(B, T, Hkv, group, D).sum(3).astype(v.dtype)
    return dq_out, dk_out, dv_out


def _bwd_blocks(block_q, block_k):
    """Per-kernel bwd block shapes: the swept defaults when the caller
    left (block_q, block_k) unset (``None`` — the public defaults), else
    the caller's explicit choice for both kernels (a VMEM-forced small
    block must bind the bwd too).  Because the public defaults are
    ``None``, an explicit 1024x1024 is distinguishable from "defaults"
    and is honored as a caller choice."""
    if block_q is None and block_k is None:
        return DEFAULT_BWD_DQ_BLOCKS, DEFAULT_BWD_DKV_BLOCKS
    bq, bk = _fwd_blocks(block_q, block_k)
    return (bq, bk), (bq, bk)


def _bwd_rule(causal, scale, block_q, block_k, interpret, window, res, do):
    import numpy as np

    q, k, v, o, lse, segment_ids, alibi_slopes = res
    dq_b, dkv_b = _bwd_blocks(block_q, block_k)
    bq, bk = _fwd_blocks(block_q, block_k)
    dq, dk, dv = _flash_backward(q, k, v, o, lse, do, None, causal, scale,
                                 bq, bk, interpret, segment_ids,
                                 window, alibi_slopes,
                                 dq_blocks=dq_b, dkv_blocks=dkv_b)
    dseg = (None if segment_ids is None
            else np.zeros(segment_ids.shape, jax.dtypes.float0))
    # slopes are constants by contract (see flash_attention docstring)
    dslopes = None if alibi_slopes is None else jnp.zeros_like(alibi_slopes)
    return dq, dk, dv, dseg, dslopes


flash_attention.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Forward returning ``(o, lse)`` with ``lse: [B, T, H]`` — the
    combinable form ring attention needs to fold per-ring-step block
    results (see parallel/ring_attention.py).  Fully differentiable in
    both outputs: the lse cotangent folds into the same Pallas backward
    kernels (see _flash_backward)."""
    o, lse, _ = _lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return o, lse


def _lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    scale_v = scale if scale is not None else q.shape[-1] ** -0.5
    bq, bk = _fwd_blocks(block_q, block_k)
    o, lse_bh = _flash_forward(q, k, v, causal, scale_v, bq, bk,
                               interpret)
    B, T, H, D = q.shape
    lse = lse_bh.reshape(B, H, T).transpose(0, 2, 1)  # [B, T, H]
    return o, lse, lse_bh


def _lse_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse, lse_bh = _lse_fwd(q, k, v, causal, scale, block_q, block_k,
                              interpret)
    return (o, lse), (q, k, v, o, lse_bh)


def _lse_bwd_rule(causal, scale, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse_bh = res
    do, dlse = cts
    B, T, H, D = q.shape
    if do is None or getattr(do, "dtype", None) == jax.dtypes.float0:
        do = jnp.zeros_like(o)
    if dlse is None or getattr(dlse, "dtype", None) == jax.dtypes.float0:
        dlse3 = None
    else:
        # [B, T, H] -> [BH, T, 1]
        dlse3 = dlse.transpose(0, 2, 1).reshape(B * H, T)[..., None]
        dlse3 = dlse3.astype(jnp.float32)
    dq_b, dkv_b = _bwd_blocks(block_q, block_k)
    bq, bk = _fwd_blocks(block_q, block_k)
    return _flash_backward(q, k, v, o, lse_bh, do, dlse3, causal, scale,
                           bq, bk, interpret,
                           dq_blocks=dq_b, dkv_blocks=dkv_b)


flash_attention_with_lse.defvjp(_lse_fwd_rule, _lse_bwd_rule)
