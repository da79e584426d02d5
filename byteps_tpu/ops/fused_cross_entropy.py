"""Fused output-projection + softmax cross-entropy (Pallas TPU kernels).

The second memory-bound hot op of LM training after attention: the naive
path materializes ``logits = x @ W`` of shape [N, V] in HBM (N = B*T,
V = vocab) three times over (forward value, softmax, backward) — at
V=32k, N=8k bf16 that is ~0.5 GB per materialization.  These kernels
stream vocab blocks through VMEM instead and never form the full logits:

  * forward — grid (N blocks, V blocks), V innermost ("arbitrary"):
    logits block = x_blk @ W_vblk on the MXU, online logsumexp carry in
    VMEM scratch, the target's logit gathered via an iota-mask row-sum
    when its vocab block streams by.  loss = lse - target_logit.
  * backward — dlogits(i,v) = (softmax - onehot) * dloss(i) is
    recomputed blockwise from the saved lse:
      - dx kernel: grid (Nb, Vb) accumulates dx_blk += dlogits @ W_vblkᵀ
      - dW kernel: grid (Vb, Nb) accumulates dW_vblk += x_blkᵀ @ dlogits

Same kernel discipline as ops/flash_attention.py: dots in the input
dtype (bf16 MXU passes) with fp32 accumulation, carries in VMEM scratch,
the innermost grid dim declared "arbitrary" so Mosaic pipelines the
HBM→VMEM operand copies against compute.

The reference has no analog (its examples pay the full logits cost);
this is TPU-first design territory, the counterpart of SURVEY.md §7's
"Pallas kernels for the hot ops" mandate.

Measured on 1x TPU v5e (bf16):
  * forward only — FASTER than XLA's fused naive path (5.4 vs 5.8 ms at
    N=8k, H=768, V=32k) while never allocating the [N, V] buffer: the
    right choice for eval/perplexity loops.
  * forward+backward — the backward trades FLOPs for memory (it
    recomputes logits blockwise in each of the dx and dW passes: 10·NHV
    total vs naive's 6·NHV) and runs at ~92% of the chip's bf16 peak on
    those FLOPs, which nets out ~1.1-1.5x slower than naive end-to-end
    (14.5 vs 12.9 ms at the config above).  Use it when the logits
    buffer is the binding constraint — it frees O(N·V) HBM (e.g. 8.6 GB
    at N=16k, V=128k) for bigger batches or models; otherwise the naive
    path is the faster choice on TPU, where XLA already fuses the
    softmax into the matmul epilogue.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_utils import fit_block as _fit, resolve_interpret

# tuned on v5e at H=768, V=32k; explicit user blocks bypass the VMEM caps
DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_V = 1024
_NEG_INF = -1e30


def _auto_blocks(H: int, block_n: Optional[int],
                 block_v: Optional[int]) -> Tuple[int, int]:
    """Resolve block sizes.  ``None`` means auto: the tuned default,
    capped so the per-program VMEM footprint stays safe as H grows (the
    dx accumulator is [BN, H] fp32, the W block [H, BV] bf16 — ~2 MB
    budget each; at H=768 the defaults pass through, at H=2048 this
    lands on (256, 512), measured working on v5e).  Explicit values are
    honored untouched — the caller owns VMEM fit and divisibility."""
    if block_n is None:
        block_n = min(DEFAULT_BLOCK_N,
                      max(128, ((2 << 20) // (4 * H)) // 128 * 128))
    if block_v is None:
        block_v = min(DEFAULT_BLOCK_V,
                      max(256, ((2 << 20) // (2 * H)) // 128 * 128))
    return block_n, block_v


def _fwd_kernel(x_ref, w_ref, tgt_ref, lse_ref, tl_ref,
                m_ref, l_ref, t_ref, *, nv: int, block_v: int):
    # x_ref [BN, H]; w_ref [H, BV]; tgt_ref [BN, 1] (int32, SMEM-ish VMEM);
    # outs: lse_ref [BN, 1], tl_ref [BN, 1]; scratch m/l/t [BN, 1] f32
    j = pl.program_id(1)
    block_n = x_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BN, BV] fp32

    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    # gather the target logit when its vocab block streams by
    tgt_local = tgt_ref[...] - j * block_v              # [BN, 1] int32
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    hit = (col == tgt_local)                            # [BN, BV]
    t_ref[...] = t_ref[...] + jnp.sum(
        jnp.where(hit, logits, 0.0), axis=-1, keepdims=True)

    @pl.when(j == nv - 1)
    def _finish():
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        tl_ref[...] = t_ref[...]


def _dx_kernel(x_ref, w_ref, tgt_ref, lse_ref, dl_ref, dx_ref, acc_ref,
               *, nv: int, block_v: int):
    # dx_blk = sum_v (softmax - onehot) * dloss @ W_vblkᵀ ; acc [BN, H] f32
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BN, BV]
    p = jnp.exp(logits - lse_ref[...])                  # softmax block
    tgt_local = tgt_ref[...] - j * block_v
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    dlogits = (p - jnp.where(col == tgt_local, 1.0, 0.0)) * dl_ref[...]
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        dlogits.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BN, H]

    @pl.when(j == nv - 1)
    def _finish():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _dw_kernel(w_ref, x_ref, tgt_ref, lse_ref, dl_ref, dw_ref, acc_ref,
               *, nn: int, block_v: int):
    # grid (Vb, Nb): dW_vblk = sum_n x_blkᵀ @ dlogits_blk ; acc [H, BV] f32
    vi = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    logits = jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BN, BV]
    p = jnp.exp(logits - lse_ref[...])
    tgt_local = tgt_ref[...] - vi * block_v
    col = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    dlogits = (p - jnp.where(col == tgt_local, 1.0, 0.0)) * dl_ref[...]
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        x, dlogits.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [H, BV]

    @pl.when(i == nn - 1)
    def _finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _fce_forward(x, w, targets, block_n, block_v, interpret):
    interpret = resolve_interpret(
        interpret, "fused_linear_cross_entropy forward")
    N, H = x.shape
    H2, V = w.shape
    assert H == H2, (x.shape, w.shape)
    block_n, block_v = _auto_blocks(H, block_n, block_v)
    bn = _fit(block_n, N)
    bv = _fit(block_v, V)
    nv = V // bv
    tgt = targets.astype(jnp.int32).reshape(N, 1)

    lse, tl = pl.pallas_call(
        functools.partial(_fwd_kernel, nv=nv, block_v=bv),
        name="fused_ce_fwd",
        grid=(N // bn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0)),   # x block
            pl.BlockSpec((H, bv), lambda i, j: (0, j)),   # W vocab block
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),   # targets
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),   # lse
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),   # target logit
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, tgt)
    # ignore-index semantics: any target outside [0, V) — e.g. the HF
    # convention of -100 for padded tokens — contributes loss 0 (and, via
    # the same mask on the loss cotangent in the backward, zero gradient)
    valid = (targets >= 0) & (targets < V)
    loss = jnp.where(valid, (lse - tl)[:, 0], 0.0)
    return loss, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_linear_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    targets: jax.Array,
    block_n: Optional[int] = None,
    block_v: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-row softmax cross-entropy of ``x @ w`` against integer
    ``targets``, without materializing the [N, V] logits.

    ``x: [N, H]``, ``w: [H, V]``, ``targets: [N]`` → ``loss: [N]``
    (take ``.mean()`` for the usual reduction).  Targets outside
    ``[0, V)`` (e.g. the HF ``-100`` padding convention) are ignored:
    loss 0 and zero gradient for those rows.  Differentiable in x and w;
    the backward recomputes logits blockwise from the saved lse.
    ``block_n``/``block_v`` default to tuned, VMEM-capped sizes; explicit
    values are used as-is.
    """
    loss, _ = _fce_forward(x, w, targets, block_n, block_v, interpret)
    return loss


def _fce_fwd_rule(x, w, targets, block_n, block_v, interpret):
    loss, lse = _fce_forward(x, w, targets, block_n, block_v, interpret)
    return loss, (x, w, targets, lse)


def _fce_bwd_rule(block_n, block_v, interpret, res, dloss):
    x, w, targets, lse = res
    interpret_b = resolve_interpret(
        interpret, "fused_linear_cross_entropy backward")
    N, H = x.shape
    V = w.shape[1]
    block_n, block_v = _auto_blocks(H, block_n, block_v)
    bn = _fit(block_n, N)
    bv = _fit(block_v, V)
    nv = V // bv
    nn = N // bn
    tgt = targets.astype(jnp.int32).reshape(N, 1)
    # ignored rows (target outside [0, V)) get a zero cotangent: dlogits =
    # (softmax - onehot) * 0 — no gradient flows from them to x or W
    valid = (tgt >= 0) & (tgt < V)
    dl = dloss.astype(jnp.float32).reshape(N, 1) * valid
    arb = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, nv=nv, block_v=bv),
        name="fused_ce_bwd_dx",
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0)),
            pl.BlockSpec((H, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, H), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, H), jnp.float32)],
        compiler_params=arb,
        interpret=interpret_b,
    )(x, w, tgt, lse, dl)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, nn=nn, block_v=bv),
        name="fused_ce_bwd_dw",
        grid=(nv, nn),
        in_specs=[
            pl.BlockSpec((H, bv), lambda vi, i: (0, vi)),
            pl.BlockSpec((bn, H), lambda vi, i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda vi, i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda vi, i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda vi, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((H, bv), lambda vi, i: (0, vi)),
        out_shape=jax.ShapeDtypeStruct((H, V), w.dtype),
        scratch_shapes=[pltpu.VMEM((H, bv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret_b,
    )(w, x, tgt, lse, dl)

    return dx, dw, None


fused_linear_cross_entropy.defvjp(_fce_fwd_rule, _fce_bwd_rule)
