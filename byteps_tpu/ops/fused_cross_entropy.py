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

**What the kernels cost** (v5e: 197 TFLOP/s bf16, 819 GB/s, ridge 240
FLOPs a byte).  Five products run where the algorithm needs three — dx
and dw each form the logits again, because one backward kernel would
need dx ``[N, H]`` or dw ``[H, V]`` whole in fp32 VMEM (168 MB at
N 16 384 x H 2560) — so a roofline share counted on three products
cannot pass 60 %.  Each kernel keeps one operand's block resident along
its inner grid axis and re-reads the other operand whole once per such
block: forward and dx read the head ``N / block_n`` times, dw reads x
``V / block_v`` times.  One bf16 product does ``block`` FLOPs per
re-read byte, so a 128-row block (the parent's choice at H 2560) runs at
half the ridge: 12.7 GB a kernel, 15.6 ms of HBM time against 8.3 ms of
MXU time, measured 20.5 / 24.4 / 24.9 ms (ledger, PR 31).

**How the blocks are chosen** (``choose_blocks``; per kernel, from
``N, H, V``, the dtypes and ``_VMEM_BUDGET``; ``block_n`` / ``block_v``
override): (1) a block divides its axis — rows in multiples of 8, the
vocabulary in multiples of 128 lanes, the LARGEST such divisors and not
the gcd with a tuned default, which took gpt2's 50 304 = 2^7 x 3 x 131
from 1024 to 128 where 384 divides; (2) ``vmem_bytes`` of the step —
both streamed blocks double-buffered, the ``[bn, bv]`` fp32 logits and
their temporaries, the accumulator, its product and its output block,
the ``[bn, 1]`` columns padded to 128 lanes — stays inside 48 MiB, under
the 64 MiB the calls pass as ``vmem_limit_bytes``; (3) the resident
operand's block reaches 512 (2.1 x the ridge) wherever its axis allows;
(4) of those the least overhead: 0.35 us a grid step, and in the
forward 1.8 ns per ROW per vocabulary block — the online
log-sum-exp's ``[bn, 1]`` carries and lane reductions cost the same
whatever ``block_n`` is, so the forward wants its vocabulary block WIDE
(up to 2048) and dx / dw, which carry nothing, do not care past 512.
(5) Where a table's divisors stop short of 512 the forward alone runs a
grid of ``cdiv(V, bv)`` and masks the last block's columns past V to
-inf (a select: the buffer's stale columns are never read as numbers);
dx and dw keep the exact divisor, since wider blocks gain them nothing.

Measured with each kernel timed alone over a grid of blocks (one v5e,
PR 32; ms a call, forward / dx / dw; "least" is the products at peak):

  * N 8192, H 1024, V 50 304 (gpt2-medium; least 4.3 / 8.6 / 8.6):
    the parent's (512, 128) 11.4 / 9.8 / 11.5; chosen (512, 2048
    masked), (1024, 384), (1024, 384): 5.0 / 8.9 / 8.9 (the forward at
    the 384 divisor: 6.5).
  * N 16 384, H 2560, V 19 456 (least 8.3 / 16.6 / 16.6): the parent's
    (128, 128) 23.3 / 25.6 / 24.2; chosen (1024, 1024), (512, 1024),
    (1024, 512): 9.1 / 16.9 / 16.9.
  * N 8192, H 2048, V 16 384 (least 2.8 / 5.6 / 5.6): the parent's
    (256, 512) 3.7 / 5.9 / 5.9; chosen (512, 2048), (1024, 512),
    (512, 1024): 3.1 / 5.8 / 5.8.

dx and dw reach 97-98 % of the MXU's peak on the two products each runs;
the forward's remainder over its product is the carries.  Blocks whose
step passes ~48 MiB by ``vmem_bytes`` fall off a cliff (dx at
(1024, 1024), H 2560: 20.9 ms where (512, 1024) takes 16.9), which is
why the budget sits there and not at the limit.  One cliff the count
does not see: a masked (512, 2048) forward at H 2560 read 13.8 ms where
(512, 1792) read 9.0 (no cell's table sends the chooser there).  In the
cells' steps the three kernels read 4.9 + 8.8 + 8.9 = 22.5 ms where
30.9 ran (gpt2-medium) and 8.9 + 16.8 + 16.8 = 42.5 where 69.8 ran
(d 2560): 57 % of the three-product roofline in both.  Gauges, set when a
kernel is traced: ``fused_ce.block_n`` / ``fused_ce.block_v`` /
``fused_ce.streamed_gb`` (the re-read operand's bytes a call), each with
``kernel=fwd|dx|dw``.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.metrics import get_registry
from ._pallas_utils import fit_block as _fit, resolve_interpret

_NEG_INF = -1e30

# What the three ``pallas_call``s pass as ``vmem_limit_bytes`` (a v5e
# core has 128 MiB; the scoped default of 16 MiB does not hold a
# (512, 512) step at H 2560), and what ``vmem_bytes`` may reach under
# it.  The estimate reads 17-76 % over what Mosaic asks for at the
# cells' blocks; of the 160 blocks swept, every one on a cliff (a step
# so large the kernel lost a tenth to a quarter of its time) reads over
# this budget and every one at or under it lies on the plateau.
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BUDGET = 48 * 1024 * 1024
_LANES = 128
_ROW_CAP, _VOCAB_CAP = 1024, 2048
# The block of the operand that stays while the other is re-read: rows
# for fwd / dx, vocabulary for dw.  One bf16 product does 2 x block
# FLOPs per re-read byte of a 2-byte operand — `block` FLOPs a byte —
# against the v5e's ridge of 197 TFLOP/s / 819 GB/s = 240: 512 clears
# it 2.1 times over.
_RESIDENT_BLOCK = 512
# What a block costs beyond its products, measured on the v5e by the
# sweep in the module docstring: a grid step, and in the forward the
# online log-sum-exp's [bn, 1] carries and lane reductions, per row and
# vocabulary block whatever bn is.
_STEP_S, _CARRY_ROW_S = 0.35e-6, 1.8e-9
_PEAK_FLOPS = 197e12


def vmem_bytes(kernel: str, bn: int, bv: int, H: int,
               x_bytes: int = 2, w_bytes: int = 2) -> int:
    """VMEM one grid step of ``kernel`` (``fwd`` | ``dx`` | ``dw``) holds
    at blocks ``(bn, bv)``: the x block and the weight block
    double-buffered, the ``[bn, bv]`` fp32 logits with their
    temporaries, the fp32 accumulator with its product and the output
    block, and the ``[bn, 1]`` columns, each padded to 128 lanes."""
    x_blk = 2 * bn * H * x_bytes
    w_blk = 2 * H * bv * w_bytes
    col = -(-bn // 8) * 8 * _LANES * 4
    # logits, exp, the iota compare and its select live together
    tile = 4 * bn * bv * 4
    if kernel == "fwd":
        # targets in, lse and target logit out, three carries
        return x_blk + w_blk + tile + (3 * 2 + 3) * col
    cols = 3 * 2 * col                      # targets, lse, cotangent
    if kernel == "dx":
        acc = 2 * bn * H * 4 + 2 * bn * H * x_bytes + bn * bv * w_bytes
    else:
        acc = 2 * H * bv * 4 + 2 * H * bv * w_bytes + bn * bv * x_bytes
    return x_blk + w_blk + tile + cols + acc


def _row_blocks(N: int) -> List[int]:
    """The divisors of N that keep fp32 sublanes whole, and N itself (a
    block that spans its axis is always legal)."""
    top = min(N, _ROW_CAP)
    return sorted({b for b in range(8, top + 1, 8) if N % b == 0}
                  | ({N} if N == top else set()))


def _vocab_blocks(kernel: str, V: int) -> List[int]:
    """The multiples of 128 lanes that divide V (and V itself where one
    block spans it).  Where they stop short of ``_RESIDENT_BLOCK`` — gpt2's
    50 304 = 2^7 x 3 x 131 stops at 384 — the forward may take ANY
    multiple of 128 and mask the last block's columns past V; dx and dw
    gain nothing from a wider block (docstring) and keep the divisor."""
    top = min(V, _VOCAB_CAP)
    lanes = range(_LANES, top + 1, _LANES)
    exact = {b for b in lanes if V % b == 0} | ({V} if V == top else set())
    if (kernel == "fwd" and V % _LANES == 0
            and max(exact, default=0) < _RESIDENT_BLOCK):
        return list(lanes)
    return sorted(exact)


def _overhead_s(kernel: str, N: int, H: int, V: int, bn: int, bv: int):
    """Seconds a call spends beyond its products at blocks (bn, bv)."""
    nv = -(-V // bv)
    s = (N // bn) * nv * _STEP_S
    if kernel == "fwd":
        s += N * nv * _CARRY_ROW_S
        s += 2 * N * H * (nv * bv - V) / _PEAK_FLOPS   # columns past V
    return s


def choose_blocks(kernel: str, N: int, H: int, V: int,
                  x_bytes: int = 2, w_bytes: int = 2,
                  block_n: Optional[int] = None,
                  block_v: Optional[int] = None) -> Tuple[int, int]:
    """``(block_n, block_v)`` of ``kernel`` (``fwd`` | ``dx`` | ``dw``) at
    ``x: [N, H]``, ``w: [H, V]``: of the pairs that divide their axes (the
    forward's vocabulary block may leave a masked remainder, see
    ``_vocab_blocks``) and whose ``vmem_bytes`` is inside the budget,
    first those whose resident operand's block reaches
    ``_RESIDENT_BLOCK`` as far as its axis allows, then the least
    ``_overhead_s``, then the larger resident block.  An explicit block is kept (fitted to its axis
    as before); with both given nothing is chosen or checked.  Raises
    where no pair fits: a table coprime to 128, or an H whose smallest
    step is over the budget."""
    rows = [_fit(block_n, N, "rows")] if block_n else _row_blocks(N)
    vocab = ([_fit(block_v, V, "vocabulary")] if block_v
             else _vocab_blocks(kernel, V))
    if block_n and block_v:
        return rows[0], vocab[0]
    fits = [(bn, bv) for bn in rows for bv in vocab
            if vmem_bytes(kernel, bn, bv, H, x_bytes, w_bytes)
            <= _VMEM_BUDGET]
    if not fits:
        raise ValueError(
            f"fused_ce_{kernel}: no (rows, vocabulary) blocks divide "
            f"N={N}, V={V} and fit {_VMEM_BUDGET >> 20} MiB of VMEM at "
            f"H={H}; pad the table to a multiple of 128 (or pass explicit "
            f"block sizes)")
    held = 1 if kernel == "dw" else 0
    return max(fits, key=lambda b: (
        min(b[held], _RESIDENT_BLOCK), -_overhead_s(kernel, N, H, V, *b),
        b[held]))


def _blocks(kernel, x, w, block_n, block_v):
    """The kernel's blocks, with their record in the registry."""
    N, H = x.shape
    V = w.shape[1]
    bn, bv = choose_blocks(kernel, N, H, V, x.dtype.itemsize,
                           w.dtype.itemsize, block_n, block_v)
    reg = get_registry()
    reg.gauge("fused_ce.block_n", kernel=kernel).set(bn)
    reg.gauge("fused_ce.block_v", kernel=kernel).set(bv)
    reread = (V // bv * N * H * x.dtype.itemsize if kernel == "dw"
              else N // bn * H * V * w.dtype.itemsize)
    reg.gauge("fused_ce.streamed_gb", kernel=kernel).set(reread / 1e9)
    return bn, bv


def _fwd_kernel(x_ref, w_ref, tgt_ref, lse_ref, tl_ref,
                m_ref, l_ref, t_ref, *, nv: int, block_v: int, V: int):
    # x_ref [BN, H]; w_ref [H, BV]; tgt_ref [BN, 1] (int32, SMEM-ish VMEM);
    # outs: lse_ref [BN, 1], tl_ref [BN, 1]; scratch m/l/t [BN, 1] f32
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    def block(ragged: bool):
        logits = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BN, BV] fp32
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        if ragged:
            # the last block's columns past V hold whatever the buffer
            # held: a select, so that not even a NaN there is read
            logits = jnp.where(col < V - j * block_v, logits, _NEG_INF)

        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

        # gather the target logit when its vocab block streams by
        tgt_local = tgt_ref[...] - j * block_v          # [BN, 1] int32
        hit = (col == tgt_local)                        # [BN, BV]
        t_ref[...] = t_ref[...] + jnp.sum(
            jnp.where(hit, logits, 0.0), axis=-1, keepdims=True)

    if V % block_v == 0:
        block(ragged=False)
    else:
        pl.when(j < nv - 1)(lambda: block(ragged=False))
        pl.when(j == nv - 1)(lambda: block(ragged=True))

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


def _params(interpret):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _fwd_call(x, w, tgt, bn, bv, interpret):
    N, H = x.shape
    V = w.shape[1]
    nv = pl.cdiv(V, bv)
    row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nv=nv, block_v=bv, V=V),
        name="fused_ce_fwd",
        grid=(N // bn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0)),   # x block
            pl.BlockSpec((H, bv), lambda i, j: (0, j)),   # W vocab block
            row,                                          # targets
        ],
        out_specs=[row, row],                             # lse, target logit
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        **_params(interpret),
    )(x, w, tgt)


def _dx_call(x, w, tgt, lse, dl, bn, bv, interpret):
    N, H = x.shape
    V = w.shape[1]
    nv = V // bv
    row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_dx_kernel, nv=nv, block_v=bv),
        name="fused_ce_bwd_dx",
        grid=(N // bn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0)),
            pl.BlockSpec((H, bv), lambda i, j: (0, j)),
            row, row, row,
        ],
        out_specs=pl.BlockSpec((bn, H), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, H), jnp.float32)],
        **_params(interpret),
    )(x, w, tgt, lse, dl)


def _dw_call(x, w, tgt, lse, dl, bn, bv, interpret):
    N, H = x.shape
    V = w.shape[1]
    nn = N // bn
    row = pl.BlockSpec((bn, 1), lambda vi, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_dw_kernel, nn=nn, block_v=bv),
        name="fused_ce_bwd_dw",
        grid=(V // bv, nn),
        in_specs=[
            pl.BlockSpec((H, bv), lambda vi, i: (0, vi)),
            pl.BlockSpec((bn, H), lambda vi, i: (i, 0)),
            row, row, row,
        ],
        out_specs=pl.BlockSpec((H, bv), lambda vi, i: (0, vi)),
        out_shape=jax.ShapeDtypeStruct((H, V), w.dtype),
        scratch_shapes=[pltpu.VMEM((H, bv), jnp.float32)],
        **_params(interpret),
    )(w, x, tgt, lse, dl)


def _valid(targets, V):
    # ignore-index semantics: any target outside [0, V) — e.g. the HF
    # convention of -100 for padded tokens — contributes loss 0 and, via
    # the same mask on the loss cotangent in the backward, zero gradient
    return (targets >= 0) & (targets < V)


def _fce_forward(x, w, targets, block_n, block_v, interpret):
    interpret = resolve_interpret(
        interpret, "fused_linear_cross_entropy forward")
    N, H = x.shape
    H2, V = w.shape
    assert H == H2, (x.shape, w.shape)
    bn, bv = _blocks("fwd", x, w, block_n, block_v)
    tgt = targets.astype(jnp.int32).reshape(N, 1)
    lse, tl = _fwd_call(x, w, tgt, bn, bv, interpret)
    loss = jnp.where(_valid(targets, V), (lse - tl)[:, 0], 0.0)
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
    ``block_n``/``block_v`` are overrides: left ``None``, each of the
    three kernels takes the blocks ``choose_blocks`` reads from the
    shape; given, all three use them as they are.
    """
    loss, _ = _fce_forward(x, w, targets, block_n, block_v, interpret)
    return loss


def _fce_fwd_rule(x, w, targets, block_n, block_v, interpret):
    loss, lse = _fce_forward(x, w, targets, block_n, block_v, interpret)
    return loss, (x, w, targets, lse)


def _fce_bwd_rule(block_n, block_v, interpret, res, dloss):
    x, w, targets, lse = res
    interpret = resolve_interpret(
        interpret, "fused_linear_cross_entropy backward")
    N = x.shape[0]
    tgt = targets.astype(jnp.int32).reshape(N, 1)
    # ignored rows get a zero cotangent: dlogits = (softmax - onehot) * 0
    dl = dloss.astype(jnp.float32).reshape(N, 1) * _valid(tgt, w.shape[1])
    dx = _dx_call(x, w, tgt, lse, dl,
                  *_blocks("dx", x, w, block_n, block_v), interpret)
    dw = _dw_call(x, w, tgt, lse, dl,
                  *_blocks("dw", x, w, block_n, block_v), interpret)
    return dx, dw, None


fused_linear_cross_entropy.defvjp(_fce_fwd_rule, _fce_bwd_rule)
