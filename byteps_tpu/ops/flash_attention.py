"""Pallas TPU flash attention (forward + backward kernels).

The hot op of the transformer stack, written for the MXU/VMEM rather than
translated from any CUDA kernel.  Every kernel shares one structure:
a 3-D grid ``(batch*heads, outer blocks, inner blocks)`` whose innermost
dim is declared "arbitrary" so Mosaic pipelines the inner-operand
HBM→VMEM copies against compute — no [T, T] score matrix ever
materializes in HBM.  Across inner steps the carry (online-softmax acc /
max / sum, or the dq/dk/dv partials) lives in VMEM scratch; where the
inner axis has ONE block (T <= the block: the whole sequence in one
step) there is nothing to carry, no scratch is allocated, and a step
writes its outputs itself.

Causal and sliding-window masking prune at **two granularities**:

  * *grid block* ``[BQ, BK]`` (1024 x 1024 by default, clamped to T): a
    block wholly outside the band does nothing, and a clamped BlockSpec
    index map elides its DMAs (an unchanged block index between
    consecutive grid steps performs no copy).  That alone prunes nothing
    when T <= the block — one block a head, the whole score square;
  * *sub-tile* ``s x s`` inside a grid step (``_SUB_TILE``): the visit
    rule (``_k_tile_range`` / ``_q_tile_range`` / ``_needs_mask``, plain
    Python) says which sub-tiles the band touches.  A sub-tile strictly
    above the diagonal or wholly left of the window is never computed
    (no QK^T, no exp, no PV / dP / dS matmul); one strictly inside the
    band is computed without iotas or the mask; only sub-tiles the
    band's edge crosses (and every tile under ``segment_ids`` / ALiBi)
    take the mask path.  Non-causal attention visits every sub-tile.

The visited sub-tiles of a step are not walked one by one: they are
gathered into **strips** (``_strips``) — for the forward (and the dq
kernel of long sequences) a band of q rows against ALL the k sub-tiles it
visits, for the backward kernel a band of k columns against all the q
sub-tiles that visit it —
and a strip is one matmul per product, so each row's softmax bookkeeping
(max, sum, exp of the carry) runs once a step, as it did for the whole
block, and the MXU sees the longest operands the band allows.  Rows (or
columns) that visit the same range the same way share a strip: a block
wholly inside the band, and every non-causal block, is ONE strip — the
whole block, exactly the pre-sub-tile kernel.  A step's position enters
the rule only through the offset ``d = first q row - first k column``
of its block, so the kernels are specialised at trace time for each
distinct plan over the grid's offsets (``_step_plans``: for causal
attention two — the diagonal block's ragged strips and the interior
block's single strip), selected by ``pl.when`` on ``d`` where the grid
has more than one block and folded away where it has one.  Walking the
sub-tiles in ``lax.fori_loop`` / ``lax.cond`` with bounds from the
program ids, or unrolled tile by tile with the online softmax carried
across k sub-tiles, was measured and lost (see the tuning notes below).
Each ``pallas_call`` build records what it will visit in
``flash.tiles_visited`` / ``flash.tiles_total`` (gauges labelled
``kernel=fwd|bwd`` — ``bwd_dq`` and ``bwd_dkv`` where the pair is built —
and ``window=<W>|none``, the band, so that the two attention kinds of a
window/global hybrid keep a record each: sub-tiles per head over the
whole grid; ``tile_visits`` is the count, shared with the tests).  A
windowed build's kernels carry the band in their names too
(``flash_fwd_w4096``, ``flash_bwd_dq_flash_bwd_dkv_w4096``).

The ``pallas_call`` objects are built once per static configuration
(``_forward_call`` / ``_dkv_call`` / ``_dq_call``, ``lru_cache``): a
``pallas_call`` is a ``jit`` of its own, so the layers of a model that
share a configuration share one trace of the kernel and one lowering to
Mosaic, where a fresh object a layer re-traced and re-lowered each of the
kernels of a 24-layer step (1.2 s + 1.6 s of the sandbox's
trace-and-lower for the whole-block kernels, 1.8 s + 2.0 s for the strip
kernels, 0.1 s + 0.1 s cached; PR 26).

Backward is a custom_vjp with residuals (q, k, v, o, lse, segment_ids)
and **one Pallas kernel**, ``flash_bwd_dq_flash_bwd_dkv``
(``_bwd_dkv_kernel`` with ``fused``) — grid (batch*heads, k blocks, q
blocks): per strip of k columns it forms S = q·kᵀ, P, dP = do·vᵀ and dS
ONCE and takes all three gradients from them — dv += Pᵀ·do, dk += dSᵀ·q,
dq += dS·k: 5 products where the split below runs 7 (PR 30).  dk / dv
of a k block are carried across the q blocks in
``[bk, D]`` scratch; a q row's dq gathers contributions from every strip
and every k block of the band, so it adds into a whole-sequence fp32
``[T, D]`` VMEM accumulator that lives for the head's whole grid row and
is scaled and cast into the head's dq block at its last step.  That
accumulator is what bounds the kernel: where ``T x D`` outgrows
``_FUSED_BWD_DQ_BYTES`` (``_fused_bwd_fits``: read from the shape, no
argument or switch) the backward is the flash-attention-2 split instead,
whose peak memory stays O(T * block) like the forward's — at the price
of recomputing S and dP in both kernels, 7 products:

  * ``_bwd_dq_kernel`` (``flash_bwd_dq``) — grid (batch*heads, q blocks,
    k blocks): recomputes the scores strip by strip and accumulates dq;
  * ``_bwd_dkv_kernel`` (``flash_bwd_dkv``) — grid (batch*heads, k
    blocks, q blocks): accumulates dk/dv for its k block across the
    q-block dim.

Gauge ``flash.bwd_fused`` (1 / 0, labelled ``window=`` like the tile
gauges) says which the last build of that band took.  Combined
with ``parallel/ring_attention.py`` (which shards T across chips and calls
this kernel per ring block — ``attn_impl="flash"`` composes with the ``sp``
axis) this covers both the single-chip memory story and the multi-chip
long-context story.

Layout convention matches the rest of the stack: ``[B, T, H, D]``.
``D`` should be a multiple of the 128-lane width for full MXU utilization
(64 works; the compiler pads).  v (and o, do, dv) carry a head width of
their own, ``Dv``: latent attention's keys are 192 wide (128 + the shared
64 rotary channels) and its values 128, and padding v to 192 would waste
a third of the PV, dV and dP products.  The builders are keyed on both
widths; where they are equal nothing differs from a one-width kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.metrics import get_registry
from ._pallas_utils import fit_block as _fit_block_impl, resolve_interpret

# Grid blocks, of every kernel of a call.  (1024, 1024) comes from sweeps
# at T=4096 bf16 (D=64 and D=128) on a TPU v5e under an EARLIER toolchain
# (it beat (512, 1024) by ~3-4% and (128, 128) by >4x; every smaller or
# rectangular backward shape lost 2-70%, larger ones failed VMEM).
# Re-swept for the single backward kernel on one TPU v5e, jax 0.9.0 /
# libtpu 0.0.34 (PR 30; ms per call of flash_bwd_dq_flash_bwd_dkv,
# causal, (block_q, block_k)):
#   B1 T8192 H32 D192/128: (1024, 1024) 12.76   (512, 2048) 12.85
#     (2048, 512) 13.13   (512, 1024) 13.36   (1024, 512) 13.37
#     (512, 512) 15.25   (2048, 1024) 35.35   (1024, 2048) 35.23
#   B2 T4096 H8 D128: (1024, 2048) 1.099   (1024, 1024) 1.126
#     (2048, 1024) 1.146   (512, 1024) 1.185   (1024, 512) 1.193
#   B8 T1024 H16 D64: (1024, 1024) 0.752   (1024, 512) 0.838
#     (512, 1024) 0.842   (512, 512) 0.993
# Nothing beats (1024, 1024) by 3 %, so one pair of blocks serves the
# forward and the backward.  Both clamp to T, so T <= 1024 is one block
# a head.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# The single backward kernel keeps a head's whole dq in VMEM: an fp32
# [T, D] accumulator beside the two buffers of its [T, D] output block
# (8.4 + 8.4 MB at T 8192 / D 192, 0.5 + 0.5 MB at T 1024 / D 64: rows
# are padded to whole 128-lane tiles).  It is built where those fit
# _FUSED_BWD_DQ_BYTES and compiled under _FUSED_BWD_VMEM_LIMIT (a v5e core
# has 128 MiB; the scoped default of 16 MiB holds the strips' fp32
# temporaries and the blocks, not the accumulator as well); longer
# sequences — past 32k positions at D <= 128, 16k at D 192 in bf16 — take
# the dq and dk/dv kernels (_fused_bwd_fits).  Both edges compile for the
# v5e (compile-only topology, PR 30).
# Per call on one TPU v5e, jax 0.9.0 / libtpu 0.0.34 (PR 30; device ms
# from the profiler; fwd / dq + dk/dv -> fwd / single backward):
#   B8 T1024 H16 D64 causal   0.335 / 0.355 + 0.584 -> 0.335 / 0.752 (-20 %)
#   B1 T8192 H32 D192/128     6.391 / 8.682 + 9.744 -> 6.369 / 12.764 (-31 %)
#   B2 T4096 H8 D128          0.621 / 0.671 + 0.891 -> 0.621 / 1.127 (-28 %)
#   B4 T2048 H16 D64          0.778 / 0.787 + 1.058 -> 0.778 / 1.313 (-29 %)
#   B8 T1024 H16 D64 full     0.474 / 0.550 + 0.742 -> 0.474 / 0.954 (-26 %)
# = the dk/dv kernel plus one product at the rate it ran before (9.744 x
# 832 / 640 = 12.67 in head-width units at D 192 / 128).  What lost:
# accumulating dq in a resident fp32 OUTPUT block (no scratch, no last
# cast; the cast rides the unfold outside) — the kernel alone 0.723 at
# T1024 but 12.925 at T8192, and the whole backward with its XLA ops
# 1.733 against 1.719 and 22.38 against 21.63: the fp32 [B*H, T, D]
# round trip through HBM costs more than the in-kernel cast.
_FUSED_BWD_DQ_BYTES = 32 * 1024 * 1024
_FUSED_BWD_VMEM_LIMIT = 64 * 1024 * 1024
# Edge s of the square sub-tiles a grid block is cut into for pruning.
# Swept on one TPU v5e, jax 0.9.0 / libtpu 0.0.34 (PR 26; ms per call,
# fwd / dq / dkv, device time from the profiler; "whole" = no sub-tiles):
#   B8 T1024 H16 D64 causal (the train cells; one block a head)
#     parent 0.438 / 0.585 / 0.821 = 1.844    whole 0.414 / 0.550 / 0.731
#     s=128  0.385 / 0.351 / 0.969            s=512 0.329 / 0.419 / 0.578
#     s=256  0.334 / 0.355 / 0.584 = 1.272 (-31 %; visits 10 of 16)
#   B2 T4096 H8 D128 causal (4 x 4 grid; 4 diagonal blocks of 10 prune)
#     parent 0.644 / 0.755 / 1.002 = 2.401    s=128 2.412   s=512 2.189
#     s=256  0.615 / 0.653 / 0.909 = 2.177 (-9 %)
#   same, window 1024: parent 1.832, s=256 1.416 (-23 %), s=512 1.489
#   B4 T2048 H16 D64 causal: parent 3.055, s=256 2.624 (-14 %), s=512 2.656
#   B8 T1024 H16 D64 non-causal: parent 1.962, any s 1.765 (one strip; the
#     -10 % is the scratch carry that one-block grids no longer keep)
# No kernel prefers another s by more than 3 % at either shape, so one
# constant serves all three.  What lost on the way, at B8 T1024 H16 D64:
# visiting sub-tiles one at a time with the online softmax carried across
# k sub-tiles (unrolled; s=256) ran the forward at 0.739 ms — the
# [s, 1] max/sum/rescale pass per TILE costs more than the pruned tiles
# save — and the same walk under lax.fori_loop / lax.cond from program
# ids ran T4096 D128 at 6.78 ms against the parent's 2.40.  Strips that
# still round-trip their state through VMEM scratch: forward 0.561.
_SUB_TILE = 256
_NEG_INF = -1e30
FLASH_OUT = "flash_out"     # checkpoint name of the forward's o and lse
_ARBITRARY_INNER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_blocks(block_q, block_k):
    """Resolve the public ``None`` block defaults to the tuned shapes
    (the forward's and the backward's alike)."""
    return (DEFAULT_BLOCK_Q if block_q is None else block_q,
            DEFAULT_BLOCK_K if block_k is None else block_k)


def _fit_block(block: int, T: int) -> int:
    return _fit_block_impl(block, T, what="seq len")


def _causal_last_k(qi, block_q: int, block_k: int, nk: int):
    """Last k-block index that intersects the causal lower triangle of q
    block ``qi``: floor(((qi+1)*BQ - 1) / BK), clamped to the grid."""
    return jnp.minimum((qi * block_q + block_q - 1) // block_k, nk - 1)


def _window_first_k(qi, block_q: int, block_k: int, window: int):
    """First k-block index that intersects the sliding-window band of q
    block ``qi``: floor((qi*BQ - (W-1)) / BK), clamped to 0."""
    return jnp.maximum((qi * block_q - (window - 1)) // block_k, 0)


# ---------------------------------------------------------------------------
# The visit rule: which sub-tiles of a grid block the band touches.  Pure
# Python on ints — a grid step's position enters only through the offset
# d = (first q row) - (first k column) of its block, and the kernels are
# specialised at trace time for each distinct answer (``_step_plans``).
# ---------------------------------------------------------------------------

def _sub_tile(bq: int, bk: int):
    """(sq, sk): the sub-tile of a [bq, bk] grid block — the tuned square
    where its edge divides the block's side; a side it does not divide
    (short or odd T) is not split."""
    s = _SUB_TILE
    return (s if bq % s == 0 else bq, s if bk % s == 0 else bk)


def _k_tile_range(r0: int, c_base: int, nks: int, sq: int, sk: int,
                  causal, window):
    """``[lo, hi)``: the k sub-tiles (``nks`` of ``sk`` columns, from
    column ``c_base``) that q rows ``[r0, r0 + sq)`` visit — up to the one
    holding the last row's diagonal, from the one holding the first row's
    window start.  Empty (``hi <= lo``) when the block is outside the
    band."""
    lo, hi = 0, nks
    if causal:
        hi = min(nks, max(r0 + sq - 1 - c_base + sk, 0) // sk)
        if window is not None:
            lo = max(r0 - (window - 1) - c_base, 0) // sk
    return lo, hi


def _q_tile_range(c0: int, r_base: int, nqs: int, sq: int, sk: int,
                  causal, window):
    """``[lo, hi)``: the q sub-tiles (``nqs`` of ``sq`` rows, from row
    ``r_base``) that visit k columns ``[c0, c0 + sk)`` — the transpose of
    ``_k_tile_range``, for the dk/dv kernel."""
    lo, hi = 0, nqs
    if causal:
        lo = max(c0 - r_base, 0) // sq
        if window is not None:
            hi = min(nqs, max(
                c0 + sk - 1 + (window - 1) - r_base + sq, 0) // sq)
    return lo, hi


def _needs_mask(r0: int, c0: int, sq: int, sk: int, causal, window) -> bool:
    """Does the band's edge cross the visited tile rows ``[r0, r0+sq)`` x
    cols ``[c0, c0+sk)`` — i.e. is any of its positions masked?"""
    if not causal:
        return False
    crossed = c0 + sk - 1 > r0
    if window is not None:
        crossed = crossed or r0 + sq - 1 - c0 >= window
    return crossed


def tile_visits(T: int, bq: int, bk: int, s, causal: bool, window=None):
    """The visit rule as a count: ``({(qi, kj): visited}, total)`` — for
    every grid block of a ``T x T`` score square cut into ``[bq, bk]``
    blocks, how many of its ``total`` sub-tiles (``s x s``, or ``s =
    (sq, sk)``) the kernels compute."""
    sq, sk = (s, s) if isinstance(s, int) else s
    nqs, nks = bq // sq, bk // sk
    visited = {}
    for qi in range(T // bq):
        for kj in range(T // bk):
            n = 0
            for a in range(nqs):
                lo, hi = _k_tile_range(qi * bq + a * sq, kj * bk, nks, sq,
                                       sk, causal, window)
                n += max(hi - lo, 0)
            visited[(qi, kj)] = n
    return visited, nqs * nks


def _band_label(window) -> str:
    """The ``window=`` label of the gauges: a model with two attention
    kinds builds each kernel once a band, and neither build may
    overwrite the other's record."""
    return "none" if window is None else str(window)


def _band_name(name: str, window) -> str:
    """A windowed build's kernel carries its band in its ``name=``
    (``flash_fwd_w4096``), so a trace tells the kinds of one model apart
    while ``flash_fwd`` / ``flash_bwd`` still match both."""
    return name if window is None else f"{name}_w{window}"


def _record_tiles(kernel: str, T, bq, bk, sub, causal, window) -> None:
    """Trace-time record of what this ``pallas_call`` build visits: tiles
    per head, summed over the grid."""
    visited, total = tile_visits(T, bq, bk, sub, causal, window)
    reg = get_registry()
    band = _band_label(window)
    reg.gauge("flash.tiles_visited", kernel=kernel, window=band).set(
        sum(visited.values()))
    reg.gauge("flash.tiles_total", kernel=kernel, window=band).set(
        total * len(visited))


def _strips(d: int, n_outer: int, span, always_mask: bool):
    """The work of a grid step at offset ``d`` as strips ``(t0, t1, lo, hi,
    masked)``: outer sub-tiles ``[t0, t1)`` against the inner sub-tiles
    ``[lo, hi)`` they visit (``span(d, t) -> lo, hi, mask flag per inner
    tile``), computed as ONE matmul each — the softmax bookkeeping runs
    once per row and the MXU sees the longest operands the band allows.
    Neighbouring outer tiles that visit the same range the same way share
    a strip, so a block wholly inside the band (and every non-causal one)
    is a single strip: the whole block."""
    strips = []
    for t in range(n_outer):
        lo, hi, masked = span(d, t)
        if hi <= lo:
            continue
        masked = tuple(always_mask or m for m in masked)
        if strips and strips[-1][1] == t and strips[-1][2:] == (lo, hi,
                                                                 masked):
            strips[-1] = (strips[-1][0], t + 1, lo, hi, masked)
        else:
            strips.append((t, t + 1, lo, hi, masked))
    return tuple(strips)


def _step_plans(nq: int, nk: int, bq: int, bk: int, strips_at):
    """``[(d_lo, d_hi, strips)]``: the distinct non-empty plans over the
    grid's offsets ``d = qi*bq - kj*bk``, each with the run of offsets it
    serves (plans change monotonically with ``d``: skipped, diagonal,
    interior, window edge, skipped)."""
    runs = []
    for d in sorted({qi * bq - kj * bk
                     for qi in range(nq) for kj in range(nk)}):
        strips = strips_at(d)
        if runs and runs[-1][2] == strips:
            runs[-1] = (runs[-1][0], d, strips)
        else:
            runs.append((d, d, strips))
    return [run for run in runs if run[2]]


def _q_major_plans(nq, nk, bq, bk, sub, causal, window, always_mask):
    """Plans of the forward and dq kernels: a strip is q sub-tiles (rows)
    against the k sub-tiles they visit."""
    sq, sk = sub

    def span(d, a):
        lo, hi = _k_tile_range(d + a * sq, 0, bk // sk, sq, sk, causal,
                               window)
        return lo, hi, [_needs_mask(d + a * sq, b * sk, sq, sk, causal,
                                    window) for b in range(lo, hi)]

    return _step_plans(nq, nk, bq, bk, lambda d: _strips(
        d, bq // sq, span, always_mask))


def _k_major_plans(nq, nk, bq, bk, sub, causal, window, always_mask):
    """Plans of the dk/dv kernel: a strip is k sub-tiles (columns)
    against the q sub-tiles that visit them."""
    sq, sk = sub

    def span(d, b):
        lo, hi = _q_tile_range(b * sk, d, bq // sq, sq, sk, causal, window)
        return lo, hi, [_needs_mask(d + a * sq, b * sk, sq, sk, causal,
                                    window) for a in range(lo, hi)]

    return _step_plans(nq, nk, bq, bk, lambda d: _strips(
        d, bk // sk, span, always_mask))


def _grid_pos(axis: int, n: int):
    """This step's index along a grid axis: 0 where the axis has one
    block (a Python int, so conditions on it fold), else the program
    id."""
    return 0 if n == 1 else pl.program_id(axis)


def _when(cond):
    """``pl.when`` that folds a trace-time condition."""
    if isinstance(cond, bool):
        return lambda f: f() if cond else None
    return pl.when(cond)


def _for_each_strip(plans, d, body):
    """Run ``body(t0, t1, lo, hi, masked)`` over the strips of the plan
    that serves offset ``d`` (a Python int, or traced from program ids)."""
    for d_lo, d_hi, strips in plans:
        @_when((d >= d_lo) & (d <= d_hi))
        def _(strips=strips):
            for strip in strips:
                body(*strip)


def _band_mask(s, row, col, causal: bool, window):
    """Apply causal and/or sliding-window masking to a score block."""
    if causal:
        valid = row >= col
        if window is not None:
            valid = valid & (row - col < window)
        s = jnp.where(valid, s, _NEG_INF)
    return s


class _Extras:
    """The optional kernel operands (segment ids of the q and k side, the
    head's ALiBi slope) peeled off the front of ``*rest``."""

    def __init__(self, rest, has_seg: bool, has_alibi: bool,
                 k_side_first: bool = False):
        self.sq_ref = self.sk_ref = self.slope_ref = None
        n = 0
        if has_seg:
            self.sq_ref, self.sk_ref = rest[0], rest[1]
            if k_side_first:
                self.sq_ref, self.sk_ref = self.sk_ref, self.sq_ref
            n = 2
        if has_alibi:
            self.slope_ref = rest[n]
            n += 1
        self.rest = rest[n:]


def _strip_scores(q, k, scale, ex: _Extras, rows, cols, r0, c0, masked,
                  axis: int, tile: int, causal: bool, window):
    """Scaled scores of one strip, fp32 ``[R, C]``, its first row ``r0``
    and first column ``c0`` (relative to the k block's first column;
    ``r0`` is traced where the plan serves several offsets).  ``masked``
    flags the strip's sub-tiles (``tile`` wide along ``axis``) the band's
    edge crosses: only those build iotas and select; under segment ids or
    ALiBi every flag is set and the whole strip takes the mask.

    MXU dtype discipline: the dots run in the INPUT dtype (bf16 inputs →
    bf16 MXU passes at full rate) with fp32 accumulation via
    preferred_element_type; only the softmax bookkeeping is fp32 — the
    standard flash-attention-2 arrangement."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale

    def band(x, r, c):
        row = r + lax.broadcasted_iota(jnp.int32, x.shape, 0)
        col = c + lax.broadcasted_iota(jnp.int32, x.shape, 1)
        if ex.slope_ref is not None:
            # ALiBi: slope_h * (j - i), 0 on the diagonal, more negative
            # with distance — computed in-kernel, no bias tensor ever
            # exists in HBM
            x = x + ex.slope_ref[0, 0, 0] * (col - row).astype(jnp.float32)
        return _band_mask(x, row, col, causal, window)

    if all(masked):
        if causal or ex.slope_ref is not None:
            s = band(s, r0, c0)
        if ex.sq_ref is not None:
            # q and k segment ids differ → masked (HF attention-mask /
            # packed-sequence semantics): sq [R, 1] int32, sk [C, 1] int32
            valid = (ex.sq_ref[0, rows, :]
                     == ex.sk_ref[0, cols, :][:, 0][None, :])
            s = jnp.where(valid, s, _NEG_INF)
        return s
    pieces, start = [], 0
    for t in range(1, len(masked) + 1):   # runs of equal flags
        if t < len(masked) and masked[t] == masked[start]:
            continue
        piece = lax.slice_in_dim(s, start * tile, t * tile, axis=axis)
        if masked[start]:
            piece = band(piece, r0 + (start * tile if axis == 0 else 0),
                         c0 + (start * tile if axis == 1 else 0))
        pieces.append(piece)
        start = t
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, nq: int, nk: int, plans, sub,
                causal: bool, scale: float, has_seg: bool,
                has_alibi: bool = False, window=None):
    ex = _Extras(rest, has_seg, has_alibi)
    o_ref, lse_ref, *carry = ex.rest
    # grid (BH, nq, nk), k innermost ("arbitrary"): Mosaic pipelines the
    # K/V HBM→VMEM copies against compute; the online-softmax carry
    # (acc, running max, running sum) lives in VMEM scratch across k
    # steps — and does not exist where one k block holds the whole
    # sequence (``nk == 1``): a strip then sees its rows' whole softmax
    # and writes o and lse itself.  q/o blocks: [1, BQ, D]; k/v block:
    # [1, BK, D]; lse: [1, BQ, 1].
    qi = _grid_pos(1, nq)
    j = _grid_pos(2, nk)
    sq, sk = sub

    def finish(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, rows, :] = m + jnp.log(l)          # [R, 1]

    if carry:
        acc_ref, m_ref, l_ref = carry

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    # a step outside the band has no plan and does nothing (its DMA was
    # also elided by the clamped index map in _flash_forward)
    d = qi * q_ref.shape[1] - j * k_ref.shape[1]

    def strip(a0, a1, lo, hi, masked):
        rows = pl.ds(a0 * sq, (a1 - a0) * sq)
        cols = pl.ds(lo * sk, (hi - lo) * sk)
        v = v_ref[0, cols, :]
        s = _strip_scores(q_ref[0, rows, :], k_ref[0, cols, :], scale, ex,
                          rows, cols, d + a0 * sq, lo * sk, masked, 1, sk,
                          causal, window)
        m_new = jnp.max(s, axis=-1, keepdims=True)
        if carry:
            m_old = m_ref[rows, :]
            m_new = jnp.maximum(m_old, m_new)
        p = jnp.exp(s - m_new)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if not carry:
            return finish(rows, m_new, l, acc)
        alpha = jnp.exp(m_old - m_new)
        l_ref[rows, :] = l_ref[rows, :] * alpha + l
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + acc
        m_ref[rows, :] = m_new

    _for_each_strip(plans, d, strip)

    if carry:
        @pl.when(j == nk - 1)
        def _finish():
            finish(slice(None), m_ref[...], l_ref[...], acc_ref[...])


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


@functools.lru_cache(maxsize=64)
def _forward_call(B, T, H, Hkv, D, Dv, dtype, bq, bk, sub, causal, scale,
                  interpret, has_seg, window, has_alibi):
    """The forward ``pallas_call`` of one static configuration.  Cached:
    every layer of a model calls the SAME object, so JAX traces the kernel
    and lowers it to Mosaic once a program, not once a layer (the
    callable is a ``jit``; a fresh one per layer missed its cache 24
    times in a 24-layer step).  ``D`` is the width of a q / k head,
    ``Dv`` of a v / o head (latent attention: 192 and 128)."""
    group = H // Hkv
    nq, nk = T // bq, T // bk
    plans = _q_major_plans(nq, nk, bq, bk, sub, causal, window,
                           has_seg or has_alibi)

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
        pl.BlockSpec((1, bk, Dv), kv_idx),
    ]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b // H, i, 0)),
            pl.BlockSpec((1, bk, 1), sk_idx),
        ]
    if has_alibi:
        in_specs += [pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0))]

    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, nq=nq, nk=nk, plans=plans, sub=sub, causal=causal,
            scale=scale, has_seg=has_seg, has_alibi=has_alibi,
            window=window),
        name=_band_name("flash_fwd", window),
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            # lse kept 3-D: TPU requires the last two block dims divisible
            # by (8, 128) or equal to the full array dims — (bq, 1) is
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dv), dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((bq, Dv), jnp.float32),  # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
        ],
        compiler_params=_ARBITRARY_INNER,
        interpret=interpret,
    )


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   segment_ids=None, window=None, alibi_slopes=None):
    interpret = resolve_interpret(interpret, "flash_attention forward")
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    H, Hkv, _ = _gqa_group(q, k)
    _check_band_args(causal, window, alibi_slopes, H)
    bq = _fit_block(block_q, T)
    bk = _fit_block(block_k, T)
    sub = _sub_tile(bq, bk)
    _record_tiles("fwd", T, bq, bk, sub, causal, window)
    # fold heads into the batch grid dim; [B, T, H, D] -> [B*H, T, D]
    operands = [q.transpose(0, 2, 1, 3).reshape(B * H, T, D),
                k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D),
                v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, Dv)]
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)[..., None]   # [B, T, 1]
        operands += [seg, seg]
    if alibi_slopes is not None:
        operands += [jnp.tile(alibi_slopes.astype(jnp.float32),
                              B)[:, None, None]]         # [B*H, 1, 1]
    o, lse = _forward_call(
        B, T, H, Hkv, D, Dv, q.dtype, bq, bk, sub, causal, scale, interpret,
        segment_ids is not None, window, alibi_slopes is not None)(*operands)
    return o.reshape(B, H, T, Dv).transpose(0, 2, 1, 3), lse[..., 0]


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
    k: ``[B, T, Hkv, D]``, v: ``[B, T, Hkv, Dv]`` (``Dv`` may differ from
    ``D``; the output is ``[B, T, H, Dv]``) with ``H % Hkv == 0`` (GQA/MQA: each group of
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
    (``DEFAULT_BLOCK_Q/K``).  Explicit values bind the forward and the
    backward kernels alike, e.g. when a VMEM budget forces the shape."""
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
    # named for a caller's recomputation policy: a block under
    # ``jax.checkpoint`` that saves these two does not run the forward
    # kernel again in the backward pass (models/transformer.py)
    o, lse = checkpoint_name(o, FLASH_OUT), checkpoint_name(lse, FLASH_OUT)
    return o, (q, k, v, o, lse, segment_ids, alibi_slopes)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   nq: int, nk: int, plans, sub, causal: bool, scale: float,
                   has_seg: bool, has_alibi: bool = False, window=None):
    """dq accumulation over the k-block grid dim (innermost): per strip
    of q rows, recompute the scores of the k columns it visits and
    accumulate dq = scale * sum_j ds_j @ k_j in VMEM scratch; same
    3-D-grid pipelining as the forward."""
    ex = _Extras(rest, has_seg, has_alibi)
    dq_ref, *carry = ex.rest        # no accumulator where nk == 1
    qi = _grid_pos(1, nq)
    j = _grid_pos(2, nk)
    sq, sk = sub

    if carry:
        dq_acc_ref, = carry

        @pl.when(j == 0)
        def _init():
            dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    d = qi * q_ref.shape[1] - j * k_ref.shape[1]

    def strip(a0, a1, lo, hi, masked):
        rows = pl.ds(a0 * sq, (a1 - a0) * sq)
        cols = pl.ds(lo * sk, (hi - lo) * sk)
        do = do_ref[0, rows, :]                       # [R, D], input dtype
        k = k_ref[0, cols, :]                         # [C, D]
        s = _strip_scores(q_ref[0, rows, :], k, scale, ex, rows, cols,
                          d + a0 * sq, lo * sk, masked, 1, sk, causal,
                          window)
        p = jnp.exp(s - lse_ref[0, rows, :].astype(jnp.float32))
        dp = lax.dot_general(
            do, v_ref[0, cols, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [R, C] fp32
        ds = p * (dp - delta_ref[0, rows, :].astype(jnp.float32))
        dq = lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if carry:
            dq_acc_ref[rows, :] = dq_acc_ref[rows, :] + dq
        else:
            dq_ref[0, rows, :] = (dq * scale).astype(dq_ref.dtype)

    _for_each_strip(plans, d, strip)

    if carry:
        @pl.when(j == nk - 1)
        def _finish():
            dq_ref[0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, *rest,
                    nk: int, nq: int, plans, sub, causal: bool,
                    scale: float, has_seg: bool, has_alibi: bool = False,
                    window=None, fused: bool = False):
    """dk/dv accumulation over the q-block grid dim (innermost): per
    strip of k columns, over the q rows at or below them that the band
    reaches, dv = sum_i p_i^T @ do_i, dk = scale * sum_i ds_i^T @ q_i,
    accumulated in VMEM scratch.

    ``fused``: the same strips also give dq = scale * sum_j ds_j @ k_j —
    the whole backward from ONE pass over the scores.  A strip's rows are
    visited again by later strips and by every other k block of the
    band, so dq adds into a whole-sequence fp32 ``[T, D]`` scratch that
    lives from the head's first grid step to its last, where it is
    scaled and cast into the head's dq block (resident across both inner
    grid axes, written back when the head changes)."""
    ex = _Extras(rest, has_seg, has_alibi, k_side_first=True)
    if fused:
        dk_ref, dv_ref, dq_ref, dq_acc_ref, *carry = ex.rest
    else:
        dk_ref, dv_ref, *carry = ex.rest  # no accumulators where nq == 1
    ki = _grid_pos(1, nk)
    i = _grid_pos(2, nq)
    sq, sk = sub
    bq = q_ref.shape[1]

    if carry:
        dk_acc_ref, dv_acc_ref = carry

        @pl.when(i == 0)
        def _init():
            dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
            dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    if fused:
        @_when((ki == 0) & (i == 0))
        def _init_dq():
            dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    d = i * bq - ki * k_ref.shape[1]

    def strip(b0, b1, lo, hi, masked):
        cols = pl.ds(b0 * sk, (b1 - b0) * sk)
        rows = pl.ds(lo * sq, (hi - lo) * sq)
        q = q_ref[0, rows, :]                         # [R, D], input dtype
        do = do_ref[0, rows, :]                       # [R, D]
        k = k_ref[0, cols, :]                         # [C, D]
        s = _strip_scores(q, k, scale, ex, rows, cols, d + lo * sq, b0 * sk,
                          masked, 0, sq, causal, window)
        p = jnp.exp(s - lse_ref[0, rows, :].astype(jnp.float32))
        dv = lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [C, D]
        dp = lax.dot_general(
            do, v_ref[0, cols, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [R, C] fp32
        ds = (p * (dp - delta_ref[0, rows, :].astype(jnp.float32))
              ).astype(q.dtype)
        # s was scaled after the q·k dot, so dL/dk = scale * sum ds^T @ q
        # (and dL/dq = scale * sum ds @ k): the scale is applied at the end
        dk = lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [C, D]
        if fused:
            seq_rows = pl.ds(i * bq + lo * sq, (hi - lo) * sq)
            dq_acc_ref[seq_rows, :] = dq_acc_ref[seq_rows, :] + (
                lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
        if carry:
            dk_acc_ref[cols, :] = dk_acc_ref[cols, :] + dk
            dv_acc_ref[cols, :] = dv_acc_ref[cols, :] + dv
        else:
            dk_ref[0, cols, :] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)

    _for_each_strip(plans, d, strip)

    if carry:
        @pl.when(i == nq - 1)
        def _finish():
            dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)

    if fused:
        @_when((ki == nk - 1) & (i == nq - 1))
        def _finish_dq():
            dq_ref[0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


@functools.lru_cache(maxsize=64)
def _dq_call(B, T, H, D, Dv, dtype, bq, bk, sub, causal, scale, interpret,
             has_seg, window, has_alibi):
    """The dq ``pallas_call`` of one static configuration (cached like
    ``_forward_call``).  Operands: q, k, v, do, lse, delta[, seg, seg]
    [, slopes]."""
    nq, nk = T // bq, T // bk
    plans = _q_major_plans(nq, nk, bq, bk, sub, causal, window,
                           has_seg or has_alibi)

    def k_block(i, j):
        if causal:
            j = jnp.minimum(j, _causal_last_k(i, bq, bk, nk))
            if window is not None:
                j = jnp.maximum(j, _window_first_k(i, bq, bk, window))
        return j

    def q_idx(b, i, j):
        return (b, i, 0)

    def kv_idx(b, i, j):
        return (b, k_block(i, j), 0)

    in_specs = [
        pl.BlockSpec((1, bq, D), q_idx),      # q block
        pl.BlockSpec((1, bk, D), kv_idx),     # k block
        pl.BlockSpec((1, bk, Dv), kv_idx),    # v block
        pl.BlockSpec((1, bq, Dv), q_idx),     # do block
        pl.BlockSpec((1, bq, 1), q_idx),      # lse block
        pl.BlockSpec((1, bq, 1), q_idx),      # delta
    ]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b // H, i, 0)),
            pl.BlockSpec((1, bk, 1),
                         lambda b, i, j: (b // H, k_block(i, j), 0)),
        ]
    if has_alibi:
        in_specs += [pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0))]

    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nq=nq, nk=nk, plans=plans,
                          sub=sub, causal=causal, scale=scale,
                          has_seg=has_seg, has_alibi=has_alibi,
                          window=window),
        name=_band_name("flash_bwd_dq", window),
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, D), q_idx),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), dtype),
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_ARBITRARY_INNER,
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _dkv_call(B, T, H, D, Dv, q_dtype, k_dtype, v_dtype, bq, bk, sub, causal,
              scale, interpret, has_seg, window, has_alibi, fused):
    """The dk/dv ``pallas_call`` of one static configuration (cached like
    ``_forward_call``) — with ``fused`` the whole backward: it returns
    ``(dk, dv, dq)``.  Operands: k, v, q, do, lse, delta[, seg, seg]
    [, slopes]."""
    nq, nk = T // bq, T // bk
    plans = _k_major_plans(nq, nk, bq, bk, sub, causal, window,
                           has_seg or has_alibi)

    def q_block(ki, i):
        if causal:
            # clamp from below: first useful q block
            i = jnp.maximum(i, (ki * bk) // bq)
            if window is not None:
                # clamp from above: last q block inside the band
                i = jnp.minimum(i, jnp.minimum(
                    (ki * bk + bk - 1 + window - 1) // bq, nq - 1))
        return i

    def kv_idx(b, ki, i):
        return (b, ki, 0)

    def q_idx(b, ki, i):
        return (b, q_block(ki, i), 0)

    in_specs = [
        pl.BlockSpec((1, bk, D), kv_idx),     # k block
        pl.BlockSpec((1, bk, Dv), kv_idx),    # v block
        pl.BlockSpec((1, bq, D), q_idx),      # q block
        pl.BlockSpec((1, bq, Dv), q_idx),     # do block
        pl.BlockSpec((1, bq, 1), q_idx),      # lse
        pl.BlockSpec((1, bq, 1), q_idx),      # delta
    ]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, bk, 1), lambda b, ki, i: (b // H, ki, 0)),
            pl.BlockSpec((1, bq, 1),
                         lambda b, ki, i: (b // H, q_block(ki, i), 0)),
        ]
    if has_alibi:
        in_specs += [pl.BlockSpec((1, 1, 1), lambda b, ki, i: (b, 0, 0))]

    out_specs = [pl.BlockSpec((1, bk, D), kv_idx),
                 pl.BlockSpec((1, bk, Dv), kv_idx)]
    out_shape = [jax.ShapeDtypeStruct((B * H, T, D), k_dtype),
                 jax.ShapeDtypeStruct((B * H, T, Dv), v_dtype)]
    scratch = [] if nq == 1 else [pltpu.VMEM((bk, D), jnp.float32),
                                  pltpu.VMEM((bk, Dv), jnp.float32)]
    params = _ARBITRARY_INNER
    if fused:
        # the head's dq: one block for the whole grid row, so it stays in
        # VMEM while the k blocks pass and is written back once a head
        out_specs += [pl.BlockSpec((1, T, D), lambda b, ki, i: (b, 0, 0))]
        out_shape += [jax.ShapeDtypeStruct((B * H, T, D), q_dtype)]
        scratch = [pltpu.VMEM((T, D), jnp.float32)] + scratch
        # dq sums over the k blocks too: only the head axis is parallel
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_FUSED_BWD_VMEM_LIMIT)

    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nk=nk, nq=nq, plans=plans,
                          sub=sub, causal=causal, scale=scale,
                          has_seg=has_seg, has_alibi=has_alibi,
                          window=window, fused=fused),
        # the single kernel carries BOTH names it replaces: the benchmark
        # finds the backward by either substring (its rooflines) and asks
        # the compiled step for each of the two (its traffic files'
        # ``kernels``); one name once those lists say so (PERF.md §7)
        name=_band_name("flash_bwd_dq_flash_bwd_dkv" if fused
                        else "flash_bwd_dkv", window),
        grid=(B * H, nk, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )


def _fused_bwd_fits(T: int, D: int, dtype) -> bool:
    """The shape rule: one backward kernel where a head's dq — the fp32
    accumulator and the two buffers of its output block — fits
    ``_FUSED_BWD_DQ_BYTES`` of VMEM (a row is padded to whole 128-lane
    tiles)."""
    lanes = -(-D // 128) * 128
    return (T * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)
            <= _FUSED_BWD_DQ_BYTES)


def _flash_backward(q, k, v, o, lse, do, dlse, causal, scale, block_q,
                    block_k, interpret, segment_ids=None, window=None,
                    alibi_slopes=None):
    """Shared Pallas backward.  ``dlse`` (``[BH, T, 1]`` or None) is the
    cotangent of the log-sum-exp output: since d(lse)/d(s) = softmax(s),
    it folds into the kernels as ``ds = p * (dp - (delta - dlse))`` — the
    same kernels serve both ``flash_attention`` and the lse-returning
    variant ring attention differentiates through.

    Which kernels run is read from the shape (``_fused_bwd_fits``): the
    single pass wherever a head's whole dq can stay in VMEM, else the
    dq and dk/dv passes.

    GQA backward materializes per-q-head k/v (one [B, T, H, D] transient
    each — the forward stays repeat-free) and group-sums dk/dv back to
    the Hkv heads; the dkv kernel's grid row owns its k block exclusively,
    which a shared kv row would break."""
    interpret = resolve_interpret(interpret, "flash_attention backward")
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    H, Hkv, group = _gqa_group(q, k)
    _check_band_args(causal, window, alibi_slopes, H)
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = scale if scale is not None else D ** -0.5
    bq, bk = _fit_block(block_q, T), _fit_block(block_k, T)
    sub = _sub_tile(bq, bk)
    fused = _fused_bwd_fits(T, D, q.dtype)
    get_registry().gauge(
        "flash.bwd_fused", window=_band_label(window)).set(int(fused))
    for kernel in ("bwd",) if fused else ("bwd_dq", "bwd_dkv"):
        _record_tiles(kernel, T, bq, bk, sub, causal, window)

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

    has_seg = segment_ids is not None
    has_alibi = alibi_slopes is not None
    extras = []
    if has_seg:
        seg = segment_ids.astype(jnp.int32)[..., None]   # [B, T, 1]
        extras += [seg, seg]
    if has_alibi:
        extras += [jnp.tile(alibi_slopes.astype(jnp.float32),
                            B)[:, None, None]]           # [B*H, 1, 1]

    config = (bq, bk, sub, causal, scale, interpret, has_seg, window,
              has_alibi)
    grads = _dkv_call(B, T, H, D, Dv, q.dtype, k.dtype, v.dtype, *config,
                      fused)(kf, vf, qf, dof, lse3, delta, *extras)
    dk, dv = grads[:2]
    dq = grads[2] if fused else _dq_call(B, T, H, D, Dv, q.dtype, *config)(
        qf, kf, vf, dof, lse3, delta, *extras)

    def unfold(x, dtype):
        return (x.reshape(B, H, T, x.shape[-1]).transpose(0, 2, 1, 3)
                .astype(dtype))

    dq_out = unfold(dq, q.dtype)
    dk_out = unfold(dk, k.dtype)
    dv_out = unfold(dv, v.dtype)
    if group > 1:  # fold per-q-head kv grads back onto the shared kv heads
        dk_out = dk_out.reshape(B, T, Hkv, group, D).sum(3).astype(k.dtype)
        dv_out = dv_out.reshape(B, T, Hkv, group, Dv).sum(3).astype(v.dtype)
    return dq_out, dk_out, dv_out


def _bwd_rule(causal, scale, block_q, block_k, interpret, window, res, do):
    import numpy as np

    q, k, v, o, lse, segment_ids, alibi_slopes = res
    bq, bk = _fwd_blocks(block_q, block_k)
    dq, dk, dv = _flash_backward(q, k, v, o, lse, do, None, causal, scale,
                                 bq, bk, interpret, segment_ids,
                                 window, alibi_slopes)
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
    bq, bk = _fwd_blocks(block_q, block_k)
    return _flash_backward(q, k, v, o, lse_bh, do, dlse3, causal, scale,
                           bq, bk, interpret)


flash_attention_with_lse.defvjp(_lse_fwd_rule, _lse_bwd_rule)
