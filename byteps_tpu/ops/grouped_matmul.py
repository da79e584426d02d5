"""Pallas TPU grouped matrix product over rows sorted by group, and the
gathers that fill and empty its buffer when their source lies in HBM.

The expert layer (``parallel/moe.py``) lays the rows routed to its
experts out group after group in one static buffer, every group starting
on a row-tile boundary, so a ``[tm, K]`` row tile belongs to exactly ONE
group.  ``tile_group [R // tm]`` names each tile's group and ``n_active``
counts the tiles that hold real rows; both arrive by scalar prefetch, so
index maps can read them:

  * ``grouped_matmul(x [R, K], w [G, K, N]) -> [R, N]`` — tile ``i``
    times ``w[tile_group[i]]``; one grid step a row tile with the whole
    ``[K, N]`` matrix of its group resident in VMEM (consecutive tiles of
    a group re-use it without a copy).  ``transpose_w`` contracts over
    ``N`` instead (``w [G, N, K]``): the backward's ``dx``.
  * ``grouped_matmul_dw(x [R, K], dy [R, N]) -> [G, K, N]`` — per group
    ``x_g^T @ dy_g``, accumulated in a VMEM scratch over the group's
    tiles and written when its last tile is done.
  * ``grouped_matmul_act(x [R, K], (w0, w1) | (w0,), act) -> h [R, N]``
    — an expert's first projection as one kernel each way: tile ``i``
    times ``w0[g]`` AND ``w1[g]`` (both resident; one pass over the row
    tile), ``h = act(x w0) * (x w1)`` — or ``act(x w0)`` for one matrix —
    on the float32 products, written once.  Under differentiation the
    forward also writes the products in the buffer's dtype; the backward
    kernel reads them and ``dh``, takes the epilogue's own ``jax.vjp`` on
    the tile (``act`` has no second definition anywhere), writes ``d(x
    w0)`` and ``d(x w1)`` for ``grouped_matmul_dw`` and sums ``dx`` over
    both in float32 before one cast.  Between the kernels XLA runs
    nothing over ``[R, N]`` or ``[R, K]``: no activation pass, no
    ``add_any`` of two ``dx``.

A step past ``n_active`` does nothing and moves nothing: its index maps
clamp to the last active tile, and an unchanged block index performs no
copy.  **Rows of inactive tiles are left unwritten** — the caller never
reads them (``parallel/moe.py`` gathers real rows only, and masks the
buffer where it reduces over all of its rows).
Every group must own at least one tile, or its ``dw`` block is never
written; the layout gives an empty group one tile whose rows hold no
assignment (their ``dy`` is zero).

No capacity, no drop: the buffer is sized for the worst routing and the
cost follows the tiles that are real.

  * ``row_gather(source [T, d], tok [R], n_active, tile) -> [R, d]`` and
    ``sum_gather(rows [R, d], dest, held [T, k], n_active, weights) ->
    [T, d]`` — the expert layer's gathers where XLA cannot keep the
    source in VMEM: each wanted row moves by ONE DMA (the rows of the
    active tiles; a token's held rows, summed in float32 in VMEM), the
    others not at all.  The comment above ``dma_rows`` says how a row
    is made to move alone; ``parallel/moe.py:from_hbm`` picks them by
    shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_utils import resolve_interpret

# One [K, N] matrix of a group (double-buffered) beside a row tile and
# its result: 2048 x 768 bf16 is 3 MB a buffer, f32 accumulation of the
# same block 6 MB — over the 16 MiB default of the scoped limit, so each
# call asks for what its blocks count to, a quarter over, up to 64 MiB.
# Not simply 64: the limit is VMEM the compiler sets ASIDE around the
# call, and the gather that feeds it (``parallel/moe.py``) keeps its
# whole ``[T, d]`` source in VMEM only if both fit the chip's 128 MiB —
# 84 MB at 16 384 x 2560 bf16 beside 64 MiB do not, and the gather then
# reads HBM row by row: 4.7 ms where 0.8 (v5e, one layer, PR 35).
_VMEM_FLOOR, VMEM_LIMIT = 16 * 1024 * 1024, 64 * 1024 * 1024


def _limit(counted: int) -> int:
    return min(VMEM_LIMIT, max(_VMEM_FLOOR, counted + counted // 4))


def _params(counted: int):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_limit(counted))


# VMEM each call's blocks count to: every block double-buffered, and the
# float32 values the body holds beside them
def _gmm_bytes(K, N, tm, dtype):
    # the float32 product before its cast
    return 2 * (tm * K + K * N + tm * N) * jnp.dtype(dtype).itemsize \
        + 4 * tm * N


def _dw_bytes(K, N, tm, dtype):
    # the accumulator and the tile's product on its way into it
    return 2 * (tm * K + tm * N + K * N) * jnp.dtype(dtype).itemsize \
        + 8 * K * N


def _act_bytes(K, N, tm, dtype, n_w, n_out):
    # the products and h
    return 2 * (tm * K + n_w * K * N + n_out * tm * N) \
        * jnp.dtype(dtype).itemsize + 4 * (n_w + 1) * tm * N


def _act_bwd_bytes(K, N, tm, dtype, n_w):
    # dh, the stored products and their gradients, and dx with the
    # product on its way into it
    return 2 * (tm * K + n_w * K * N + (1 + 2 * n_w) * tm * N) \
        * jnp.dtype(dtype).itemsize + 4 * (1 + 2 * n_w) * tm * N \
        + 8 * tm * K


def scoped_vmem(d: int, f: int, tm: int, dtype, n_w: int) -> int:
    """The most scoped VMEM any call of an expert layer asks for — the
    first projection (``n_w`` matrices ``[d, f]``) each way, down each
    way and the weight gradients — at row tile ``tm``."""
    return max(_limit(b) for b in (
        _act_bytes(d, f, tm, dtype, n_w, 1 + n_w),
        _act_bwd_bytes(d, f, tm, dtype, n_w), _dw_bytes(d, f, tm, dtype),
        _gmm_bytes(f, d, tm, dtype), _gmm_bytes(d, f, tm, dtype),
        _dw_bytes(f, d, tm, dtype)))


def _row(i, tile_group, n_active):
    """Block index of row tile ``i``, clamped to the last active tile (an
    unchanged index moves nothing)."""
    return (jnp.minimum(i, n_active[0] - 1), 0)


def _group(i, tile_group, n_active):
    """Block index of the group's matrix for row tile ``i``."""
    return (tile_group[jnp.minimum(i, n_active[0] - 1)], 0, 0)


def _gmm_kernel(tile_group_ref, n_active_ref, x_ref, w_ref, o_ref, *,
                transpose_w: bool):
    i = pl.program_id(0)

    @pl.when(i < n_active_ref[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transpose_w else (
            ((1,), (0,)), ((), ()))
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _gmm_call(R, K, N, tm, dtype, transpose_w, interpret):
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        name="grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // tm,),
            in_specs=[pl.BlockSpec((tm, K), _row),
                      pl.BlockSpec((1, N, K) if transpose_w else (1, K, N),
                                   _group)],
            out_specs=pl.BlockSpec((tm, N), _row)),
        out_shape=jax.ShapeDtypeStruct((R, N), dtype),
        compiler_params=_params(_gmm_bytes(K, N, tm, dtype)),
        interpret=interpret,
    )


def _dw_kernel(tile_group_ref, n_active_ref, x_ref, dy_ref, o_ref, acc_ref):
    i = pl.program_id(0)
    n = n_active_ref[0]
    g = tile_group_ref[jnp.minimum(i, n - 1)]
    first = (i == 0) | (tile_group_ref[jnp.maximum(i, 1) - 1] != g)
    last = (i == n - 1) | (
        tile_group_ref[jnp.minimum(i + 1, n - 1)] != g)

    @pl.when(i < n)
    def _():
        part = lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(first)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += part

        @pl.when(last)
        def _():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _dw_call(R, K, N, G, tm, dtype, interpret):
    return pl.pallas_call(
        _dw_kernel,
        name="grouped_matmul_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // tm,),
            in_specs=[pl.BlockSpec((tm, K), _row),
                      pl.BlockSpec((tm, N), _row)],
            out_specs=pl.BlockSpec((1, K, N), _group),
            scratch_shapes=[pltpu.VMEM((K, N), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, K, N), dtype),
        compiler_params=_params(_dw_bytes(K, N, tm, dtype)),
        interpret=interpret,
    )


def _scalars(tile_group, n_active):
    return (tile_group.astype(jnp.int32),
            jnp.reshape(n_active, (1,)).astype(jnp.int32))


def _gmm(x, w, tile_group, n_active, transpose_w, interpret):
    interpret = resolve_interpret(interpret, "grouped_matmul")
    R, K = x.shape
    N = w.shape[1] if transpose_w else w.shape[2]
    tm = R // tile_group.shape[0]
    return _gmm_call(R, K, N, tm, x.dtype, transpose_w, interpret)(
        *_scalars(tile_group, n_active), x, w.astype(x.dtype))


def grouped_matmul_dw(x, dy, tile_group, n_active, groups: int,
                      interpret=None):
    """``[G, K, N]``: per group, ``x_g^T @ dy_g`` over its row tiles."""
    interpret = resolve_interpret(interpret, "grouped_matmul_dw")
    (R, K), N = x.shape, dy.shape[1]
    tm = R // tile_group.shape[0]
    return _dw_call(R, K, N, groups, tm, x.dtype, interpret)(
        *_scalars(tile_group, n_active), x, dy.astype(x.dtype))


def _zero(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, n_active, interpret=None):
    """``x [R, K]`` times, tile by tile, the matrix ``w[g] [K, N]`` of the
    tile's group (``tile_group [R // tm]``), for the first ``n_active``
    tiles; the rows of the others are left unwritten."""
    return _gmm(x, w, tile_group, n_active, False, interpret)


def _fwd(x, w, tile_group, n_active, interpret):
    return (_gmm(x, w, tile_group, n_active, False, interpret),
            (x, w, tile_group, n_active))


def _bwd(interpret, res, dy):
    x, w, tile_group, n_active = res
    dx = _gmm(dy.astype(x.dtype), w, tile_group, n_active, True, interpret)
    dw = grouped_matmul_dw(x, dy, tile_group, n_active, w.shape[0],
                           interpret).astype(w.dtype)
    return dx, dw, _zero(tile_group), _zero(n_active)


grouped_matmul.defvjp(_fwd, _bwd)


# ------------------------------------- a product pair and its activation


def _epilogue(act, pre):
    """``h`` from the float32 products ``pre``: ``act`` of the first,
    times the second where there are two."""
    return act(pre[0]) * pre[1] if len(pre) == 2 else act(pre[0])


def _act_kernel(tile_group_ref, n_active_ref, x_ref, *refs, act, n_w,
                residuals):
    w_refs, h_ref, pre_refs = refs[:n_w], refs[n_w], refs[n_w + 1:]

    @pl.when(pl.program_id(0) < n_active_ref[0])
    def _():
        x = x_ref[...]
        pre = [jnp.dot(x, w[0], preferred_element_type=jnp.float32)
               for w in w_refs]
        h_ref[...] = _epilogue(act, pre).astype(h_ref.dtype)
        if residuals:
            for ref, a in zip(pre_refs, pre):
                ref[...] = a.astype(ref.dtype)


@functools.lru_cache(maxsize=64)
def _act_call(R, K, N, tm, dtype, act, n_w, residuals, interpret):
    n_out = 1 + n_w * residuals
    return pl.pallas_call(
        functools.partial(_act_kernel, act=act, n_w=n_w,
                          residuals=residuals),
        name="grouped_matmul_act",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // tm,),
            in_specs=[pl.BlockSpec((tm, K), _row)]
            + [pl.BlockSpec((1, K, N), _group)] * n_w,
            out_specs=[pl.BlockSpec((tm, N), _row)] * n_out),
        out_shape=[jax.ShapeDtypeStruct((R, N), dtype)] * n_out,
        compiler_params=_params(_act_bytes(K, N, tm, dtype, n_w, n_out)),
        interpret=interpret,
    )


def _act_bwd_kernel(tile_group_ref, n_active_ref, dh_ref, *refs, act, n_w):
    pre_refs, w_refs = refs[:n_w], refs[n_w:2 * n_w]
    dpre_refs, dx_ref = refs[2 * n_w:3 * n_w], refs[3 * n_w]

    @pl.when(pl.program_id(0) < n_active_ref[0])
    def _():
        # the activation's derivative is the epilogue's own transpose, on
        # the tile: whatever ``act`` is, it has no second definition
        _, vjp = jax.vjp(lambda *pre: _epilogue(act, pre),
                         *(r[...].astype(jnp.float32) for r in pre_refs))
        dx = None
        for dpre, ref, w in zip(vjp(dh_ref[...].astype(jnp.float32)),
                                dpre_refs, w_refs):
            ref[...] = dpre = dpre.astype(ref.dtype)
            part = lax.dot_general(
                dpre, w[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dx = part if dx is None else dx + part
        dx_ref[...] = dx.astype(dx_ref.dtype)


@functools.lru_cache(maxsize=64)
def _act_bwd_call(R, K, N, tm, dtype, act, n_w, interpret):
    tile = pl.BlockSpec((tm, N), _row)
    return pl.pallas_call(
        functools.partial(_act_bwd_kernel, act=act, n_w=n_w),
        name="grouped_matmul_act_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // tm,),
            in_specs=[tile] * (1 + n_w)
            + [pl.BlockSpec((1, K, N), _group)] * n_w,
            out_specs=[tile] * n_w + [pl.BlockSpec((tm, K), _row)]),
        out_shape=[jax.ShapeDtypeStruct((R, N), dtype)] * n_w
        + [jax.ShapeDtypeStruct((R, K), dtype)],
        compiler_params=_params(_act_bwd_bytes(K, N, tm, dtype, n_w)),
        interpret=interpret,
    )


def _act_fwd_pass(x, ws, tile_group, n_active, act, residuals, interpret):
    """``[h]``, or ``[h, *products]`` with ``residuals``."""
    interpret = resolve_interpret(interpret, "grouped_matmul_act")
    (R, K), N = x.shape, ws[0].shape[2]
    tm = R // tile_group.shape[0]
    return _act_call(R, K, N, tm, x.dtype, act, len(ws), residuals,
                     interpret)(*_scalars(tile_group, n_active), x,
                                *(w.astype(x.dtype) for w in ws))


def _act_bwd_pass(dh, pre, ws, tile_group, n_active, act, interpret):
    """``[*dproducts, dx]`` from ``dh`` and the stored products."""
    interpret = resolve_interpret(interpret, "grouped_matmul_act_bwd")
    (R, N), K = dh.shape, ws[0].shape[1]
    tm = R // tile_group.shape[0]
    dtype = pre[0].dtype
    return _act_bwd_call(R, K, N, tm, dtype, act, len(ws), interpret)(
        *_scalars(tile_group, n_active), dh.astype(dtype), *pre,
        *(w.astype(dtype) for w in ws))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul_act(x, ws, tile_group, n_active, act, interpret=None):
    """``h [R, N]``: ``act(x w0) * (x w1)`` for ``ws = (w0, w1)``, ``act(x
    w0)`` for ``ws = (w0,)`` — ``grouped_matmul``'s products (each ``w
    [G, K, N]``) with the elementwise ``act`` on their float32 tiles, in
    one kernel; the rows of inactive tiles are left unwritten."""
    return _act_fwd_pass(x, ws, tile_group, n_active, act, False,
                         interpret)[0]


def _act_fwd(x, ws, tile_group, n_active, act, interpret):
    h, *pre = _act_fwd_pass(x, ws, tile_group, n_active, act, True,
                            interpret)
    return h, (x, ws, pre, tile_group, n_active)


def _act_bwd(act, interpret, res, dh):
    x, ws, pre, tile_group, n_active = res
    *dpre, dx = _act_bwd_pass(dh, pre, ws, tile_group, n_active, act,
                              interpret)
    dws = tuple(grouped_matmul_dw(x, d, tile_group, n_active, w.shape[0],
                                  interpret).astype(w.dtype)
                for d, w in zip(dpre, ws))
    return dx, dws, _zero(tile_group), _zero(n_active)


grouped_matmul_act.defvjp(_act_fwd, _act_bwd)


# ---------------------------------------------- rows moved from HBM by DMA
#
# Mosaic slices an HBM array for a DMA at whole tiles of its layout
# only, and XLA lays an ``[N, d]`` array in tiles of eight 32-bit
# sublane rows (sixteen bf16 rows): one row cannot move alone.  A row of
# ``[N, p, d / p]`` can (its tile is the row).  So each gather runs in two
# phases over one grid.  Phase one walks the source's tiles that can
# hold a wanted row (every tile of ``[T, d]``; the active tiles of the
# buffer) through VMEM and writes them to an HBM copy laid ``[N, p, d /
# p]`` — row ``n`` whole sublane rows, ``p = 4 / itemsize`` (a 16-bit
# row travels as its two halves packed together).  Phase two walks ONE
# flat list of the rows a tile moves (indices in SMEM) and moves each
# row of that copy by ONE DMA, the starts eight to a loop step
# (``_issue``).  Every loop is a rolled ``pl.loop``, so a kernel lowers
# to the same few kilobytes whatever its tile or ``k``.


def dma_rows(d: int, dtype) -> int:
    """Lanes a row of ``[N, d]`` is cut into to move alone (``d / p``),
    or 0 where it cannot: another width than 16 or 32 bits, or a part
    that is not a whole number of 128-lane tiles."""
    size = jnp.dtype(dtype).itemsize
    if size not in (2, 4) or d % (128 * (4 // size)):
        return 0
    return d // (4 // size)


def _parts(buf, rows=slice(None)):
    """``(columns, [n, d / p])`` a part: the ``[n, p, d / p]`` rows
    ``rows`` of ``buf`` back in the row's columns."""
    per, h = buf.shape[1:]
    return [(slice(a * h, (a + 1) * h), buf[rows, a]) for a in range(per)]


def _issue(n, start):
    """``start(e)`` for every ``e`` in ``[0, n)``: a rolled loop whose
    step makes eight calls (Mosaic unrolls a ``pl.loop`` whole or not at
    all), then one a step for the last ``n % 8``."""
    @pl.loop(0, n // 8)
    def _(b):
        for u in range(8):
            start(b * 8 + u)

    @pl.loop(n - n % 8, n)
    def _(e):
        start(e)


def _relay(tile_ref, lay, copy_ref, first, sem):
    """Phase one, a tile: ``tile_ref [tm, d]`` laid ``[tm, p, d / p]`` in
    VMEM (``lay``), then written to rows ``first ...`` of the HBM copy."""
    per, h = lay.shape[1:]
    for a in range(per):
        lay[:, a, :] = tile_ref[:, a * h:(a + 1) * h]
    dma = pltpu.make_async_copy(
        lay, copy_ref.at[pl.ds(first, lay.shape[0])], sem)
    dma.start()
    dma.wait()


def _scratch(N, d, tm, dtype):
    """The HBM copy (an output nobody reads) and the VMEM it passes."""
    per = 4 // jnp.dtype(dtype).itemsize
    return (jax.ShapeDtypeStruct((N, per, d // per), dtype),
            pltpu.VMEM((tm, per, d // per), dtype))


def _row_gather_kernel(n_active_ref, tok_ref, src_ref, o_ref, copy_ref,
                       lay, buf, sem):
    i, tm = pl.program_id(0), buf.shape[0]
    src_tiles = copy_ref.shape[0] // tm
    j = i - src_tiles

    @pl.when(i < src_tiles)
    def _():
        _relay(src_ref, lay, copy_ref, i * tm, sem)

    @pl.when((j >= 0) & (j < n_active_ref[0]))
    def _():
        _issue(tm, lambda r: pltpu.make_async_copy(
            copy_ref.at[tok_ref[0, r]], buf.at[r], sem).start())
        # a DMA semaphore counts bytes: one wait for the whole tile
        pltpu.make_async_copy(buf, buf, sem).wait()
        for cols, part in _parts(buf):
            o_ref[:, cols] = part


@functools.lru_cache(maxsize=64)
def _row_gather_call(T, R, d, tm, dtype, interpret):
    src_tiles = T // tm

    def src(i, n_active):
        return (jnp.minimum(i, src_tiles - 1), 0)

    def row(i, n_active):      # phase one holds tile 0, writing nothing
        return (jnp.clip(i - src_tiles, 0, n_active[0] - 1), 0)

    copy, lay = _scratch(T, d, tm, dtype)
    return pl.pallas_call(
        _row_gather_kernel,
        name="row_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(src_tiles + R // tm,),
            # the tile's row indices in SMEM a tile at a time: all of
            # them would not fit its 1 MiB at every size
            in_specs=[pl.BlockSpec((None, 1, tm),
                                   lambda i, n: (*row(i, n), 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((tm, d), src)],
            out_specs=[pl.BlockSpec((tm, d), row),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[lay, lay, pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((R, d), dtype), copy],
        # the source's tile and the result's, double-buffered, the two
        # laid tiles, and what Mosaic holds of a tile on its way between
        # the layouts (17.5 MB in all at 256 x 2560 bf16: the v5e
        # compiler's count)
        compiler_params=_params(14 * tm * d * jnp.dtype(dtype).itemsize),
        interpret=interpret,
    )


def row_gather(source, tok, n_active, tile: int, interpret=None):
    """``[R, d]``: row ``r`` is ``source[tok[r]]`` (``tok [R]``) on the
    first ``n_active`` row tiles of ``tile`` rows, each row ONE DMA from
    the source's copy in HBM; the rows of the other tiles are left
    unwritten.  ``source [T, d]`` needs ``dma_rows``, ``T`` a multiple
    of ``tile``."""
    interpret = resolve_interpret(interpret, "row_gather")
    (T, d), R = source.shape, tok.shape[0]
    return _row_gather_call(T, R, d, tile, source.dtype, interpret)(
        jnp.reshape(n_active, (1,)).astype(jnp.int32),
        tok.reshape(R // tile, 1, tile).astype(jnp.int32), source)[0]


def _sum_gather_kernel(n_active_ref, row_ref, at_ref, slot_ref, size_ref,
                       rows_ref, m_ref, *refs, weighted):
    w_ref = refs[0] if weighted else None
    o_ref, copy_ref, lay, buf, acc, sem = refs[-6:]
    i, tm, tt = pl.program_id(0), lay.shape[0], acc.shape[0]
    k = buf.shape[0] // tt
    j = i - copy_ref.shape[0] // tm

    @pl.when(i < n_active_ref[0])
    def _():
        _relay(rows_ref, lay, copy_ref, i * tm, sem)

    @pl.when(j >= 0)
    def _():
        # the tile's held rows, one list in the order (t, j): token t's
        # q-th held row (``row[t * k + q]`` of the copy) to slot
        # ``q * tt + t`` of ``buf`` (``moves``)
        total, top = size_ref[0, 0], size_ref[0, 1]
        _issue(total, lambda e: pltpu.make_async_copy(
            copy_ref.at[row_ref[0, at_ref[0, e]]], buf.at[slot_ref[0, e]],
            sem).start())

        # a DMA semaphore counts bytes: one wait per eight rows moved
        eight, one = buf.at[pl.ds(0, 8)], buf.at[pl.ds(0, 1)]

        @pl.loop(0, total // 8)
        def _(_):
            pltpu.make_async_copy(eight, eight, sem).wait()

        @pl.loop(0, total % 8)
        def _(_):
            pltpu.make_async_copy(one, one, sem).wait()

        # in float32, slot by slot, up to the tile's fullest token
        # (``top``); a slot past n_t adds nothing (it holds what an
        # earlier tile left there: a select keeps it out)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        lanes = lax.broadcasted_iota(jnp.int32, (tt, k), 1)

        @pl.loop(0, top)
        def _(q):
            pick = lanes == q
            held = jnp.max(jnp.where(pick, m_ref[...], 0), axis=1,
                           keepdims=True) > 0
            if weighted:
                w = jnp.sum(jnp.where(pick, w_ref[...], 0.0), axis=1,
                            keepdims=True)
            for cols, part in _parts(buf, pl.ds(q * tt, tt)):
                term = jnp.where(held, part.astype(jnp.float32), 0.0)
                acc[:, cols] += term * w if weighted else term

        # the result is the sum over all k slots, which adds a +0.0 to
        # a token short of k rows after its last one (a -0.0 becomes
        # +0.0): the loop leaves that out where it stopped at top < k,
        # so it is added here (and x + -0.0 is x)
        zero = jnp.where(top < k, 0.0, -0.0)
        o_ref[...] = (acc[...] + zero).astype(o_ref.dtype)


@functools.lru_cache(maxsize=64)
def _sum_gather_call(T, R, d, k, tm, dtype, weighted, interpret):
    row_tiles, tt = R // tm, tm

    def rows(i, n_active):     # phase two holds the last, moving nothing
        return (jnp.minimum(i, n_active[0] - 1), 0)

    def tok(i, n_active):      # phase one holds tile 0, writing nothing
        return (jnp.maximum(i - row_tiles, 0), 0)

    def smem(shape):
        return pl.BlockSpec((None, 1, shape), lambda i, n: (*tok(i, n), 0),
                            memory_space=pltpu.SMEM)

    copy, lay = _scratch(R, d, tm, dtype)
    per = 4 // jnp.dtype(dtype).itemsize
    return pl.pallas_call(
        functools.partial(_sum_gather_kernel, weighted=weighted),
        name="sum_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(row_tiles + T // tt,),
            # the token tile's held rows, its list of moves and its
            # (length, fullest token) in SMEM a tile at a time (as in
            # row_gather)
            in_specs=[smem(tt * k), smem(tt * k), smem(tt * k), smem(2),
                      pl.BlockSpec((tm, d), rows)]
            + [pl.BlockSpec((tt, k), tok)] * (1 + weighted),
            out_specs=[pl.BlockSpec((tt, d), tok),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[lay,
                            pltpu.VMEM((k * tt, per, d // per), dtype),
                            pltpu.VMEM((tt, d), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((T, d), dtype), copy],
        # the buffer's tile double-buffered and laid (with row_gather's
        # allowance for its way between the layouts), k rows a token,
        # the result's block double-buffered, the float32 sum and a term
        # on its way into it, and the [tt, k] blocks (a lane tile wide)
        compiler_params=_params(
            (7 * tm + (k + 2) * tt) * d * jnp.dtype(dtype).itemsize
            + 8 * tt * d + 2 * (1 + weighted) * tt * 128 * 4),
        interpret=interpret,
    )


def moves(n, k: int, tile: int):
    """A token tile's held rows as ONE list in the order ``(t, j)``, from
    ``n [T]`` (token t's held rows) and built without a sort, a scatter
    or a gather: ``(at, slot [T / tile, tile k], size [T / tile, 2])`` —
    entry ``e < length`` is row ``q`` of token t, ``at[e] = t k + q``
    (into the tile's ``[tile, k]`` held rows, token t's first), bound
    for slot ``slot[e] = q tile + t``; ``size`` is each tile's
    ``(length, fullest token's n_t)``.  Token t's rows are the entries
    from its running count's start on, so entry e is the last token's
    whose start is not past it: one compare and one max over the
    tile's tokens."""
    n = n.reshape(-1, tile)
    ends = jnp.cumsum(n, axis=1)
    start = ends - n
    e = jnp.arange(tile * k, dtype=ends.dtype)
    # [tiles, tile k]: start * tile + t of entry e's token (rising in t)
    last = jnp.max(jnp.where(start[:, :, None] <= e, (
        start * tile + jnp.arange(tile, dtype=ends.dtype))[:, :, None], 0),
        axis=1)
    t, q = last % tile, e - last // tile
    return t * k + q, q * tile + t, jnp.stack([ends[:, -1],
                                               jnp.max(n, axis=1)], axis=1)


def sum_gather(rows, dest, held, n_active, weights=None, tile: int = 256,
               interpret=None):
    """``[T, d]`` in ``rows``' dtype: token ``t``'s held rows ``rows[dest[t,
    j]]`` (``held [T, k]``), times ``weights [T, k]`` where given, summed
    in float32 in the order ``j = 0 ... k - 1`` and cast once — each row
    ONE DMA from the copy of the first ``n_active`` row tiles of the
    buffer (``rows [R, d]``, tiles of ``tile`` rows: every held row lies
    there) in HBM, a row not held neither moved nor read.  Needs
    ``dma_rows``; ``T`` a multiple of ``tile``."""
    interpret = resolve_interpret(interpret, "sum_gather")
    (T, k), (R, d) = dest.shape, rows.shape
    tiles = T // tile
    # token t's held rows first, in their order: [T, j, q]
    into = ((jnp.cumsum(held, axis=1) - 1)[:, :, None] == jnp.arange(k)) \
        & held[:, :, None]
    n = jnp.sum(held, axis=1, dtype=jnp.int32)

    def packed(a):
        return jnp.sum(jnp.where(into, a[:, :, None], 0), axis=1)

    m = (jnp.arange(k) < n[:, None]).astype(jnp.int32)
    extra = () if weights is None else (packed(weights.astype(jnp.float32)),)
    at, slot, size = moves(n, k, tile)
    return _sum_gather_call(T, R, d, k, tile, rows.dtype, weights is not None,
                            interpret)(
        jnp.reshape(n_active, (1,)).astype(jnp.int32),
        packed(dest).astype(jnp.int32).reshape(tiles, 1, tile * k),
        at.reshape(tiles, 1, tile * k), slot.reshape(tiles, 1, tile * k),
        size.reshape(tiles, 1, 2), rows, m, *extra)[0]
