"""Pallas TPU grouped matrix product over rows sorted by group.

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
_VMEM_FLOOR, _VMEM_LIMIT = 16 * 1024 * 1024, 64 * 1024 * 1024


def _params(counted: int):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=min(_VMEM_LIMIT, max(_VMEM_FLOOR,
                                              counted + counted // 4)))


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
        # both operands' blocks and the result's, double-buffered, and
        # the float32 product before its cast
        compiler_params=_params(2 * (tm * K + K * N + tm * N)
                                * jnp.dtype(dtype).itemsize + 4 * tm * N),
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
        # as above, and two float32 [K, N]: the accumulator and the
        # tile's product on its way into it
        compiler_params=_params(2 * (tm * K + tm * N + K * N)
                                * jnp.dtype(dtype).itemsize + 8 * K * N),
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
        # every block double-buffered; in float32 the products and h
        compiler_params=_params(
            2 * (tm * K + n_w * K * N + n_out * tm * N)
            * jnp.dtype(dtype).itemsize + 4 * (n_w + 1) * tm * N),
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
        # every block double-buffered; in float32 dh, the stored
        # products and their gradients, and dx with the product on its
        # way into it
        compiler_params=_params(
            2 * (tm * K + n_w * K * N + (1 + 2 * n_w) * tm * N)
            * jnp.dtype(dtype).itemsize
            + 4 * (1 + 2 * n_w) * tm * N + 8 * tm * K),
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
