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
    import numpy as np

    x, w, tile_group, n_active = res
    dx = _gmm(dy.astype(x.dtype), w, tile_group, n_active, True, interpret)
    dw = grouped_matmul_dw(x, dy, tile_group, n_active, w.shape[0],
                           interpret).astype(w.dtype)
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dx, dw, zero(tile_group), zero(n_active)


grouped_matmul.defvjp(_fwd, _bwd)
