"""Pallas TPU selective state-space scan in its chunked (state-space
duality) form: the Mamba-2 recurrence (Dao & Gu, arXiv:2405.21060,
section 6) without a step per position and without a ``[T, T]`` array.

Per head ``h`` (which reads group ``h // (H / G)`` of ``B`` and ``C``),
state ``S [P, N]``, ``S_0 = 0``:

    S_t = exp(dt_t A) S_{t-1} + dt_t * X_t (x) B_t
    Y_t = S_t C_t + D X_t

With ``a_t = dt_t A`` and ``cs`` its running sum INSIDE a chunk of ``Q``
positions, chunk ``c`` computes

    Y_intra   = ((C B^T) o L) (dt o X),   L[t, s] = exp(cs_t - cs_s), s <= t
    own_c     = sum_s exp(cs_Q - cs_s) dt_s X_s (x) B_s
    S_c       = exp(cs_Q) S_{c-1} + own_c
    Y_inter[t] = exp(cs_t) * S_{c-1} C_t

``ssd_fwd`` walks the chunks of one (sequence, group) in order as the
last, sequential grid axis, the group's ``H / G`` states carried in a
VMEM scratch; a grid step holds one chunk of the group's heads (``X
[Q, H/G * P]`` lane-dense), so ``C B^T`` is formed once a group, the
products that share an operand across the group's heads (the states
read through ``C``, built against ``B``) run once for all of them, and
every product is ``Q``-shaped.  ``ssd_bwd`` is the written backward:
the same grid walked from the last chunk to the first with the state's
cotangent carried, given the chunk-boundary states the forward wrote
(``[B, T/Q, H P, N]`` float32, written only where a gradient is asked;
named ``SSD_OUT`` with the output, so that a recomputed block can keep
both and skip its second walk).  The walk over the chunk states was
also built as a ``lax.scan`` over ``[T/Q, H, P, N]`` between two
chunk-local passes in plain XLA; PERF.md section 6 has which lost
where.

**Precision.**  ``X``, ``B``, ``C`` arrive in the compute dtype and are
the operands of the in-chunk products (float32 accumulation).  The
decay algebra — ``a``, its running sums, every ``exp`` — is float32;
the running sums are taken outside the kernel by XLA (``[B, T, H]``
float32, 1/64 of ``X``'s bytes) and differentiated by it.  The carried
state is float32, and what is ADDED to it is exact to float32 too: the
scaled operand ``exp(cs_Q - cs_s) dt_s X_s`` is split into a high and a
low bfloat16 half, two MXU passes — one rounding of it to bfloat16
would put 2^-9 into a state that 64 chunks then carry.  The state is
rounded to the compute dtype only where a product READS it.

Block sizes follow from the shape: a chunk of ``chunk`` positions, a
group's heads a grid step.  ``T`` is padded to a whole number of chunks
with ``dt = 0`` (no decay, nothing added).  Gauges (label ``kernel`` =
``fwd`` | ``bwd``), set when a kernel is traced: ``ssd.chunk``,
``ssd.chunks``, ``ssd.heads``, ``ssd.state_bytes`` (the chunk-boundary
states of one call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.metrics import get_registry
from ._pallas_utils import resolve_interpret

__all__ = ["ssd_scan", "ssd_scan_with_states"]

F32 = jnp.float32
CARRY_DTYPE = jnp.float32     # of the state carried from chunk to chunk
SSD_OUT = "ssd_out"     # checkpoint name of the forward's y and states
_VMEM_LIMIT = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=F32)


def _decay(csc, csr):
    """``L [Q, Q]``: ``exp(cs_t - cs_s)`` for ``s <= t``, else 0 (masked
    before the ``exp``: above the diagonal the difference is positive
    and may overflow)."""
    Q = csc.shape[0]
    t = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return jnp.exp(jnp.where(s <= t, csc - csr, -jnp.inf))


def _split(v, cdt):
    """A float32 ``v`` as the operands of a product that is exact to
    float32: a high and a low bfloat16 half where the compute dtype is
    bfloat16 (two passes), ``v`` itself otherwise (one, at full
    precision)."""
    if cdt != jnp.bfloat16:
        return [v]
    hi = v.astype(jnp.bfloat16)
    return [hi, (v - hi.astype(F32)).astype(jnp.bfloat16)]


def _split_scratch(Q, width, dtype):
    """VMEM scratch for ``_split``'s parts of a ``[Q, width]`` operand."""
    if dtype == jnp.bfloat16:
        return [pltpu.VMEM((Q, width), dtype)] * 2
    return [pltpu.VMEM((Q, width), F32)]


def _dot_tn_exact(parts, m):
    """``v^T @ m`` for ``v`` as ``_split`` left it in ``parts``."""
    if len(parts) == 2:
        return _dot(parts[0][...], m, _TN) + _dot(parts[1][...], m, _TN)
    return lax.dot_general(parts[0][...], m.astype(F32), _TN,
                           preferred_element_type=F32,
                           precision=lax.Precision.HIGHEST)


def _fwd_kernel(dec_ref, d_ref, x_ref, dt_ref, cs_ref, csr_ref, b_ref,
                c_ref, y_ref, *rest, hb: int, P: int, with_states: bool):
    """One chunk of one group's heads.  What the heads share an operand
    in runs as ONE product over all of them — the entering states read
    through ``C`` (``[Q, N] x [N, H/G P]``), the chunk's own states
    built against ``B`` (``[H/G P, Q] x [Q, N]``) — so that only the two
    products whose BOTH operands are a head's own stay ``P`` wide."""
    st_ref, s_ref, *wx = rest if with_states else (None,) + rest
    Q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    bm, cm = b_ref[0], c_ref[0]                       # [Q, N]
    cdt = bm.dtype
    G = _dot(cm, bm, _NT)                             # [Q, Q], once a group
    s_all = s_ref[...].astype(F32)                    # [H/G P, N]
    if with_states:
        st_ref[0, 0] = s_all
    y_in = _dot(cm, s_all.astype(cdt), _NT)           # [Q, H/G P]
    for j in range(hb):
        sl = slice(j * P, (j + 1) * P)
        dtc = dt_ref[0, 0, :, j:j + 1]                # [Q, 1]
        csc = cs_ref[0, 0, :, j:j + 1]
        csr = csr_ref[0, 0, j:j + 1, :]               # [1, Q]
        xf = x_ref[0, :, sl].astype(F32)              # [Q, P]
        xd = xf * dtc
        M = G * _decay(csc, csr)
        y = _dot(M.astype(cdt), xd.astype(cdt), _NN)
        y += jnp.exp(csc) * y_in[:, sl] + d_ref[0, :, j:j + 1] * xf
        y_ref[0, :, sl] = y.astype(y_ref.dtype)
        for ref, part in zip(wx, _split(
                jnp.exp(csc[Q - 1:Q] - csc) * xd, cdt)):
            ref[:, sl] = part
    own = _dot_tn_exact(wx, bm)                       # [H/G P, N]
    for j in range(hb):
        sl = slice(j * P, (j + 1) * P)
        s_ref[sl] = (dec_ref[0, 0, j] * s_all[sl] + own[sl]).astype(
            s_ref.dtype)


def _bwd_kernel(dec_ref, d_ref, x_ref, dt_ref, cs_ref, csr_ref, b_ref,
                c_ref, dy_ref, st_ref, dx_ref, ddt_ref, dcs_ref, dcsr_ref,
                db_ref, dc_ref, dd_ref, ds_ref, wxd_ref, *edy_refs,
                hb: int, P: int):
    Q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)                   # the LAST chunk
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    bm, cm = b_ref[0], c_ref[0]
    cdt = bm.dtype
    G = _dot(cm, bm, _NT)
    s_all, ds_all = st_ref[0, 0], ds_ref[...]         # [H/G P, N] float32
    sp, dsn = s_all.astype(cdt), ds_all.astype(cdt)
    y_in = _dot(cm, sp, _NT)                          # [Q, H/G P]
    u_all = _dot(bm, dsn, _NT)                        # d(w o dt o X)
    is_last = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    dG = jnp.zeros((Q, Q), F32)
    for j in range(hb):
        sl = slice(j * P, (j + 1) * P)
        dy = dy_ref[0, :, sl]
        dtc = dt_ref[0, 0, :, j:j + 1]
        csc = cs_ref[0, 0, :, j:j + 1]
        csr = csr_ref[0, 0, j:j + 1, :]
        xf, dyf = x_ref[0, :, sl].astype(F32), dy.astype(F32)
        xd = xf * dtc
        L = _decay(csc, csr)
        M = G * L
        w, dec = jnp.exp(csc[Q - 1:Q] - csc), dec_ref[0, 0, j]
        # the in-chunk product Y = M (dt o X)
        dM = _dot(dy, xd.astype(cdt), _NT)            # [Q, Q]
        dxd = _dot(M.astype(cdt), dy, _TN)            # M^T dY  [Q, P]
        W = dM * M
        dcs = jnp.sum(W, axis=1, keepdims=True)       # rows: + at t
        dcsr_ref[0, 0, j:j + 1, :] = jnp.sum(W, axis=0, keepdims=True)
        dG += dM * L
        # what the entering state adds: exp(cs_t) C_t S_prev
        edy = jnp.exp(csc) * dyf
        dcs += jnp.sum(edy * y_in[:, sl], axis=1, keepdims=True)
        for ref, part in zip(edy_refs, _split(edy, cdt)):
            ref[:, sl] = part
        # what the chunk adds to the state: (w o dt o X)^T B
        wxd = w * xd
        wxd_ref[:, sl] = wxd.astype(cdt)
        U = u_all[:, sl]
        dxd += w * U
        dw = jnp.sum(U * wxd, axis=1, keepdims=True)  # d w_s * w_s
        tail = jnp.sum(dw, axis=0, keepdims=True) + dec * jnp.sum(
            ds_all[sl] * s_all[sl], keepdims=True)
        dcs_ref[0, 0, :, j:j + 1] = dcs - dw + jnp.where(is_last, tail, 0.0)
        ddt_ref[0, 0, :, j:j + 1] = jnp.sum(dxd * xf, axis=1, keepdims=True)
        dx = dtc * dxd + d_ref[0, :, j:j + 1] * dyf
        dx_ref[0, :, sl] = dx.astype(dx_ref.dtype)
        dd_ref[0, 0, 0, j:j + 1, :] = jnp.sum(dyf * xf, axis=0,
                                              keepdims=True)
    dGc = dG.astype(cdt)
    edy_lp = edy_refs[0][...].astype(cdt)
    dc_ref[0] = (_dot(edy_lp, sp, _NN) + _dot(dGc, bm, _NN)).astype(
        dc_ref.dtype)
    db_ref[0] = (_dot(wxd_ref[...], dsn, _NN) + _dot(dGc, cm, _TN)).astype(
        db_ref.dtype)
    ds_prev = _dot_tn_exact(edy_refs, cm)             # [H/G P, N]
    for j in range(hb):
        sl = slice(j * P, (j + 1) * P)
        ds_ref[sl] = dec_ref[0, 0, j] * ds_all[sl] + ds_prev[sl]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _specs(Q, hb, P, N, chunk_of):
    """Block specs of ``dec, d, x, dt, cs, cs_row, b, c`` for grid
    ``(batch, group, step)``; ``chunk_of(step)`` is the chunk a step
    works on.  ``dec`` — a chunk's whole decay ``exp(cs_Q)``, one number
    a head — lies in SMEM: it scales a ``[P, N]`` state, and Mosaic
    broadcasts a vector along lanes or sublanes, not both."""
    at = chunk_of
    return [
        pl.BlockSpec((1, 1, hb), lambda b, g, i: (
            (b * pl.num_programs(1) + g) * pl.num_programs(2) + at(i), 0, 0),
            memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, hb), lambda b, g, i: (g, 0, 0)),
        pl.BlockSpec((1, Q, hb * P), lambda b, g, i: (b, at(i), g)),
        pl.BlockSpec((1, 1, Q, hb), lambda b, g, i: (b, g, at(i), 0)),
        pl.BlockSpec((1, 1, Q, hb), lambda b, g, i: (b, g, at(i), 0)),
        pl.BlockSpec((1, 1, hb, Q), lambda b, g, i: (b, g, 0, at(i))),
        pl.BlockSpec((1, Q, N), lambda b, g, i: (b, at(i), g)),
        pl.BlockSpec((1, Q, N), lambda b, g, i: (b, at(i), g)),
    ]


def _note(kernel, Q, nc, H, state_bytes):
    reg = get_registry()
    reg.gauge("ssd.chunk", kernel=kernel).set(Q)
    reg.gauge("ssd.chunks", kernel=kernel).set(nc)
    reg.gauge("ssd.heads", kernel=kernel).set(H)
    reg.gauge("ssd.state_bytes", kernel=kernel).set(state_bytes)


@functools.lru_cache(maxsize=64)
def _fwd_call(Bt, T, G, hb, P, N, Q, dtype, with_states, carry, interpret):
    nc, H = T // Q, G * hb
    state = jax.ShapeDtypeStruct((Bt, nc, H * P, N), F32)
    y = jax.ShapeDtypeStruct((Bt, T, H * P), dtype)
    y_spec = pl.BlockSpec((1, Q, hb * P), lambda b, g, i: (b, i, g))
    st_spec = pl.BlockSpec((1, 1, hb * P, N), lambda b, g, i: (b, i, g, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, P=P, with_states=with_states),
        name="ssd_fwd",
        grid=(Bt, G, nc),
        in_specs=_specs(Q, hb, P, N, lambda i: i),
        out_specs=[y_spec, st_spec] if with_states else [y_spec],
        out_shape=[y, state] if with_states else [y],
        scratch_shapes=[pltpu.VMEM((hb * P, N), carry)] + _split_scratch(
            Q, hb * P, dtype),
        compiler_params=_params(),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _bwd_call(Bt, T, G, hb, P, N, Q, dtype, interpret):
    nc, H = T // Q, G * hb
    rev = lambda i: nc - 1 - i  # noqa: E731
    col = pl.BlockSpec((1, 1, Q, hb), lambda b, g, i: (b, g, rev(i), 0))
    row = pl.BlockSpec((1, 1, hb, Q), lambda b, g, i: (b, g, 0, rev(i)))
    wide = pl.BlockSpec((1, Q, hb * P), lambda b, g, i: (b, rev(i), g))
    bc = pl.BlockSpec((1, Q, N), lambda b, g, i: (b, rev(i), g))
    sds = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, P=P),
        name="ssd_bwd",
        grid=(Bt, G, nc),
        in_specs=_specs(Q, hb, P, N, rev) + [
            wide, pl.BlockSpec((1, 1, hb * P, N),
                               lambda b, g, i: (b, rev(i), g, 0))],
        out_specs=[wide, col, col, row, bc, bc,
                   pl.BlockSpec((1, 1, 1, hb, P),
                                lambda b, g, i: (b, g, rev(i), 0, 0))],
        out_shape=[sds((Bt, T, H * P), dtype), sds((Bt, G, T, hb), F32),
                   sds((Bt, G, T, hb), F32), sds((Bt, G, hb, T), F32),
                   sds((Bt, T, G * N), dtype), sds((Bt, T, G * N), dtype),
                   sds((Bt, G, nc, hb, P), F32)],
        scratch_shapes=[pltpu.VMEM((hb * P, N), F32),
                        pltpu.VMEM((Q, hb * P), dtype)] + _split_scratch(
            Q, hb * P, dtype),
        compiler_params=_params(),
        interpret=interpret,
    )


def _decays(cs, Q):
    """``exp(cs_Q) [B G T/Q, 1, H/G]``: each chunk's whole decay."""
    return jnp.exp(cs[:, :, Q - 1::Q]).reshape(-1, 1, cs.shape[-1])


def _sizes(x, dt, b):
    """``(B, T, G, H/G, P, N)`` of the kernel's layouts."""
    Bt, T, _ = x.shape
    _, G, _, hb = dt.shape
    return Bt, T, G, hb, x.shape[-1] // (G * hb), b.shape[-1] // G


def _forward(x, dt, cs, b, c, d, Q, interpret, with_states):
    """``x [B, T, H P]``, ``dt, cs [B, G, T, H/G]`` float32, ``b, c
    [B, T, G N]``, ``d [G, 1, H/G]`` float32."""
    Bt, T, G, hb, P, N = _sizes(x, dt, b)
    _note("fwd", Q, T // Q, G * hb,
          Bt * (T // Q) * G * hb * P * N * 4 if with_states else 0)
    return _fwd_call(Bt, T, G, hb, P, N, Q, x.dtype, with_states,
                     jnp.dtype(CARRY_DTYPE), interpret)(
        _decays(cs, Q), d, x, dt, cs, jnp.swapaxes(cs, 2, 3), b, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_core(x, dt, cs, b, c, d, Q, interpret):
    return _forward(x, dt, cs, b, c, d, Q, interpret, False)[0]


def _core_fwd(x, dt, cs, b, c, d, Q, interpret):
    y, states = _forward(x, dt, cs, b, c, d, Q, interpret, True)
    # named for a caller's recomputation policy: a block under
    # ``jax.checkpoint`` that saves these two does not walk the chunks
    # again in the backward pass (models/transformer.py)
    y, states = checkpoint_name(y, SSD_OUT), checkpoint_name(states, SSD_OUT)
    return y, (x, dt, cs, b, c, d, states)


def _core_bwd(Q, interpret, res, dy):
    x, dt, cs, b, c, d, states = res
    Bt, T, G, hb, P, N = _sizes(x, dt, b)
    _note("bwd", Q, T // Q, G * hb, states.size * 4)
    dx, ddt, dcs, dcsr, db, dc, dd = _bwd_call(
        Bt, T, G, hb, P, N, Q, x.dtype, interpret)(
        _decays(cs, Q), d, x, dt, cs, jnp.swapaxes(cs, 2, 3), b, c,
        dy.astype(x.dtype), states)
    # d cs_s gains the row sums at s and loses the column sums at s
    dcs = dcs - jnp.swapaxes(dcsr, 2, 3)
    dd = jnp.sum(dd, axis=(0, 2, 4)).reshape(d.shape)
    return dx, ddt, dcs, db, dc, dd


_ssd_core.defvjp(_core_fwd, _core_bwd)


def _prepare(x, dt, A, B, C, D, chunk):
    """The kernel's layouts from the caller's: ``T`` padded to whole
    chunks, heads folded into lanes, ``dt`` and the in-chunk running
    sums of ``dt * A`` group-major in float32."""
    Bt, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    if H % G:
        raise ValueError(f"{H} heads do not divide into {G} groups")
    hb = H // G
    pad = -T % chunk
    if pad:
        grow = lambda a: jnp.pad(  # noqa: E731
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = grow(x), grow(dt), grow(B), grow(C)
    Tp = T + pad
    dt = dt.astype(F32)
    a = dt * A.astype(F32)
    cs = jnp.cumsum(a.reshape(Bt, Tp // chunk, chunk, H), axis=2)
    group_major = lambda v: v.reshape(Bt, Tp, G, hb).transpose(  # noqa: E731
        0, 2, 1, 3)
    return (x.reshape(Bt, Tp, H * P), group_major(dt),
            group_major(cs.reshape(Bt, Tp, H)),
            B.astype(x.dtype).reshape(Bt, Tp, G * N),
            C.astype(x.dtype).reshape(Bt, Tp, G * N),
            D.astype(F32).reshape(G, 1, hb))


def ssd_scan(x, dt, A, B, C, D, chunk: int = 128, interpret=None):
    """``y [B, T, H, P]`` of the recurrence above for ``x [B, T, H, P]``,
    ``dt [B, T, H]`` (positive: after its softplus), ``A [H]``
    (negative), ``B, C [B, T, G, N]``, ``D [H]``.  Differentiable in all
    six."""
    interpret = resolve_interpret(interpret, "ssd_scan")
    Bt, T, H, P = x.shape
    y = _ssd_core(*_prepare(x, dt, A, B, C, D, chunk), chunk, interpret)
    return y[:, :T].reshape(Bt, T, H, P)


def ssd_scan_with_states(x, dt, A, B, C, D, chunk: int = 128,
                         interpret=None):
    """``(y, states)``: ``ssd_scan``'s result and the state each chunk
    ENTERED with, ``[B, ceil(T / chunk), H, P, N]`` float32 — what the
    backward pass reads, and what a cache of the recurrence would keep.
    Forward only."""
    interpret = resolve_interpret(interpret, "ssd_scan")
    Bt, T, H, P = x.shape
    y, states = _forward(*_prepare(x, dt, A, B, C, D, chunk), chunk,
                         interpret, True)
    return y[:, :T].reshape(Bt, T, H, P), states.reshape(
        Bt, -1, H, P, states.shape[-1])
