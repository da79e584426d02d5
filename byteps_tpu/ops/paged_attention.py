"""Fused paged-attention decode kernel: block-table-indexed KV reads.

The paged serving engine (serving/blocks.py, PR 9) stores the KV cache
as a pool of fixed-size blocks and — until this kernel — materialized a
dense ``[1, max_seq, ...]`` row per slot per decode tick via
``gather_paged_rows`` before attending it.  That gather re-copies the
entire cache stream every tick, which is exactly the byte traffic the
whole system exists to avoid (docs/rationale.md).  This kernel is the
vLLM PagedAttention
move on the TPU decode kernel (ops/decode_attention.py): the **block
table rides into the kernel** and the BlockSpec index map resolves grid
step ``(b, j)`` to the *physical* block id, so each step DMAs one
contiguous KV block straight out of the pool — no gather, no dense row,
no extra copy of the cache stream.

Everything else transfers wholesale from the v2 decode kernel — this is
that kernel's v3 with an indirection in the index map:

* the cache pool is stored FLAT ``[n_blocks, block, KV*D]``
  (``init_paged_cache(layout="flat")``), so one block is one fully
  contiguous ``[block, KV*D]`` chunk — the stream the HBM controller
  likes, no per-head striding (reshaping a ``[.., KV, D]`` pool at call
  time is a physical copy of the whole pool, the very bug this layout
  exists to avoid);
* the query is pre-arranged into the **block-diagonal** ``[tq*H, KV*D]``
  form (row ``(i, h)`` carries q of query position ``i``, head ``h`` in
  its KV-group's D-column block), so the score and PV sides are each ONE
  dense MXU matmul per chunk, GQA/MQA included, padded to >=16 rows so
  the dot stays on the MXU;
* **split-S online softmax**: the logical-block axis is the innermost
  ("arbitrary") grid dim, the (m, l, acc) carry lives in VMEM scratch,
  and Mosaic pipelines the next block's DMA against the current block's
  compute;
* the slot's block table and write cursor ride **scalar prefetch**:
  chunks beyond the written prefix skip compute (``pl.when``) AND their
  DMA — the index map clamps the logical index to the cursor's block,
  and Mosaic skips the copy when consecutive grid steps resolve to the
  same physical block.  A slot at position p therefore reads
  ``ceil((p + tq) / block)`` blocks — allocated, position-covered
  blocks only, never the null block's padding.

The kernel generalizes to ``tq >= 1`` query positions so the
speculative-decoding verify pass (PR 12: the decode step widened to
k+1 positions) rides the SAME kernel as plain decode: per query row the
online-softmax accumulation order over chunks is identical regardless
of ``tq`` (rows are independent in both dots), which is what keeps
spec-on token-identical to spec-off on the kernel path — the same
one-implementation argument the dense engine makes, one indirection
deeper.

Numerics vs the gather path: the gather path computes one dense softmax
over the full row; this kernel computes the same softmax as an online
chunked reduction.  The results agree to float rounding (different
accumulation order), NOT bit-for-bit — greedy/seeded token parity is
pinned by tests/test_paged_attention.py, and the engine never mixes the
two paths within one stream (the kernel serves decode AND verify, or
neither).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_utils import resolve_interpret

_NEG_INF = -1e30


def _paged_kernel(tab_ref, pos_ref, *refs, nb: int, bs: int, tq: int,
                  H: int, window: Optional[int], quant: bool, cdt):
    if quant:
        (qblk_ref, k_ref, v_ref, ks_ref, vs_ref, oh_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        qblk_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = oh_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]
    Rp = qblk_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the last query position (pos + tq - 1) bounds the readable prefix;
    # a window additionally floors it at the FIRST query's window start
    compute = j * bs <= pos + tq - 1
    if window is not None:
        compute = compute & (j * bs + bs - 1 > pos - window)

    @pl.when(compute)
    def _step():
        qb = qblk_ref[0]                       # [Rp, KV*D]
        k = k_ref[0]                           # [BS, KV*D]
        if quant:
            # the s8 block streams half the pool's HBM bytes (the whole
            # point); the VMEM-resident convert feeds the MXU at the
            # compute dtype.  Dequant scale commutes out of the
            # D-contraction (constant along D within a head's block)
            # and lands on the scores below via the onehot row->group
            # map — ops/decode_attention.py, one indirection deeper.
            k = k.astype(cdt)
        s = jax.lax.dot_general(
            qb, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Rp, BS]
        # row r = (i, h) with i = r // H: query i sits at absolute
        # position pos + i, so its causal frontier is per-row.  Pad
        # rows (r >= tq*H) are zero queries — their mask is harmless
        # and their output is discarded outside.
        kidx = j * bs + jax.lax.broadcasted_iota(jnp.int32, (Rp, bs), 1)
        qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (Rp, bs), 0) // H
        valid = kidx <= qpos
        if window is not None:
            valid = valid & (kidx > qpos - window)
        if quant:
            # scale[r, c] = k_scale[c, grp[r % H]]: [Rp, KV] @ [BS, KV]^T
            srow = jax.lax.dot_general(
                oh_ref[...], ks_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [Rp, BS]
            s = s * srow
        s = jnp.where(valid, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0]
        if quant:
            # v's scale varies per (position, head): fold
            # v_scale[c, grp[r % H]] into p before the PV dot — row r's
            # output block then carries the dequantized sum, cross-head
            # columns are garbage and discarded outside.  Mask invalid
            # columns FIRST: positions past a slot's cursor carry a
            # stale tenant's (or the zero-init null block's) scale rows
            # — p is exactly 0 there, but 0 * garbage must stay 0.
            vrow = jax.lax.dot_general(
                oh_ref[...], vs_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [Rp, BS]
            p = p * jnp.where(valid, vrow, 0.0)
            v = v.astype(cdt)
        # no tail handling: every physical block is exactly `bs` rows
        # (the pool's second dim), so chunks are never ragged
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Rp, KV*D]

    @pl.when(j == nb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, ck, cv, table, pos, *,
                           k_scale=None, v_scale=None,
                           window: Optional[int] = None, interpret=None):
    """Fused cached attention straight out of a paged block pool.

    ``q [B, tq, H, D]`` — per slot ``b``, ``tq`` fresh query positions
    at absolute offsets ``pos[b] + i`` (``tq = 1`` is the plain decode
    step; ``tq = k + 1`` is the speculative verify widening) — against
    flat block pools ``ck/cv [n_blocks, block, KV*D]`` indexed by the
    per-slot block table ``table [B, max_blocks]`` (int32, unallocated
    entries pointing at the null block).  The fresh positions' K/V must
    already be scattered into the pool (the engine writes before the
    kernel reads — models/transformer.py paged-view branch).  Returns
    ``[B, tq, H, D]``, numerically matching ``_cached_attention`` over
    the gathered dense row (same softmax as an online chunked
    reduction; token parity pinned, bit-equality not claimed).

    Blocks past each slot's written prefix are neither read nor
    computed: the index map clamps the logical block index at the last
    query's block (consecutive same-block steps skip the DMA) and
    ``pl.when`` skips the arithmetic — the per-tick cache stream is
    each slot's ACTUAL prefix, not ``max_blocks * block`` rows of
    null-block padding.

    Quantized pools (``kv_dtype="int8"``, PR 19): pass int8 ``ck/cv``
    plus per-(position, head) scale pools ``k_scale/v_scale
    [n_blocks, block, KV]`` and each grid step DMAs the s8 chunk + its
    scale rows and dequantizes in-register before the accumulate —
    the HBM stream stays at the pool's (halved) width.
    """
    B, tq, H, D = q.shape
    nb_phys, bs, KVD = ck.shape
    if cv.shape != ck.shape:
        raise ValueError(f"k/v pool shape mismatch: {ck.shape} vs "
                         f"{cv.shape}")
    KV = KVD // D
    if KV * D != KVD or H % KV:
        raise ValueError(
            f"pool minor dim {KVD} is not kv_heads*{D} with kv_heads "
            f"dividing {H} query heads")
    G = H // KV
    nb = table.shape[-1]
    interpret = resolve_interpret(interpret, "paged_decode_attention")

    quant = k_scale is not None or v_scale is not None
    if quant:
        if k_scale is None or v_scale is None:
            raise ValueError("quantized pool needs BOTH k_scale and "
                             "v_scale (per-(position, head) rows)")
        if ck.dtype != jnp.int8:
            raise ValueError(f"scales passed but pool dtype is "
                             f"{ck.dtype}, expected int8")
        want = (nb_phys, bs, KV)
        if tuple(k_scale.shape) != want or tuple(v_scale.shape) != want:
            raise ValueError(
                f"scale pool shape {k_scale.shape}/{v_scale.shape} != "
                f"{want} ([n_blocks, block, kv_heads])")
    elif ck.dtype == jnp.int8:
        raise ValueError("int8 pool needs k_scale/v_scale")

    # Block-diagonal scaled query [B, tq*H (pad 16), KV*D]: row (i, h)
    # = q[i, h] * D^-1/2 in its group's D-block (ops/decode_attention.py
    # — zero blocks contribute nothing, pad rows are zero queries).
    scale = D ** -0.5
    qh = (q * scale).astype(q.dtype)                    # [B, tq, H, D]
    grp = jnp.repeat(jnp.arange(KV), G)                 # [H] head -> group
    onehot = jax.nn.one_hot(grp, KV, dtype=q.dtype)     # [H, KV]
    qblk = (qh[:, :, :, None, :]
            * onehot[None, None, :, :, None]).reshape(B, tq * H, KVD)
    R = tq * H
    Rp = -(-R // 16) * 16
    if Rp != R:
        qblk = jnp.pad(qblk, ((0, 0), (0, Rp - R), (0, 0)))
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(B)
    tab_arr = jnp.asarray(table, jnp.int32).reshape(B, nb)

    def kv_idx(b, j, tab_ref, pos_ref):
        # clamp at the last query position's logical block, then chase
        # the table to the PHYSICAL block — the indirection this kernel
        # exists for.  Clamped (skipped) steps resolve to the previous
        # step's block, so their DMA is elided.
        jj = jnp.minimum(j, (pos_ref[b] + tq - 1) // bs)
        if window is not None:
            jj = jnp.maximum(
                jj, jnp.maximum(pos_ref[b] - window + 1, 0) // bs)
        return (tab_ref[b, jj], 0, 0)

    in_specs = [
        pl.BlockSpec((1, Rp, KVD), lambda b, j, t, p: (b, 0, 0)),
        pl.BlockSpec((1, bs, KVD), kv_idx),
        pl.BlockSpec((1, bs, KVD), kv_idx),
    ]
    operands = [qblk, ck, cv]
    if quant:
        # scale rows ride the SAME indirected index map as their block;
        # the onehot row->group matrix is tiled per query position
        # (row r = (i, h) -> group of head r % H) and grid-constant.
        oh_rows = jnp.tile(onehot, (tq, 1)).astype(jnp.float32)
        if Rp != R:
            oh_rows = jnp.pad(oh_rows, ((0, Rp - R), (0, 0)))
        in_specs += [
            pl.BlockSpec((1, bs, KV), kv_idx),
            pl.BlockSpec((1, bs, KV), kv_idx),
            pl.BlockSpec((Rp, KV), lambda b, j, t, p: (0, 0)),
        ]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32), oh_rows]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Rp, KVD),
                               lambda b, j, t, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Rp, KVD), jnp.float32),
            pltpu.VMEM((Rp, 1), jnp.float32),
            pltpu.VMEM((Rp, 1), jnp.float32),
        ],
    )
    oacc = pl.pallas_call(
        functools.partial(_paged_kernel, nb=nb, bs=bs, tq=tq, H=H,
                          window=window, quant=quant, cdt=q.dtype),
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Rp, KVD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tab_arr, pos_arr, *operands)

    # Row (i, h)'s true output lives in its group's D-block; cross-head
    # columns of the PV dot are discarded by the static onehot
    # contraction (NOT take_along_axis — the decode kernel measured a
    # TPU gather at 5x the whole kernel; the masked sum fuses away).
    o4 = oacc[:, :R].reshape(B, tq, H, KV, D)
    out = jnp.einsum("bthkd,hk->bthd", o4.astype(jnp.float32),
                     onehot.astype(jnp.float32)).astype(q.dtype)
    return out


def paged_decode_attention_sharded(q, ck, cv, table, pos, *,
                                   k_scale=None, v_scale=None,
                                   window: Optional[int] = None,
                                   interpret=None):
    """:func:`paged_decode_attention` over **tensor-parallel** per-shard
    block pools: ``ck/cv [tp, n_blocks, block, (KV/tp)*D]`` (int8 adds
    per-shard scale pools ``[tp, n_blocks, block, KV/tp]``).

    Head slicing is an *exact* partition of the unsharded kernel, not
    an approximation: the block-diagonal query is laid out group-major
    (``grp = repeat(arange(KV), G)``), so query-head slice
    ``[s*H/tp, (s+1)*H/tp)`` interacts with exactly KV-group slice
    ``[s*KV/tp, (s+1)*KV/tp)`` and no other — shard ``s``'s kernel
    call performs the same per-row arithmetic (same chunk order, same
    online-softmax carries) as the corresponding row slice of the
    unsharded call, and the head-axis concat reassembles the unsharded
    output.  Equal to a few ulps, not bit-for-bit: the per-shard dot
    contracts ``KV*D/tp`` columns where the unsharded one contracts
    ``KV*D`` (the rest exact zeros), and a backend may sum the two in a
    different order.  One static Python loop, ``tp`` kernel
    calls per step; under a real tp mesh each call's operands live on
    shard ``s``'s device and the loop is the per-device program
    (docs/parallel.md — the o-projection's row-parallel psum merges
    the outputs there; on one host the concat below is that merge).

    The block table and cursor vector are REPLICATED across shards —
    paging is head-agnostic, which is what lets COW / prefix sharing /
    preempt-resume bookkeeping stay single-copy (serving/blocks.py).
    """
    B, tq, H, D = q.shape
    tp = ck.shape[0]
    if cv.shape != ck.shape:
        raise ValueError(f"k/v pool shape mismatch: {ck.shape} vs "
                         f"{cv.shape}")
    if H % tp:
        raise ValueError(
            f"tp ({tp}) must divide num_heads ({H})")
    Hs = H // tp
    quant = k_scale is not None
    outs = []
    for s in range(tp):
        outs.append(paged_decode_attention(
            q[:, :, s * Hs:(s + 1) * Hs, :], ck[s], cv[s], table, pos,
            k_scale=(k_scale[s] if quant else None),
            v_scale=(v_scale[s] if quant else None),
            window=window, interpret=interpret))
    return jnp.concatenate(outs, axis=2)


def paged_attention_usable(q_shape, block: int, kvd: int) -> bool:
    """Static gate for the engine's ``paged_kernel="auto"`` resolution:
    the f32 accumulator ``[tq*H (pad 16), KV*D]`` must stay a small
    fraction of the ~16 MB VMEM alongside the double-buffered block
    pair.  Any block size works (one block per grid step; larger blocks
    amortize the per-step overhead — BYTEPS_SERVE_BLOCK >= 128 is the
    TPU-efficient setting), and any table length works (skipped chunks
    cost neither DMA nor compute)."""
    B, tq, H, D = q_shape
    Rp = -(-(tq * H) // 16) * 16
    acc = Rp * kvd * 4
    chunks = 4 * block * kvd * 4  # k+v double-buffered, f32 upper bound
    return acc + chunks < 8 * 1024 * 1024
