"""Pallas TPU decode-attention kernel (single-token q vs KV cache).

An earlier on-chip decomposition (round 4, TPU v5e) showed MHA
long-context decode bound by the cached-attention read running at
~310-610 GB/s effective — well under the chip's ~700-790 GB/s streaming rate — and a first fused
kernel (grid ``(B, k-blocks)``, per-KV-group thin dots) measured 2x
*slower* than XLA's dense path: per-group ``[1, D] x [D, BS]`` matvecs
starve the MXU.  This is the named v2 design: a **head-parallel
block-diagonal formulation with split-S online reduction** that keeps
every dot a single dense MXU matmul over the *contiguous* cache chunk:

* The cache is stored FLAT ``[B, S, KV*D]`` (``init_cache
  layout="flat"``), so each grid step DMAs one fully contiguous
  ``[BS, KV*D]`` chunk of K and V — the stream the HBM controller
  likes, no per-head striding.  (Reshaping a ``[B, S, KV, 64]`` cache
  at call time is NOT a free view on TPU: the minor-dim retiling is a
  physical copy of the whole cache per step — measured 119 vs 52
  us/layer — which is why the layout lives in the cache itself.)
* The query is pre-arranged (outside the kernel, B*H*KV*D elements -
  trivial) into a **block-diagonal** matrix ``qblk [H, KV*D]`` where row
  ``h`` carries q_h in its KV-group's D-column block and zeros elsewhere.
  One dense dot ``qblk @ k_chunk^T -> [H, BS]`` then computes exactly the
  grouped scores (zero blocks contribute nothing): all heads in ONE
  matmul, padded to >=16 sublanes (an M=12 dot falls off the MXU: Mosaic
  lowers sub-tile matmuls to the VPU at ~0.6 TF/s, measured).
* The PV side runs the transpose trick: ``p [H, BS] @ v_chunk [BS, KV*D]
  -> [H, KV*D]``, whose row ``h`` holds the true output in its group's
  D-block; cross-head terms are discarded by a static onehot contraction
  outside the kernel (NOT take_along_axis — a TPU gather at this shape
  measures ~80 us, 5x the whole kernel).
* **Split-S**: the S axis is the innermost ("arbitrary") grid dim;
  the online-softmax carry (m, l, acc) lives in VMEM scratch across
  S-chunks, so Mosaic pipelines the next chunk's HBM DMA against the
  current chunk's compute — flash-decoding's split-KV reduction, laid
  out for a single sequential TPU core.
* ``pos`` rides scalar prefetch: chunks beyond the written prefix skip
  both compute (``pl.when``) and their DMA (clamped BlockSpec index
  map), so a step at position p reads ceil((p+1)/BS) chunks, not the
  whole cache ring — the dense path always reads all of ``cache_len``.

Arithmetic-intensity check (why the extra block FLOPs are free): both
dots cost ``2*H*KV*D*BS`` FLOPs per ``2*BS*KV*D``-byte chunk -> H
flops/byte of cache stream.  At H=12 and 800 GB/s that is <10 TF/s
against the MXU's >100 — decode stays bandwidth-bound, which is the
point.

Reference frame: the reference's whole reason to exist is moving bytes
at line rate (reference docs/rationale.md); this kernel is that story
for the decode cache stream.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_utils import resolve_interpret

# Default S-chunk. 512 rows x KV*D lanes of bf16 K + V double-buffered
# stays well inside VMEM at any sane KV*D (H=12 MHA: 2 * 2 * 512*768*2B
# = 3 MB); short caches use a single full-size block.
DEFAULT_BLOCK_S = 512
_NEG_INF = -1e30


def _decode_kernel(pos_ref, *refs, ns: int, bs: int, S: int,
                   window: Optional[int], quant: bool, cdt):
    if quant:
        (qblk_ref, k_ref, v_ref, ks_ref, vs_ref, oh_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        (qblk_ref, k_ref, v_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
        ks_ref = vs_ref = oh_ref = None
    j = pl.program_id(1)
    pos = pos_ref[0]
    H = qblk_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    compute = j * bs <= pos
    if window is not None:
        compute = compute & (j * bs + bs - 1 > pos - window)

    @pl.when(compute)
    def _step():
        qb = qblk_ref[0]                       # [Hp, KV*D]
        k = k_ref[0]                           # [BS, KV*D]
        if quant:
            # the s8 chunk streams half the HBM bytes (the whole point);
            # the VMEM-resident convert feeds the MXU at the compute
            # dtype.  Dequant scale commutes out of the D-contraction
            # (constant along D within a head's block), applied to the
            # scores below via the onehot row->group map.
            k = k.astype(cdt)
        s = jax.lax.dot_general(
            qb, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Hp, BS]
        if quant:
            # scale[h, j] = k_scale[j, grp[h]]: [Hp, KV] @ [BS, KV]^T
            srow = jax.lax.dot_general(
                oh_ref[...], ks_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [Hp, BS]
            s = s * srow
        kidx = j * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
        valid = kidx <= pos
        if window is not None:
            valid = valid & (kidx > pos - window)
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
            # v's scale is constant along the contracted S axis's
            # *partner* (the output D-block) but varies per (row, head):
            # fold v_scale[j, grp[h]] into p before the PV dot — row h's
            # output block then carries the dequantized sum, cross-head
            # columns are garbage and discarded outside.  Mask invalid
            # columns FIRST: a tail chunk's out-of-range scale rows are
            # padding (arbitrary bits — NaN on hardware), and p's zero
            # there does not survive 0 * NaN.
            vrow = jax.lax.dot_general(
                oh_ref[...], vs_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [Hp, BS]
            p = p * jnp.where(valid, vrow, 0.0)
            v = v.astype(cdt)
        if S % bs:
            # the tail chunk's out-of-range rows are padding (NaN in
            # interpret mode, arbitrary bits on hardware); their p
            # columns are exactly 0 but 0 * NaN = NaN, so zero the rows
            # before the PV dot.  Static gate: dividing chunks skip it.
            rows = j * bs + jax.lax.broadcasted_iota(
                jnp.int32, (bs, 1), 0)
            v = jnp.where(rows < S, v, jnp.zeros_like(v))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Hp, KV*D]

    @pl.when(j == ns - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_s",
                                             "interpret"))
def decode_attention(q, ck, cv, pos, *, k_scale=None, v_scale=None,
                     window: Optional[int] = None,
                     block_s: int = DEFAULT_BLOCK_S, interpret=None):
    """Fused single-step cached attention.

    ``q [B, 1, H, D]`` at absolute position ``pos`` (traced scalar or
    int) against caches ``ck/cv [B, S, KV, D]`` whose slots beyond
    ``pos`` are unwritten (``H % KV == 0``; GQA/MQA welcome).  Returns
    ``[B, 1, H, D]``, numerically matching
    ``models.transformer._cached_attention`` at tq=1.

    ``k_scale``/``v_scale`` (``[B, S, KV]`` f32, both or neither) mark
    an int8 cache: ``ck/cv`` are s8 with per-(position, head) symmetric
    scales (``_quantize_kv``).  The s8 chunks stream half the HBM bytes
    and dequantize in VMEM; the scales fold into the scores / the
    probabilities exactly as in the dense mixed-dot path
    (``_cached_attention_q8``), so the result matches it at tq=1.
    """
    B, tq, H, D = q.shape
    if tq != 1:
        raise ValueError(f"decode_attention is tq=1 only, got tq={tq}")
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale or neither")
    S = ck.shape[1]
    if ck.ndim == 3:
        # flat [B, S, KV*D] cache — the layout this kernel exists for.
        # A 4D cache reshaped here costs a PHYSICAL copy of the whole
        # cache every step (XLA relayouts [.., KV, 64] minor-dim tiles;
        # measured 119 vs 52 us/layer at H=12 S=1280) — init_cache
        # stores flat so the stream arrives copy-free.
        KV = ck.shape[2] // D
    else:
        KV = ck.shape[2]
    if H % KV:
        raise ValueError(f"q heads {H} not a multiple of kv heads {KV}")
    G = H // KV
    KVD = KV * D
    interpret = resolve_interpret(interpret, "decode_attention")
    # The chunk size need not divide S: the grid is ceil(S/bs) and the
    # last chunk's out-of-range rows are always masked (kidx <= pos <= S-1),
    # so Mosaic's OOB-read padding never reaches the softmax.  (fit_block
    # is the wrong tool here — gcd fallback at an awkward cache_len like
    # 1248 would shrink the chunk to 32 rows and crawl.)
    # VMEM budget: k+v chunks double-buffered must fit alongside the
    # f32 accumulator — cap the pair at ~8 MB of the ~16 MB VMEM.  Wide
    # models shrink the chunk instead of failing the Mosaic compile
    # (H=32 D=128 MHA: KV*D=4096 -> bs caps at 256).
    # (conservative for the quant path too: the s8 chunk's in-kernel
    # convert transiently holds a compute-dtype copy alongside it)
    itemsize = jnp.dtype(q.dtype).itemsize
    vmem_cap = (8 * 1024 * 1024) // (4 * KVD * itemsize)
    bs = max(8, min(block_s, S, (vmem_cap // 8) * 8))
    if bs % 8:
        bs = S  # single block, "equal to array dim" is always legal
    ns = -(-S // bs)

    # Block-diagonal scaled query [B, H, KV*D]: row h = q_h * D^-1/2 in
    # its group's D-block.  Built in XLA (B*H*KV*D elems, fuses away).
    scale = D ** -0.5
    qh = (q[:, 0] * scale).astype(q.dtype)              # [B, H, D]
    grp = jnp.repeat(jnp.arange(KV), G)                 # [H] head -> group
    onehot = jax.nn.one_hot(grp, KV, dtype=q.dtype)     # [H, KV]
    qblk = (qh[:, :, None, :]
            * onehot[None, :, :, None]).reshape(B, H, KVD)
    # Pad the head rows up to the bf16 sublane tile (16): an M=12 dot
    # drops off the MXU (Mosaic lowers sub-tile matmuls to the VPU —
    # measured ~0.6 TF/s, 7x the whole kernel's cost); at M=16 both
    # dots ride the MXU and the kernel goes bandwidth-bound.  Pad rows
    # are zero queries: their scores are 0/-inf, harmless, discarded.
    Hp = -(-H // 16) * 16
    if Hp != H:
        qblk = jnp.pad(qblk, ((0, 0), (0, Hp - H), (0, 0)))
    kf = ck if ck.ndim == 3 else ck.reshape(B, S, KVD)
    vf = cv if cv.ndim == 3 else cv.reshape(B, S, KVD)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    def kv_idx(b, j, pos_ref):
        jj = jnp.minimum(j, pos_ref[0] // bs)
        if window is not None:
            jj = jnp.maximum(
                jj, jnp.maximum(pos_ref[0] - window + 1, 0) // bs)
        return (b, jj, 0)

    in_specs = [
        pl.BlockSpec((1, Hp, KVD), lambda b, j, p: (b, 0, 0)),
        pl.BlockSpec((1, bs, KVD), kv_idx),
        pl.BlockSpec((1, bs, KVD), kv_idx),
    ]
    operands = [qblk, kf, vf]
    if quant:
        # scale chunks ride the same clamped index map as their s8
        # cache chunks; the padded onehot maps score/probability rows
        # to their group's scale column in-kernel
        in_specs += [
            pl.BlockSpec((1, bs, KV), kv_idx),
            pl.BlockSpec((1, bs, KV), kv_idx),
        ]
        oh_pad = onehot.astype(jnp.float32)
        if Hp != H:
            oh_pad = jnp.pad(oh_pad, ((0, Hp - H), (0, 0)))
        in_specs += [pl.BlockSpec((Hp, KV), lambda b, j, p: (0, 0))]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32), oh_pad]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hp, KVD), lambda b, j, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hp, KVD), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
        ],
    )
    oacc = pl.pallas_call(
        functools.partial(_decode_kernel, ns=ns, bs=bs, S=S,
                          window=window, quant=quant, cdt=q.dtype),
        name="flat_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, KVD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, *operands)

    # Row h's true output lives in its group's D-block; the cross-head
    # columns of the PV dot are discarded by a static onehot contraction.
    # NOT take_along_axis: a TPU gather at this shape measures ~80 us —
    # 5x the whole kernel — while the masked sum fuses to nothing.
    o3 = oacc[:, :H].reshape(B, H, KV, D)
    out = jnp.einsum("bhkd,hk->bhd", o3.astype(jnp.float32),
                     onehot.astype(jnp.float32)).astype(q.dtype)
    return out[:, None]                                  # [B, 1, H, D]


def decode_attention_usable(q_shape, cache_len: int,
                            quant_cache: bool,
                            kv_heads: Optional[int] = None) -> bool:
    """Static gate for the auto-switch: tq=1, and for an s8 cache MHA
    only (``kv_heads == H``).  The kernel is chosen by shape because,
    on an earlier runtime's chip, the flat-s8 kernel won exactly where
    the cache is at its largest — MHA, KV*D=768 — and lost at every GQA
    point (KV*D<=384: the GQA-shrunken cache's byte saving no longer
    pays for the in-VMEM dequant and the KV-deep scale dots).
    GQA s8 caches keep the dense mixed-dot path; explicit
    ``init_cache(layout="flat")`` overrides.  Any cache length works —
    the kernel grid is ceil(S/block) with the tail masked — and wide
    models shrink the chunk to fit VMEM, so the only hard limit is a
    per-head accumulator row that no longer fits (absurd KV*D)."""
    B, tq, H, D = q_shape
    if tq != 1:
        return False
    if quant_cache and (kv_heads is None or kv_heads != H):
        return False
    # f32 accumulator [Hp, KV*D] must stay a small fraction of VMEM
    Hp = -(-H // 16) * 16
    return Hp * H * D * 4 < 4 * 1024 * 1024
