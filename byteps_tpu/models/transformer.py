"""Decoder-only Transformer with first-class dp x tp x sp parallelism.

The reference never partitions along model dimensions (SURVEY.md §2.4 "Not
present": tensor/sequence parallelism) — this model is the TPU-native
generalization the rebuild treats as first-class.  Parallel design, following
the scaling-book recipe (mesh + annotated shardings + XLA collectives):

* **dp**: batch dim sharded over ``dp`` via input shardings; gradient
  reduction is XLA's automatic psum (or the framework's scheduled push_pull
  when driven through ``shard_map``).
* **tp**: attention heads and MLP hidden dim sharded over ``tp`` with
  ``nn.with_partitioning`` kernel annotations — XLA's SPMD partitioner
  inserts the reduce-scatter/all-reduce pairs (Megatron-style column/row
  split) on ICI.
* **sp**: the sequence dim sharded over ``sp``; exact attention runs as ring
  attention (``lax.ppermute`` K/V rotation) or Ulysses (``all_to_all``)
  inside a ``shard_map`` island — see parallel/ring_attention.py.

Everything is static-shaped; the loop over layers is unrolled at trace
time (layer count is small and static).  A looped model's passes over
the shared stack (``loop_steps``) are one ``nn.scan``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.ring_attention import (
    local_attention,
    ring_attention,
    ulysses_attention,
)


LAYER_KINDS = ("mamba", "attn", "moe", "mlp", "conv")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    # GQA/MQA: number of shared K/V heads (None = num_heads, i.e. MHA).
    # Every group of num_heads/num_kv_heads query heads reads one K/V
    # head — the KV cache shrinks by the same factor, which is *the*
    # decode-bandwidth lever at long context (the cache stream scales
    # with B*T*kv_heads while weights are constant).  Flash attention
    # consumes grouped K/V natively (ops/flash_attention.py _gqa_group);
    # cached decode runs grouped mixed dots without materializing the
    # head repeat; sp/ring paths broadcast K/V to full heads in-register.
    num_kv_heads: Optional[int] = None
    d_model: int = 512
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    causal: bool = True  # False => bidirectional encoder (BERT-style)
    attn_impl: str = "local"  # local | flash | ring | ulysses
    # Mistral-style causal sliding window (flash impl only, no sp axis):
    # each position attends to the last `attn_window` positions
    attn_window: Optional[int] = None
    # an attention kind per layer (window/global hybrids): one entry a
    # layer, each axis a tuple (tuples keep the config hashable).
    # ``attn_window_layout[i]`` is layer i's window or None (attend the
    # whole causal prefix); ``rope_layout[i]`` says whether layer i
    # rotates q/k (``pos_emb`` must then be "rope" or "none": there is
    # no learned table beside a per-layer rotation).  Without a layout
    # the scalars ``attn_window`` / ``pos_emb`` hold for every layer.
    # Training path only: the cache paths raise on a layout.
    attn_window_layout: Optional[tuple] = None
    rope_layout: Optional[tuple] = None
    # architecture axes for GPT-2-family compatibility
    # (integrations/gpt2.py): pre-norm layer norm with bias, biased
    # projections, and an lm_head tied to the input embedding
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-6
    use_bias: bool = False
    tie_embeddings: bool = False
    # architecture axes for LLaMA-family compatibility
    # (integrations/llama.py): rotary embeddings instead of a learned
    # position table, and a gated SwiGLU MLP.  "rope" applies the HF
    # half-split rotation to q/k inside Attention (position-aware in
    # cached decode: cached keys are stored rotated, which preserves
    # the relative-position property)
    pos_emb: str = "learned"  # learned | rope | none
    rope_theta: float = 10000.0
    # frequency-rescaled RoPE for long-context checkpoints (Llama-3.x):
    # a tuple of sorted (key, value) pairs (tuples keep the config
    # hashable) mirroring HF's rope_scaling dict — rope_type "llama3"
    # (factor / low_freq_factor / high_freq_factor /
    # original_max_position_embeddings) or "linear" (factor)
    rope_scaling: Optional[tuple] = None
    # explicit per-head dim (Llama-3.x checkpoints may set
    # head_dim != hidden_size / num_heads); None derives it
    head_dim: Optional[int] = None
    mlp: str = "gelu"  # gelu | swiglu | relu2 (non-gated: down(relu(up)^2))
    # latent attention (DeepSeek-V2 report, section 2.1): queries through
    # a ``q_lora_rank`` latent, keys and values through a
    # ``kv_lora_rank`` latent, each head's key the latent's
    # ``qk_nope_head_dim`` channels beside ONE ``qk_rope_head_dim``-wide
    # rotary key that all heads share; values ``v_head_dim`` wide.
    # ``rope_interleave`` rotates the adjacent channel pairs (2i, 2i+1)
    # instead of the half-split pairs.  Training path only (no cache).
    attn_kind: str = "mha"  # mha | mla
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # fine-grained experts (parallel/moe.py): with ``moe_experts`` > 0
    # the first ``dense_layers`` blocks keep the dense MLP and every
    # later one routes each token to ``moe_top_k`` of ``moe_experts``
    # gated experts of width ``moe_d_ff`` beside ``moe_shared`` shared
    # experts.  ``moe_scoring`` is the router's rule (``sigmoid``:
    # sigmoid scores and a balancing bias, weights normalised;
    # ``softmax_topk``: the top k of the logits, weights their softmax,
    # no bias), the weights multiplied by ``moe_scale``; ``moe_act`` the
    # expert's activation — of the gate for a gated expert (``silu`` |
    # ``relu``), of the up-projection for a non-gated one (``relu2``:
    # ``down(relu(up x)^2)``, two matrices an expert, and the shared
    # expert in the same form); with
    # ``moe_router_pre_attn`` the router scores the block's normalised
    # ATTENTION input (the experts still read the feed-forward input).
    # ``moe_held = (first, count)`` is the contiguous
    # slice of experts THIS rank holds (default: all): the router keeps
    # all its outputs, the layer computes its own experts' part.  With
    # ``moe_ep_axis`` (inside shard_map over that axis) the rank's
    # offset is its axis index times ``count`` and the parts are
    # exchanged and summed.
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_shared: int = 0
    moe_scale: float = 1.0
    moe_scoring: str = "sigmoid"
    moe_act: str = "silu"
    moe_router_pre_attn: bool = False
    moe_held: Optional[tuple] = None
    moe_ep_axis: Optional[str] = None
    dense_layers: int = 0
    # a KIND per layer (state-space / attention / expert hybrids): one
    # entry a layer of ``mamba`` | ``attn`` | ``moe`` | ``mlp`` |
    # ``conv`` (the gated short convolution, ``ShortConv``).  With a
    # kind layout every block is ONE sublayer, ``x + sublayer(norm(x))``
    # (``SublayerBlock``); None means ``Block`` (attention then
    # feed-forward) in every layer.  Training path only: the cache paths
    # raise on a kind layout.
    layer_kinds: Optional[tuple] = None
    # the Mamba-2 mixer of a ``mamba`` layer (``Mamba2Mixer``,
    # ``ops/ssd_scan.py``): ``ssm_heads`` heads of ``ssm_head_dim``
    # channels, a state of ``ssm_state`` a channel, ``B`` and ``C``
    # shared by the heads of each of ``ssm_groups`` groups, a causal
    # depthwise convolution of ``ssm_conv`` taps, the scan in chunks of
    # ``ssm_chunk`` positions
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # the gated short convolution of a ``conv`` layer (``ShortConv``):
    # a causal depthwise convolution of ``conv_taps`` taps between an
    # input gate and an output gate (LFM2's ``conv_L_cache``)
    conv_taps: int = 3
    # an RMS norm over each head's channels of q and of k, each with a
    # scale of its own, before any rotation (``Attention``)
    qk_norm: bool = False
    # multi-token prediction (DeepSeek-V3 report, section 2.2): one module
    # off the final hidden state that predicts the token after next;
    # ``lm_loss_fn`` adds ``mtp_loss_weight`` times its cross-entropy
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    # a norm on each sublayer's OUTPUT as well as its input (``Block``:
    # ``h = x + N2(attn(N1(x)))``, ``h + N4(ffn(N3(h)))``), each with a
    # scale of its own
    sandwich_norm: bool = False
    # a looped model (Ouro's LoopLM): the whole stack of blocks runs
    # ``loop_steps`` times a token with one set of weights, the final
    # norm after every pass and its output fed to the next; an exit gate
    # reads each pass's state, and ``lm_loss_fn`` takes the exit
    # distribution's expected loss less a weight times its entropy
    # (``training/step.py:EXIT_ENTROPY_WEIGHT``).  Training path only:
    # no cache holds a pass's K/V.
    loop_steps: int = 1
    # recompute each block in the backward pass instead of keeping its
    # activations (``nn.remat``)
    remat: bool = False
    # mesh axis names; attention shard_map uses (dp_axis, sp_axis, tp_axis)
    dp_axis: str = "dp"
    sp_axis: str = "sp"
    tp_axis: str = "tp"
    mesh: Optional[Mesh] = None

    @property
    def d_head(self) -> int:
        return (self.head_dim if self.head_dim is not None
                else self.d_model // self.num_heads)

    @property
    def kv_heads(self) -> int:
        kv = self.num_kv_heads
        if kv is None:
            return self.num_heads
        if kv < 1 or self.num_heads % kv:
            raise ValueError(
                f"num_kv_heads {kv} must divide num_heads {self.num_heads}")
        return kv

    @property
    def has_attn_layout(self) -> bool:
        return (self.attn_window_layout is not None
                or self.rope_layout is not None)

    def _layout_entry(self, layout, layer, default):
        if layout is None or layer is None:
            return default
        if len(layout) != self.num_layers:
            raise ValueError(
                f"a per-layer layout has {len(layout)} entries, the model "
                f"{self.num_layers} layers")
        return layout[layer]

    def layer_window(self, layer: Optional[int]) -> Optional[int]:
        """Layer ``layer``'s attention window (None: the whole causal
        prefix); ``attn_window`` without a layout or a layer."""
        return self._layout_entry(self.attn_window_layout, layer,
                                  self.attn_window)

    def layer_rope(self, layer: Optional[int]) -> bool:
        """Does layer ``layer`` rotate q/k?"""
        if self.rope_layout is not None and self.pos_emb == "learned":
            raise ValueError("rope_layout needs pos_emb 'rope' or 'none', "
                             "not a learned table")
        return bool(self._layout_entry(self.rope_layout, layer,
                                       self.pos_emb == "rope"))

    def partition(self, init, spec):
        """Wrap an initializer with tp-sharding metadata — only when this
        config's mesh actually has the tp axis (flax re-applies the
        constraint at apply time, so a dangling axis name would fail under
        a dp-only mesh)."""
        if self.mesh is not None and self.tp_axis in self.mesh.axis_names:
            return nn.with_partitioning(init, spec)
        return init

    def make_norm(self, name: str):
        if self.norm == "layernorm":
            return nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                                name=name)
        if self.norm != "rmsnorm":
            raise ValueError(f"unknown norm {self.norm!r}")
        return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                          name=name)

    def block_cls(self):
        """``Block`` (``SublayerBlock`` under a kind layout), or with
        ``remat`` that block recomputed in the backward pass — all of it
        but what is small to keep and dear to make again: the flash
        forward kernel's output and log-sum-exp (the kernel would
        otherwise run twice), the expert layer's choice and layout (a
        top-k and a sort) and, under a kind layout, the state-space
        scan's output and chunk-boundary states (its sequential walk
        would otherwise run twice)."""
        from ..ops.flash_attention import FLASH_OUT
        from ..parallel.moe import PLAN

        cls, keep = Block, (FLASH_OUT, PLAN)
        if self.layer_kinds is not None:
            from ..ops.ssd_scan import SSD_OUT

            cls, keep = SublayerBlock, keep + (SSD_OUT,)
        if not self.remat:
            return cls
        return nn.remat(cls, policy=jax.checkpoint_policies.
                        save_only_these_names(*keep))

    def layer_kind(self, layer: int) -> str:
        """Layer ``layer``'s kind under a kind layout."""
        kind = self._layout_entry(self.layer_kinds, layer, None)
        if kind not in LAYER_KINDS:
            raise ValueError(f"unknown layer kind {kind!r}: {LAYER_KINDS}")
        return kind

    @property
    def has_sp(self) -> bool:
        """True when the mesh carries an active (>1) sequence axis."""
        return (self.mesh is not None
                and self.sp_axis in self.mesh.axis_names
                and self.mesh.shape[self.sp_axis] > 1)

    def attention_fn(self, layer: Optional[int] = None):
        causal = self.causal
        names = set(self.mesh.axis_names) if self.mesh is not None else set()
        has_sp = self.has_sp
        if self.attn_impl == "flash" and not has_sp:
            from ..ops.flash_attention import flash_attention

            window = self.layer_window(layer)
            return lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                                   window=window)
        if self.layer_window(layer) is not None:
            raise ValueError(
                "attn_window requires attn_impl='flash' without an active "
                f"sp axis (got attn_impl={self.attn_impl!r})")
        if self.attn_impl == "local" or self.mesh is None:
            return lambda q, k, v: local_attention(q, k, v, causal=causal)
        if self.attn_impl == "flash":
            # flash (x) sp: ring schedule with the Pallas kernel per block
            from ..parallel.ring_attention import ring_flash_attention

            inner = ring_flash_attention
        else:
            inner = (ring_attention if self.attn_impl == "ring"
                     else ulysses_attention)
        if self.sp_axis not in names:
            return lambda q, k, v: local_attention(q, k, v, causal=causal)
        spec = P(
            self.dp_axis if self.dp_axis in names else None,
            self.sp_axis,
            self.tp_axis if self.tp_axis in names else None,
            None,
        )

        from ..parallel.collectives import shard_map

        fn = partial(inner, axis_name=self.sp_axis, causal=causal)
        return shard_map(
            fn, mesh=self.mesh, in_specs=(spec, spec, spec), out_specs=spec
        )


class QuantDense(nn.Module):
    """Dense / DenseGeneral replacement that also accepts int8
    weight-only-quantized parameter trees.

    With an fp tree (``kernel`` float, no ``scale``) it computes exactly
    what ``nn.Dense``/``nn.DenseGeneral`` compute.  With a quantized tree
    (``kernel`` int8 + per-output-channel fp32 ``scale``, produced by
    ``inference.quantize_params``) it dequantizes *inside* the matmul —
    ``kernel.astype(dtype) * scale`` fuses into the dot's operand read, so
    HBM streams int8 bytes.  That halves decode's weight traffic, which is
    the whole cost of bandwidth-bound generation.
    ``init`` never creates ``scale``: quantization is a property of the
    parameter tree, not the module.

    ``features`` may be an int or tuple; ``in_axes`` is how many trailing
    input dims contract (1 for Dense/qkv, 2 for the o-projection).
    """

    features: Any
    in_axes: int = 1
    dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    use_bias: bool = False
    # accumulate/output dtype of the dot when it differs from the operand
    # dtype (preferred_element_type).  The lm_head uses dtype=bf16,
    # accum_dtype=f32: bf16 operands stream at half the HBM bytes while
    # the MXU still accumulates and emits fp32 logits.  An explicit
    # .astype(f32) on a bf16 dot's OUTPUT would be (nearly) the same math
    # but reads the weight as a separate bf16->f32 convert instruction,
    # which XLA materializes as a full-size temp inside a decode loop.
    accum_dtype: Any = None

    @nn.compact
    def __call__(self, x):
        feats = (self.features if isinstance(self.features, tuple)
                 else (self.features,))
        kshape = tuple(x.shape[-self.in_axes:]) + feats
        kernel = self.param("kernel", self.kernel_init, kshape)
        quantized = self.has_variable("params", "scale")
        dims = ((tuple(range(x.ndim - self.in_axes, x.ndim)),
                 tuple(range(self.in_axes))), ((), ()))
        out_dtype = self.accum_dtype if self.accum_dtype else self.dtype
        if quantized:
            scale = self.get_variable("params", "scale")
            if isinstance(scale, nn.meta.AxisMetadata):
                # a tp-sharded quantized tree may arrive still boxed
                # (nn.Partitioned); self.param unboxes automatically but
                # get_variable does not
                scale = scale.unbox()
            # int8 weight-only: the dot consumes the s8 kernel DIRECTLY
            # (mixed s8 x bf16 dot) — an explicit kernel.astype(bf16)
            # compiles to a standalone convert that materializes a
            # full-size bf16 temp every decode step (XLA LICM must then
            # be defeated, and even in-body the temp's write+read triples
            # the traffic; measured on-chip r4).  The per-output-channel
            # scale commutes out of the contraction — x @ (q * s) ==
            # (x @ q) * s — so dequant applies to the [..., out]
            # activation after the dot.  (A per-dot Pallas dequant kernel
            # was measured slower here: 73 small pallas_calls per decode
            # step pay more in launch overhead than the s8 stream saves;
            # the mixed dot + AUTO input layouts — see
            # inference.make_generate_fn — reads s8 at full rate.)
            #
            # preferred_element_type MUST stay the operand dtype even
            # when accum_dtype asks for f32: a mixed dot with an f32
            # output makes XLA convert the whole s8 kernel to an f32
            # temp hoisted OUT of the decode loop — the lm_head then
            # streams 4 bytes/param instead of 1 (measured r4: 125 us vs
            # 65 us per B=1 matvec at V=32k).  The MXU accumulates f32
            # internally either way; the one extra bf16 rounding at the
            # dot output is the same class as the bf16 weight rounding
            # quantization already accepts, and the upcast-then-scale
            # below restores the accum dtype for downstream sampling.
            y = jax.lax.dot_general(
                x.astype(self.dtype), kernel, dims,
                preferred_element_type=self.dtype)
            y = y.astype(out_dtype) * scale.astype(out_dtype)
        else:
            y = jax.lax.dot_general(
                x.astype(self.dtype), kernel.astype(self.dtype), dims,
                preferred_element_type=out_dtype)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, feats)
            y = y + bias.astype(out_dtype)
        return y


def _quantize_kv(x):
    """Per-(position, head) symmetric int8 quantization of K or V
    ``[B, t, H, D]`` -> (s8 values, f32 scales ``[B, t, H]``)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127)
    return q.astype(jnp.int8), scale


def _scaled_inv_freq(inv_freq, scaling):
    """Frequency rescaling for long-context RoPE variants, matching HF's
    ``_compute_llama3_parameters`` / linear scaling exactly (the angles
    must agree with the torch reference for converted checkpoints).

    ``scaling`` is a dict or tuple of pairs: rope_type "linear" divides
    every frequency by ``factor``; "llama3" keeps high frequencies,
    divides low ones, and smoothly interpolates the band between
    (wavelengths measured against original_max_position_embeddings)."""
    s = dict(scaling)
    rt = s.get("rope_type", s.get("type", "default"))
    if rt in (None, "default"):
        return inv_freq
    factor = float(s.get("factor", 1.0))
    if rt == "linear":
        return inv_freq / factor
    if rt == "llama3":
        low = float(s.get("low_freq_factor", 1.0))
        high = float(s.get("high_freq_factor", 4.0))
        orig = float(s.get("original_max_position_embeddings", 8192))
        wavelen = 2.0 * jnp.pi / inv_freq
        scaled = inv_freq / factor
        smooth = (orig / wavelen - low) / (high - low)
        smoothed = (1.0 - smooth) * scaled + smooth * inv_freq
        return jnp.where(
            wavelen < orig / high, inv_freq,
            jnp.where(wavelen > orig / low, scaled, smoothed))
    raise ValueError(f"unsupported rope_scaling type {rt!r}")


def apply_rope(x, positions, theta: float = 10000.0, scaling=None,
               interleave: bool = False):
    """Rotary position embedding, HF half-split convention (or, with
    ``interleave``, the adjacent pairs (x[2i], x[2i+1]) at the same
    angles):
    ``x [B, T, H, D]`` rotated by per-position angles
    ``pos / theta^(2i/D)``; ``positions`` is ``[T]`` absolute offsets
    (prefill: ``arange(T)``; decode step: ``pos + arange(tq)``) or
    ``[B, T]`` when each batch row sits at its own offset (the fused
    paged decode step — every serving slot has its own cursor).

    The rotation acts on (x[..., :D/2], x[..., D/2:]) pairs — the same
    ``rotate_half`` layout HF LLaMA uses, so converted q/k weights work
    unpermuted (integrations/llama.py).  Computed in fp32 and cast back:
    the angles lose too much to bf16 at long context.  ``scaling``
    applies the Llama-3-family frequency rescale (see
    ``_scaled_inv_freq``).
    """
    D = x.shape[-1]
    half = D // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                / half))
    if scaling is not None:
        inv_freq = _scaled_inv_freq(inv_freq, scaling)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if ang.ndim == 2:                      # [T, D/2] -> broadcast batch
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]      # [1|B, T, 1, D/2]
    sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        pairs = xf.reshape(xf.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(xf.shape).astype(x.dtype)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _group_q(q, KV):
    """``[B, tq, H, D] -> [B, KV, G*tq, D]`` with ``G = H // KV``: query
    heads fold onto their shared K/V head's batch row (group-major,
    query-position-minor), so cached GQA attention is two plain batched
    dots against the *un-repeated* cache — the whole point of GQA is
    that the cache streams KV heads' bytes, and a materialized
    ``jnp.repeat`` would hand that win straight back."""
    B, tq, H, D = q.shape
    G = H // KV
    return (q.reshape(B, tq, KV, G, D).transpose(0, 2, 3, 1, 4)
            .reshape(B, KV, G * tq, D))


def _ungroup_o(o, tq):
    """Inverse of ``_group_q`` on the attention output:
    ``[B, KV, G*tq, D] -> [B, tq, KV*G, D]``."""
    B, KV, GT, D = o.shape
    G = GT // tq
    return (o.reshape(B, KV, G, tq, D).transpose(0, 3, 1, 2, 4)
            .reshape(B, tq, KV * G, D))


def _grouped_mask(S, tq, G, pos, window):
    """Causal (+ optional sliding-window) keep-mask ``[1, 1, G*tq, S]``
    matching ``_group_q``'s row order (each query position appears once
    per group, at the same absolute offset)."""
    kidx = jnp.arange(S)[None, None, None, :]
    qidx = jnp.tile(pos + jnp.arange(tq), G)[None, None, :, None]
    mask = kidx <= qidx
    if window is not None:
        mask = mask & (kidx > qidx - window)
    return mask


def _cached_attention_q8(q, ck, ck_scale, cv, cv_scale, pos, window=None):
    """Dense cached attention against an int8-quantized KV cache
    (``ck/cv [B, S, KV, D]`` s8 with per-(position, head) f32 scales);
    ``KV`` may be fewer heads than q carries (GQA/MQA).

    The dequant never materializes: K's scale commutes out of the QK^T
    contraction (it is constant along D), so the score dot runs mixed
    ``bf16 x s8`` and the scale multiplies the [B, KV, G*tq, S] scores;
    V's scale is constant along the *contracted* S axis, so it folds
    into the probabilities before the mixed PV dot — the cache streams
    s8 bytes end to end, halving decode's second-largest HBM read.
    """
    B, tq, H, D = q.shape
    KV = ck.shape[2]
    scale = D ** -0.5
    qg = _group_q((q * scale).astype(q.dtype), KV)
    # scores[b,c,r,k] = sum_d qg[b,c,r,d] * ck[b,k,c,d]  (mixed s8 dot).
    # preferred_element_type MUST stay the operand dtype: asking the
    # mixed dot for an f32 output makes XLA convert the whole s8 cache
    # to a materialized f32 temp every step (observed r4) — the dot
    # accumulates f32 internally either way, and the [B, KV, G*tq, S]
    # scores are upcast right after, which is cheap.
    scores = jax.lax.dot_general(
        qg, ck, (((3,), (3,)), ((0, 1), (0, 2))),
        preferred_element_type=q.dtype)            # [B, KV, G*tq, S]
    scores = (scores.astype(jnp.float32)
              * jnp.transpose(ck_scale, (0, 2, 1))[:, :, None, :])
    mask = _grouped_mask(ck.shape[1], tq, H // KV, pos, window)
    scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    probs = (probs
             * jnp.transpose(cv_scale, (0, 2, 1))[:, :, None, :]
             ).astype(q.dtype)
    # out[b,c,r,d] = sum_k probs[b,c,r,k] * cv[b,k,c,d]  (mixed s8 dot;
    # same rule — output at operand dtype so the s8 cache is consumed
    # directly)
    out = jax.lax.dot_general(
        probs, cv, (((3,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=q.dtype)            # [B, KV, G*tq, D]
    return _ungroup_o(out, tq).astype(q.dtype)


def _cached_attention(q, ck, cv, pos, window=None):
    """Dense attention of ``q [B, tq, H, D]`` (absolute offset ``pos``)
    against a KV cache ``ck/cv [B, S, KV, D]`` whose slots beyond
    ``pos + tq`` are unwritten; ``KV`` may be fewer heads than q
    carries (GQA/MQA — each group of H/KV query heads reads one cache
    head, via ``_group_q``'s fold rather than a materialized repeat).

    The causal mask ``key_j <= pos + i`` both enforces autoregressive
    order and excludes the unwritten tail, so one static-shape program
    serves prefill (tq = prompt length, pos = 0) and decode (tq = 1)
    alike — no dynamic shapes, no recompilation per step.  O(S) dense
    scores are the right call here: decode is HBM-bound on the cache
    read anyway, and tq is tiny.
    """
    B, tq, H, D = q.shape
    KV = ck.shape[2]
    scale = D ** -0.5
    qg = _group_q(q * scale, KV)
    scores = jax.lax.dot_general(
        qg, ck, (((3,), (3,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32)        # [B, KV, G*tq, S]
    mask = _grouped_mask(ck.shape[1], tq, H // KV, pos, window)
    scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jax.lax.dot_general(
        probs, cv, (((3,), (1,)), ((0, 1), (0, 2))))
    return _ungroup_o(out, tq)


class Attention(nn.Module):
    cfg: TransformerConfig
    # the layer's index, read against the config's per-layer layout
    # (window or whole prefix, RoPE or none); None: the scalars hold
    layer: Optional[int] = None

    @nn.compact
    def __call__(self, x, key_mask=None, cache=None, pos=None):
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.d_head
        KV = cfg.kv_heads
        if cache is not None and cfg.has_attn_layout:
            raise NotImplementedError(
                "a per-layer attention layout is built for training: no "
                "cache knows a layer's kind yet")
        window = cfg.layer_window(self.layer)
        proj = partial(
            QuantDense, dtype=cfg.dtype, use_bias=cfg.use_bias,
            kernel_init=cfg.partition(
                nn.initializers.xavier_uniform(), (None, cfg.tp_axis, None)
            ),
        )
        q = proj(features=(H, D), name="q")(x)
        kv_proj = proj
        if (cfg.mesh is not None and cfg.tp_axis in cfg.mesh.axis_names
                and KV % cfg.mesh.shape[cfg.tp_axis]):
            # MQA/small-KV under tensor parallelism: the kv head axis
            # (KV entries) is not divisible by the tp size, so sharding
            # it would fail deep inside GSPMD.  Replicate the k/v
            # kernels instead (the standard Megatron MQA treatment —
            # they are num_heads/KV-fold smaller than q's anyway).
            kv_proj = partial(QuantDense, dtype=cfg.dtype,
                              use_bias=cfg.use_bias,
                              kernel_init=nn.initializers.xavier_uniform())
        k = kv_proj(features=(KV, D), name="k")(x)
        v = kv_proj(features=(KV, D), name="v")(x)
        if cfg.qk_norm:
            q = cfg.make_norm("q_norm")(q)
            k = cfg.make_norm("k_norm")(k)
        if cfg.layer_rope(self.layer):
            # rotate q/k before the cache write and before any attention
            # path (flash/local/ring all consume rotated q/k; cached K
            # is stored rotated — RoPE's relative-position property
            # makes scores depend only on position deltas, so rotating
            # at write time is exact)
            if cache is None:
                rpos = jnp.arange(x.shape[1])
            elif jnp.ndim(pos) == 1:
                # fused paged decode: per-slot cursors [B]
                rpos = pos[:, None] + jnp.arange(x.shape[1])[None, :]
            else:
                rpos = pos + jnp.arange(x.shape[1])
            q = apply_rope(q, rpos, cfg.rope_theta, cfg.rope_scaling)
            k = apply_rope(k, rpos, cfg.rope_theta, cfg.rope_scaling)
        o_proj = QuantDense(
            features=cfg.d_model, in_axes=2, dtype=cfg.dtype, name="o",
            use_bias=cfg.use_bias,
            kernel_init=cfg.partition(
                nn.initializers.xavier_uniform(), (cfg.tp_axis, None, None)
            ),
        )
        if cache is not None:
            # autoregressive decode/prefill against an explicit KV cache
            # (a functional pytree the caller threads through lax.scan —
            # not flax mutable state, so the whole loop jits cleanly)
            if not cfg.causal:
                raise ValueError("KV-cache decode requires causal=True")
            if key_mask is not None:
                raise ValueError(
                    "KV-cache decode does not support key_mask: pad "
                    "tokens' K/V would enter the cache as real context. "
                    "Strip padding from the prompt before generate().")
            if "table" in cache:
                # fused paged decode/verify (serving paged_kernel path,
                # Transformer.decode_paged_fused): fresh K/V scatters
                # into the SHARED block pool at host-computed (block,
                # offset) targets, then the Pallas kernel reads
                # allocated, position-covered blocks in place through
                # the block table — no gathered dense row, no extra
                # copy of the cache stream (ops/paged_attention.py).
                # Masked/ungranted positions aim at the null block,
                # whose content is never admitted by the causal mask.
                B_, T_ = x.shape[0], x.shape[1]
                pk, pv = cache["k"], cache["v"]
                wblk, woff = cache["wblk"], cache["woff"]
                from ..ops.paged_attention import (
                    paged_decode_attention, paged_decode_attention_sharded)

                # tensor-parallel pool: a leading tp axis of per-shard
                # flat pools [tp, n_blocks, block, (KV/tp)*D]
                # (init_paged_cache tp>1).  ndim is unambiguous here —
                # only FLAT pools reach the fused branch, so 4-D means
                # sharded, never grouped.  The flat minor axis is
                # head-major, so reshape(B, T, tp, X/tp) splits fresh
                # rows into exactly each shard's KV-head slice; the
                # table/write targets are head-agnostic and shared.
                tp_ = pk.shape[0] if pk.ndim == 4 else 1
                dst = ((wblk, woff) if tp_ == 1
                       else (slice(None), wblk, woff))
                attend = (paged_decode_attention if tp_ == 1
                          else paged_decode_attention_sharded)

                def _shard_rows(rows):
                    if tp_ == 1:
                        return rows
                    w = rows.shape[-1]
                    return rows.reshape(
                        B_, T_, tp_, w // tp_).transpose(2, 0, 1, 3)

                if pk.dtype == jnp.int8:
                    # int8 pool (kv_dtype="int8"): quantize-at-scatter —
                    # fresh K/V lands in the pool as s8 + its per-
                    # (position, head) scale rows, and the kernel
                    # dequantizes in-register at DMA time.  Every read
                    # of these positions (this step included) sees the
                    # quantized values, so re-prefill after preempt or
                    # disagg fallback reproduces identical pool bytes.
                    kq, ks = _quantize_kv(k)
                    vq, vs = _quantize_kv(v)
                    pks, pvs = cache["k_scale"], cache["v_scale"]
                    pk = pk.at[dst].set(
                        _shard_rows(kq.reshape(B_, T_, KV * D)))
                    pv = pv.at[dst].set(
                        _shard_rows(vq.reshape(B_, T_, KV * D)))
                    pks = pks.at[dst].set(
                        _shard_rows(ks.astype(pks.dtype)))
                    pvs = pvs.at[dst].set(
                        _shard_rows(vs.astype(pvs.dtype)))
                    out = attend(
                        q, pk, pv, cache["table"], pos,
                        k_scale=pks, v_scale=pvs,
                        window=cfg.attn_window)
                    return o_proj(out), dict(cache, k=pk, v=pv,
                                             k_scale=pks, v_scale=pvs)
                row_k = k.reshape(B_, T_, KV * D).astype(pk.dtype)
                row_v = v.reshape(B_, T_, KV * D).astype(pv.dtype)
                pk = pk.at[dst].set(_shard_rows(row_k))
                pv = pv.at[dst].set(_shard_rows(row_v))
                out = attend(q, pk, pv, cache["table"], pos,
                             window=cfg.attn_window)
                return o_proj(out), dict(cache, k=pk, v=pv)
            import math as _math

            quant_cache = cache["k"].dtype == jnp.int8
            flat_cache = cache["k"].ndim == 3
            prefill_flash = (
                isinstance(pos, int) and pos == 0 and x.shape[1] > 1
                and cfg.attn_impl == "flash" and not cfg.has_sp
                and _math.gcd(x.shape[1], 1024) >= 128)
            if flat_cache:
                # [B, S, KV*D] decode-native layout (init_cache
                # layout="flat"): the cache IS the contiguous stream the
                # fused decode kernel reads, so no per-step relayout
                # ever happens — reshaping a [B, S, KV, D] cache costs
                # a PHYSICAL copy of the whole cache every step
                # (ops/decode_attention.py; measured 3.1x on MHA decode)
                B_, T_ = x.shape[0], x.shape[1]
                if quant_cache:
                    # flat int8: quantize at write time (the same
                    # per-(position, head) scales as the grouped s8
                    # cache), store the values flat so the fused kernel
                    # streams s8 bytes copy-free — half the cache HBM
                    # read on top of the kernel's layout win
                    kq, ks = _quantize_kv(k)
                    vq, vs = _quantize_kv(v)
                    row_k = kq.reshape(B_, T_, KV * D)
                    row_v = vq.reshape(B_, T_, KV * D)
                else:
                    row_k = k.reshape(B_, T_, KV * D).astype(
                        cache["k"].dtype)
                    row_v = v.reshape(B_, T_, KV * D).astype(
                        cache["v"].dtype)
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], row_k, (0, pos, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], row_v, (0, pos, 0))
                new_cache = {"k": ck, "v": cv}
                if quant_cache:
                    cks = jax.lax.dynamic_update_slice(
                        cache["k_scale"],
                        ks.astype(cache["k_scale"].dtype), (0, pos, 0))
                    cvs = jax.lax.dynamic_update_slice(
                        cache["v_scale"],
                        vs.astype(cache["v_scale"].dtype), (0, pos, 0))
                    new_cache = {"k": ck, "v": cv,
                                 "k_scale": cks, "v_scale": cvs}
                if prefill_flash:
                    from ..ops.flash_attention import flash_attention

                    # (quant cache: prefill attends the exact pre-
                    # quantization k/v in hand — only later reads see
                    # s8, the same contract as the grouped path)
                    out = flash_attention(q, k, v, causal=True,
                                          window=cfg.attn_window)
                elif T_ == 1:
                    from ..ops.decode_attention import decode_attention

                    if quant_cache and jax.default_backend() != "tpu":
                        # off-TPU the fused kernel only interprets, and
                        # this branch ALSO runs under per-slot vmap when
                        # the paged engine's gather fallback attends an
                        # int8 pool's gathered rows (the rows ARE a flat
                        # quant cache) — interpret-mode pallas_call does
                        # not batch.  The dense q8 path is the same
                        # dequantize-after-read numerics.
                        S_ = ck.shape[1]
                        out = _cached_attention_q8(
                            q, ck.reshape(B_, S_, KV, D), cks,
                            cv.reshape(B_, S_, KV, D), cvs, pos,
                            window=cfg.attn_window)
                    elif quant_cache:
                        out = decode_attention(
                            q, ck, cv, pos, k_scale=cks, v_scale=cvs,
                            window=cfg.attn_window)
                    else:
                        out = decode_attention(q, ck, cv, pos,
                                               window=cfg.attn_window)
                elif isinstance(pos, int) and pos == 0:
                    # dense prefill fallback (awkward prompt lengths):
                    # at static pos=0 the valid cache slots are exactly
                    # the fresh k/v in hand — attend those directly and
                    # never read the cache back
                    out = _cached_attention(q, k, v, 0,
                                            window=cfg.attn_window)
                else:
                    # tq>1 at pos>0 (speculative verify): dense path
                    # needs the grouped view; pays the one relayout
                    S_ = ck.shape[1]
                    if quant_cache:
                        out = _cached_attention_q8(
                            q, ck.reshape(B_, S_, KV, D), cks,
                            cv.reshape(B_, S_, KV, D), cvs, pos,
                            window=cfg.attn_window)
                    else:
                        out = _cached_attention(
                            q, ck.reshape(B_, S_, KV, D),
                            cv.reshape(B_, S_, KV, D), pos,
                            window=cfg.attn_window)
                return o_proj(out), new_cache
            if quant_cache:
                # int8 KV cache: K/V quantize at write time (per
                # position+head scales); reads stay s8 end to end
                # (_cached_attention_q8), halving the cache stream that
                # dominates decode HBM traffic after the weights
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], kq, (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], vq, (0, pos, 0, 0))
                cks = jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks.astype(cache["k_scale"].dtype),
                    (0, pos, 0))
                cvs = jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs.astype(cache["v_scale"].dtype),
                    (0, pos, 0))
                new_cache = {"k": ck, "v": cv,
                             "k_scale": cks, "v_scale": cvs}
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
                new_cache = {"k": ck, "v": cv}

            if prefill_flash:
                # prefill fast path: at a *static* pos=0 the valid keys are
                # exactly the q/k/v just computed, so the causal Pallas
                # kernel serves prefill directly — O(T) memory instead of
                # the dense [T, S] score matrix, and the same kernel the
                # model trains with (1.96x at T=2048).  The gcd gate keeps
                # awkward prompt lengths (tiny, or T>1024 coprime with the
                # kernel's block) on the dense path, where the Pallas
                # block fitter would crash or degrade to slivers.  (With a
                # quantized cache, prefill attention reads the exact
                # pre-quantization K/V — only later reads see s8.)
                from ..ops.flash_attention import flash_attention

                out = flash_attention(q, k, v, causal=True,
                                      window=cfg.attn_window)
            elif quant_cache and isinstance(pos, int) and pos == 0:
                # dense prefill on the exact pre-quantization k/v in
                # hand: without this, prompt lengths failing the flash
                # gcd gate attended the prompt against already-quantized
                # K/V, so first-token logits carried a quantization
                # error that varied with prompt length (r4 advisor)
                out = _cached_attention(q, k, v, 0,
                                        window=cfg.attn_window)
            elif quant_cache:
                out = _cached_attention_q8(q, ck, cks, cv, cvs, pos,
                                           window=cfg.attn_window)
            else:
                out = _cached_attention(q, ck, cv, pos,
                                        window=cfg.attn_window)
            return o_proj(out), new_cache
        if KV != H and not (cfg.attn_impl == "flash" and not cfg.has_sp):
            # GQA on the non-flash training paths (local / ring /
            # ulysses): broadcast K/V to full heads in-register — the
            # repeat is a fused broadcast under XLA, and these paths
            # have no cache whose bytes the grouping could save.  The
            # flash kernel instead consumes grouped K/V natively
            # (ops/flash_attention.py _gqa_group).
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        if key_mask is not None:
            if cfg.attn_impl == "flash" and not cfg.has_sp:
                # padding mask rides the flash kernel's segment ids (pads
                # only see pads; valid positions match the masked softmax
                # exactly — ops/flash_attention.py)
                from ..ops.flash_attention import flash_attention

                out = flash_attention(q, k, v, cfg.causal,
                                      segment_ids=key_mask,
                                      window=window)
            else:
                if window is not None:
                    raise ValueError(
                        "attn_window requires attn_impl='flash' without an "
                        f"active sp axis (got attn_impl={cfg.attn_impl!r})")
                # sp-parallel impls don't take a mask; cfg.attention_fn
                # raises first if an sp axis is active
                out = local_attention(q, k, v, causal=cfg.causal,
                                      key_mask=key_mask)
        else:
            out = cfg.attention_fn(self.layer)(q, k, v)
        return o_proj(out)


class LatentAttention(nn.Module):
    """Multi-head latent attention, the training path (no cache):

        c_q = norm(x W_qa);   [q_nope | q_rope] = c_q W_qb      per head
        [c_kv | k_rope] = x W_kva;   c_kv = norm(c_kv)
        [k_nope | v] = c_kv W_kvb                               per head
        q = [q_nope | rope(q_rope)],  k = [k_nope | rope(k_rope)]

    with the one ``k_rope`` shared by every head.  q and k heads are
    ``qk_nope_head_dim + qk_rope_head_dim`` wide, v heads ``v_head_dim``
    (the flash kernels carry both widths); scores scale by the q width.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, key_mask=None, cache=None, pos=None):
        cfg = self.cfg
        if cache is not None or key_mask is not None:
            raise NotImplementedError(
                "latent attention is built for training: no cache, no "
                "key_mask")
        H = cfg.num_heads
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        r = cfg.kv_lora_rank
        dense = partial(QuantDense, dtype=cfg.dtype,
                        kernel_init=nn.initializers.xavier_uniform())
        c_q = cfg.make_norm("q_norm")(
            dense(features=cfg.q_lora_rank, name="q_a")(x))
        q = dense(features=(H, dn + dr), name="q_b")(c_q)
        kv_a = dense(features=r + dr, name="kv_a")(x)
        c_kv = cfg.make_norm("kv_norm")(kv_a[..., :r])
        kv = dense(features=(H, dn + dv), name="kv_b")(c_kv)
        rope = partial(apply_rope, positions=jnp.arange(x.shape[1]),
                       theta=cfg.rope_theta, scaling=cfg.rope_scaling,
                       interleave=cfg.rope_interleave)
        k_rope = rope(kv_a[..., None, r:])               # [B, T, 1, dr]
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H, dr))], axis=-1)
        out = cfg.attention_fn()(q, k, kv[..., dn:])
        return QuantDense(
            features=cfg.d_model, in_axes=2, dtype=cfg.dtype, name="o",
            kernel_init=nn.initializers.xavier_uniform())(out)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from ..parallel.moe import UNGATED

        cfg = self.cfg
        col = partial(
            QuantDense, features=cfg.d_ff, dtype=cfg.dtype,
            use_bias=cfg.use_bias,
            kernel_init=cfg.partition(
                nn.initializers.xavier_uniform(), (None, cfg.tp_axis)
            ),
        )
        if cfg.mlp == "swiglu":
            # LLaMA-family gated MLP: down(silu(gate(x)) * up(x)).
            # gate/up are column-parallel, down row-parallel — the same
            # tp layout as the gelu variant, one extra matmul
            h = nn.silu(col(name="gate")(x)) * col(name="up")(x)
        elif cfg.mlp == "gelu":
            h = nn.gelu(col(name="up")(x))
        elif cfg.mlp in UNGATED:
            h = UNGATED[cfg.mlp](col(name="up")(x))
        else:
            raise ValueError(f"unknown mlp {cfg.mlp!r}")
        return QuantDense(
            features=cfg.d_model, dtype=cfg.dtype, name="down",
            use_bias=cfg.use_bias,
            kernel_init=cfg.partition(
                nn.initializers.xavier_uniform(), (cfg.tp_axis, None)
            ),
        )(h)


class _Leaves(nn.Module):
    """Named parameter leaves with no computation of their own:
    ``((name, shape, initializer), ...)`` -> ``{name: array}``."""

    leaves: tuple

    @nn.compact
    def __call__(self):
        return {name: self.param(name, init, shape)
                for name, shape, init in self.leaves}


class ExpertLayer(nn.Module):
    """The routed feed-forward of a block: the held experts' part
    (``parallel/moe.py:expert_layer``) plus the shared expert.
    ``router_x`` is what the router scores where that is not ``x`` (the
    block's normalised attention input under ``moe_router_pre_attn``).
    A non-gated expert (``moe_act`` of ``parallel/moe.py:UNGATED``) has
    no ``gate`` leaf, and the shared expert takes the same form.
    Sows the layer's counts (``assignments_held``, ``rows_computed``,
    and ``dma_rows{op=...}`` where a gather moves its rows by DMA:
    ``parallel/moe.py:expert_layer``) into the ``moe_stats`` collection
    (when the caller makes it mutable)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, router_x=None):
        from ..parallel.moe import UNGATED, expert_layer

        cfg = self.cfg
        gated = cfg.moe_act not in UNGATED
        d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.moe_experts
        held = cfg.moe_held or (0, E)
        init = nn.initializers.normal(stddev=0.02)
        leaves = (("kernel", (d, E), init),)
        if cfg.moe_scoring == "sigmoid":      # the balancing bias's rule
            leaves += (("bias", (E,), nn.initializers.zeros),)
        router = _Leaves(leaves, name="router")()
        w = _Leaves(
            ((("gate", (held[1], d, f), init),) if gated else ())
            + (("up", (held[1], d, f), init),
               ("down", (held[1], f, d), init)), name="experts")()
        flat = x.reshape(-1, d)
        y, counts = expert_layer(
            flat, router["kernel"], router.get("bias"), w.get("gate"),
            w["up"], w["down"], top_k=cfg.moe_top_k, scale=cfg.moe_scale,
            held=held, axis_name=cfg.moe_ep_axis,
            router_x=None if router_x is None else router_x.reshape(-1, d),
            scoring=cfg.moe_scoring, act=cfg.moe_act)
        for name, n in counts.items():
            self.sow("moe_stats", name, n, reduce_fn=lambda a, b: a + b,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        y = y.reshape(x.shape)
        if cfg.moe_shared:
            shared = dataclasses.replace(
                cfg, mlp="swiglu" if gated else cfg.moe_act,
                d_ff=f * cfg.moe_shared)
            y = y + MLP(shared, name="shared")(x)
        return y


class Block(nn.Module):
    cfg: TransformerConfig
    experts: bool = False     # the feed-forward is an expert layer
    layer: Optional[int] = None   # index into a per-layer layout

    @nn.compact
    def __call__(self, x, key_mask=None, cache=None, pos=None):
        cfg = self.cfg
        attention = (LatentAttention if cfg.attn_kind == "mla"
                     else partial(Attention, layer=self.layer))
        # with sandwich norms each sublayer's output is normalised too
        out_norm = ((lambda name, y: cfg.make_norm(name)(y))
                    if cfg.sandwich_norm else (lambda name, y: y))
        y = cfg.make_norm("ln1")(x)
        # the router may score the attention input: kept for the expert
        # layer below, which still reads the feed-forward input
        router_x = y if cfg.moe_router_pre_attn else None
        if cache is not None:
            if key_mask is not None:
                raise ValueError(
                    "KV-cache decode does not support key_mask (pad K/V "
                    "would enter the cache as real context)")
            attn_out, new_cache = attention(cfg, name="attn")(
                y, cache=cache, pos=pos)
        else:
            new_cache = None
            attn_out = attention(cfg, name="attn")(y, key_mask=key_mask)
        x = x + out_norm("ln1_out", attn_out)
        y = cfg.make_norm("ln2")(x)
        if self.experts:
            ffn_out = ExpertLayer(cfg, name="moe")(y, router_x)
        else:
            ffn_out = MLP(cfg, name="mlp")(y)
        x = x + out_norm("ln2_out", ffn_out)
        return (x, new_cache) if cache is not None else x


def causal_depthwise_conv(u, w):
    """``conv(u)[t, c] = sum_k w[k, c] u[t - (K - 1) + k, c]`` for ``u
    [B, T, C]`` and ``w [K, C]``, zeros before the start: position ``t``
    sees ``t`` and the ``K - 1`` before it, nothing after."""
    K, T = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, k:k + T] * w[k] for k in range(K))


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """Mamba-2's gated norm, the gate FIRST: ``y * silu(z)``, then an RMS
    norm over each of ``groups`` equal runs of channels, times
    ``scale``; statistics in float32."""
    dtype = y.dtype
    g = (y * nn.silu(z)).astype(jnp.float32)
    g = g.reshape(g.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    return (g.reshape(y.shape) * scale).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060), training path:

        [z | xBC | dt] = x W_in            widths d_in | d_in + 2 G N | H
        xBC = silu(conv(xBC) + b)          causal, depthwise, K taps
        [X | B | C] = xBC                  X [T, H, P]; B, C [T, G, N]
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        Y = ssd_scan(X, dt, A, B, C, D)    ops/ssd_scan.py
        out = norm(Y * silu(z)) W_out      RMS over each group's channels

    ``d_in = H P``; head ``h`` reads group ``h // (H / G)``.  ``dt``, the
    decay and the carried state are float32 whatever the compute dtype.
    Scopes under the module: ``in_proj``, ``conv``, ``ssd``, ``norm``,
    ``out_proj``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.ssd_scan import ssd_scan

        cfg = self.cfg
        H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        d_in, gn = H * P, G * N
        dense = partial(QuantDense, dtype=cfg.dtype,
                        kernel_init=nn.initializers.normal(stddev=0.02))
        z, xbc, dt = jnp.split(
            dense(features=2 * d_in + 2 * gn + H, name="in_proj")(x),
            (d_in, 2 * d_in + 2 * gn), axis=-1)
        conv = _Leaves((
            ("kernel", (cfg.ssm_conv, d_in + 2 * gn),
             nn.initializers.normal(stddev=0.02)),
            ("bias", (d_in + 2 * gn,), nn.initializers.zeros)),
            name="conv")()
        with jax.named_scope("conv"):
            xbc = nn.silu(causal_depthwise_conv(
                xbc, conv["kernel"].astype(cfg.dtype))
                + conv["bias"].astype(cfg.dtype))
        ssd = _Leaves((
            ("A_log", (H,), lambda key, shape: jnp.log(
                jnp.arange(1, shape[0] + 1, dtype=jnp.float32))),
            ("dt_bias", (H,), nn.initializers.zeros),
            ("D", (H,), nn.initializers.ones)), name="ssd")()
        lead = x.shape[:-1]
        with jax.named_scope("ssd"):
            y = ssd_scan(
                xbc[..., :d_in].reshape(lead + (H, P)),
                jax.nn.softplus(dt.astype(jnp.float32) + ssd["dt_bias"]),
                -jnp.exp(ssd["A_log"].astype(jnp.float32)),
                xbc[..., d_in:d_in + gn].reshape(lead + (G, N)),
                xbc[..., d_in + gn:].reshape(lead + (G, N)),
                ssd["D"], chunk=cfg.ssm_chunk)
        scale = _Leaves((("scale", (d_in,), nn.initializers.ones),),
                        name="norm")()["scale"]
        with jax.named_scope("norm"):
            y = gated_group_norm(y.reshape(lead + (d_in,)), z, scale, G,
                                 cfg.norm_eps)
        return dense(features=cfg.d_model, name="out_proj")(y)


def short_conv_taps_init(key, shape, dtype=jnp.float32):
    """Taps ``[K, C]`` from ``U(-1/sqrt(K), 1/sqrt(K))``: PyTorch's
    default for a depthwise ``Conv1d`` of K taps, which LFM2's published
    initialiser leaves alone (at N(0, 0.02) the operator would add ~0.03
    of its input)."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def gated_short_conv(b, c, u, taps):
    """``c * conv(b * u)``: the input gate, the causal taps, the output
    gate."""
    return c * causal_depthwise_conv(b * u, taps)


class ShortConv(nn.Module):
    """The gated short convolution of LFM2 (``lfm2`` modelling code),
    training path:

        [B | C | X] = x W_in               three widths of d_model
        y = C * conv(B * X)                causal, depthwise, K taps
        out = y W_out

    ``conv`` is ``causal_depthwise_conv``: no bias, no activation.
    Scopes under the module: ``in_proj``, ``conv`` (the two gates and
    the taps), ``out_proj``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d = cfg.d_model
        dense = partial(QuantDense, dtype=cfg.dtype,
                        kernel_init=nn.initializers.normal(stddev=0.02))
        b, c, u = jnp.split(dense(features=3 * d, name="in_proj")(x), 3,
                            axis=-1)
        taps = _Leaves((("kernel", (cfg.conv_taps, d),
                         short_conv_taps_init),), name="conv")()["kernel"]
        with jax.named_scope("conv"):
            y = gated_short_conv(b, c, u, taps.astype(cfg.dtype))
        return dense(features=d, name="out_proj")(y)


class SublayerBlock(nn.Module):
    """A block of ONE sublayer, ``x + sublayer(norm(x))``, its kind read
    from the config's kind layout: ``mamba`` (``Mamba2Mixer``), ``conv``
    (``ShortConv``), ``attn`` (``Attention``), ``moe`` (``ExpertLayer``)
    or ``mlp``.  Training path only: no cache knows a recurrent state
    yet."""

    cfg: TransformerConfig
    layer: int = 0

    @nn.compact
    def __call__(self, x, key_mask=None, cache=None, pos=None):
        if cache is not None:
            raise NotImplementedError(
                "a per-layer kind layout is built for training: no cache "
                "holds a recurrent state or knows a layer's kind yet")
        cfg = self.cfg
        kind = cfg.layer_kind(self.layer)
        y = cfg.make_norm("norm")(x)
        if kind == "mamba":
            return x + Mamba2Mixer(cfg, name="mamba")(y)
        if kind == "conv":
            return x + ShortConv(cfg, name="shortconv")(y)
        if kind == "attn":
            return x + Attention(cfg, name="attn")(y, key_mask=key_mask)
        if kind == "moe":
            return x + ExpertLayer(cfg, name="moe")(y)
        return x + MLP(cfg, name="mlp")(y)


class MTPModule(nn.Module):
    """One multi-token-prediction depth: the final hidden state and the
    NEXT token's embedding, each normalised, projected together to
    ``d_model``, through one more block and a norm; the caller applies
    the shared head."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, next_emb):
        cfg = self.cfg
        x = jnp.concatenate([cfg.make_norm("norm_h")(h),
                             cfg.make_norm("norm_e")(next_emb)], axis=-1)
        x = QuantDense(cfg.d_model, dtype=cfg.dtype, name="proj")(x)
        x = cfg.block_cls()(cfg, experts=cfg.moe_experts > 0,
                            name="block")(x)
        return cfg.make_norm("norm")(x)


def exit_log_probs(exit_logits):
    """The exit distribution of a looped model, in logs, over axis 0 (the
    ``R`` passes): with ``lam_t = sigmoid(exit_logits[t])`` pass ``t <
    R`` takes ``lam_t * prod_{j<t} (1 - lam_j)`` and the last pass the
    remaining mass ``prod_{j<R} (1 - lam_j)``.  Float32, in log space
    (``log_sigmoid``), so that a pass of vanishing mass keeps a finite
    log for the entropy."""
    logits = exit_logits.astype(jnp.float32)
    log_stay = jnp.cumsum(jax.nn.log_sigmoid(-logits), axis=0)
    # log prod_{j<t} (1 - lam_j): 0 for the first pass
    log_reach = jnp.concatenate(
        [jnp.zeros_like(log_stay[:1]), log_stay[:-1]], axis=0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(logits[:-1]) + log_reach[:-1],
         log_reach[-1:]], axis=0)


def loop_pass(model, x, _):
    """One pass of a looped model (``Transformer.hidden``'s scan body):
    the shared stack of blocks, then the readout's part that feeds the
    next pass — the final norm — and the exit gate on its output.
    Returns ``(next pass's input, (normed state, exit logit))``."""
    from ..common.tracing import SCOPE_HEAD, SCOPE_LOOP_READOUT

    for block in model.blocks:
        x = block(x)
    with jax.named_scope(SCOPE_HEAD), jax.named_scope(SCOPE_LOOP_READOUT):
        s = model.ln_f(x)
        gate = model.exit_gate(s)[..., 0]
    return s, (s, gate)


class Transformer(nn.Module):
    """Causal LM.  Input ``tokens [B, T]`` -> logits ``[B, T, vocab]``.

    setup()-style (not compact) so ``hidden`` can be called as a separate
    method: the fused LM-head cross-entropy path
    (ops/fused_cross_entropy.py, training.lm_loss_fn) consumes the
    pre-head hidden states and the ``lm_head`` kernel directly, never
    materializing the [B, T, vocab] logits.  Parameter tree is identical
    to the previous compact form (embed / pos / block_i / ln_f / lm_head).
    """

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed",
            embedding_init=cfg.partition(
                nn.initializers.normal(stddev=0.02), (None, None)
            ),
        )
        if cfg.pos_emb == "learned":
            self.pos = nn.Embed(
                cfg.max_seq_len, cfg.d_model, dtype=cfg.dtype, name="pos",
            )
        elif cfg.pos_emb not in ("rope", "none"):
            raise ValueError(f"unknown pos_emb {cfg.pos_emb!r}")
        block = cfg.block_cls()
        if cfg.layer_kinds is not None:
            if cfg.mtp_layers or cfg.has_attn_layout:
                raise ValueError(
                    "a kind layout is built without a multi-token-"
                    "prediction module and without a per-layer attention "
                    "layout")
            self.blocks = [block(cfg, layer=i, name=f"block_{i}")
                           for i in range(cfg.num_layers)]
        else:
            self.blocks = [
                block(cfg,
                      experts=cfg.moe_experts > 0 and i >= cfg.dense_layers,
                      layer=i, name=f"block_{i}")
                for i in range(cfg.num_layers)
            ]
        self.ln_f = cfg.make_norm("ln_f")
        if cfg.loop_steps > 1:
            if (cfg.layer_kinds is not None or cfg.mtp_layers
                    or cfg.moe_experts):
                raise ValueError(
                    "a looped model is built of Blocks without experts "
                    "and without a multi-token-prediction module")
            # float32: the exit distribution is a product of R gates
            self.exit_gate = QuantDense(1, dtype=jnp.float32, use_bias=True,
                                        name="exit_gate")
        elif cfg.loop_steps < 1:
            raise ValueError(f"loop_steps={cfg.loop_steps}: at least 1")
        if cfg.mtp_layers > 1:
            raise ValueError("one multi-token-prediction depth is built; "
                             f"mtp_layers={cfg.mtp_layers}")
        if cfg.mtp_layers:
            self.mtp = MTPModule(cfg, name="mtp")
        if not cfg.tie_embeddings:
            # bf16 operands + fp32 accumulate: sampling still sees fp32
            # logits (MXU accumulates fp32 regardless) but the vocab-wide
            # kernel — the single largest per-token HBM stream in decode —
            # moves at 2 bytes/param instead of 4
            self.lm_head = QuantDense(
                cfg.vocab_size, dtype=cfg.dtype,
                accum_dtype=jnp.float32, name="lm_head",
            )

    def hidden(self, tokens):
        """Everything up to (and including) the final norm:
        ``[B, T] -> [B, T, d_model]``.  A looped model (``loop_steps`` R
        > 1) returns ``(states [R, B, T, d_model], exit_logits [R, B,
        T])``: every pass's normed state and its exit gate's logit, the
        passes one ``nn.scan`` over the shared stack whose parameters
        are broadcast — the lowered program does not grow with R, and
        the shared weights' gradients sum over the passes."""
        from ..observability.metrics import get_registry

        cfg = self.cfg
        reg = get_registry()        # set when the model is traced
        reg.gauge("loop.steps").set(cfg.loop_steps)
        reg.gauge("model.layers_held").set(cfg.num_layers)
        attn_layers = range(cfg.num_layers)
        if cfg.layer_kinds is not None:
            kinds = [cfg.layer_kind(i) for i in attn_layers]
            for kind in sorted(set(kinds)):
                reg.gauge("model.layers", kind=kind).set(kinds.count(kind))
            attn_layers = [i for i in attn_layers if kinds[i] == "attn"]
        windowed = sum(cfg.layer_window(i) is not None for i in attn_layers)
        reg.gauge("attn.layers", kind="window").set(windowed)
        reg.gauge("attn.layers", kind="full").set(
            len(attn_layers) - windowed)
        x = self.embed(tokens)
        if cfg.pos_emb == "learned":
            x = x + self.pos(jnp.arange(tokens.shape[1])[None, :])
        if cfg.loop_steps > 1:
            passes = nn.scan(
                loop_pass, variable_broadcast="params",
                split_rngs={"params": False}, length=cfg.loop_steps)
            return passes(self, x, None)[1]
        for block in self.blocks:
            x = block(x)
        return self.ln_f(x)

    def logits(self, h):
        """LM head over hidden states — the tied variant multiplies by
        the input embedding table (GPT-2 convention).  Both variants
        ACCUMULATE in fp32 (sampling and speculative-accept decisions
        read these logits) while streaming the vocab-wide weight at the
        model dtype — the head weight is decode's largest per-token HBM
        read, and an fp32-operand head would double it."""
        cdt = self.cfg.dtype
        if self.cfg.tie_embeddings:
            emb = self.embed.embedding
            return jax.lax.dot_general(
                h.astype(cdt), emb.astype(cdt),
                (((h.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return self.lm_head(h).astype(jnp.float32)

    def hidden_mtp(self, tokens):
        """``(h, h_mtp)``: ``hidden`` and, from it and the next token's
        embedding, the multi-token-prediction module's final state
        (position t predicts token t + 2; the last position's "next
        token" wraps round and its targets are the caller's to ignore)."""
        h = self.hidden(tokens)
        return h, self.mtp(h, jnp.roll(self.embed(tokens), -1, axis=1))

    def __call__(self, tokens):
        """Logits; of every pass (``[R, B, T, vocab]``) for a looped
        model."""
        if self.cfg.mtp_layers and self.is_initializing():
            return self.logits(self.hidden_mtp(tokens)[0])
        if self.cfg.loop_steps > 1:
            return self.logits(self.hidden(tokens)[0])
        return self.logits(self.hidden(tokens))

    def _refuse_a_loop(self):
        if self.cfg.loop_steps > 1:
            raise NotImplementedError(
                f"a looped model (loop_steps={self.cfg.loop_steps}) is "
                "built for training: no cache holds each pass's K/V, and "
                "decoding by the exit gate is not built")

    def decode(self, tokens, caches, pos, last_only=False, last_idx=None):
        """One autoregressive step over ``tokens [B, tq]`` at absolute
        offset ``pos`` (traced scalar) against per-layer KV caches.

        Returns ``(logits [B, tq, vocab], new_caches)``.  The same method
        serves prefill (``tq`` = prompt length, ``pos=0``) and decode
        (``tq=1``) — static shapes throughout, so a generation loop
        compiles exactly two programs.  Build caches with ``init_cache``;
        drive the loop with ``byteps_tpu.inference.generate``.

        ``last_only=True`` applies the LM head to the final position only
        (logits ``[B, 1, vocab]``) — generation prefill needs just the
        next-token distribution, and the full ``[B, tq, vocab]`` fp32
        logits would otherwise dominate prefill HBM at real vocab sizes.
        ``last_idx`` (a traced scalar) is the same head narrowing at a
        *dynamic* position — a right-padded chunk's true last prompt
        token instead of the literal last row (see ``prefill_chunk``).
        """
        self._refuse_a_loop()
        x = self.embed(tokens)
        if self.cfg.pos_emb == "learned":
            idx = (pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
                   if jnp.ndim(pos) == 1     # per-slot cursors (fused
                   else (pos                 # paged decode)
                         + jnp.arange(tokens.shape[1]))[None, :])
            x = x + self.pos(idx)
        new_caches = []
        for block, c in zip(self.blocks, caches):
            x, nc = block(x, cache=c, pos=pos)
            new_caches.append(nc)
        if last_only:
            x = x[:, -1:]
        elif last_idx is not None:
            x = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        return self.logits(self.ln_f(x)), tuple(new_caches)

    def prefill_chunk(self, tokens, caches, pos, last_idx):
        """Position-offset prefill: one chunk ``tokens [B, C]`` written
        into the caches at absolute positions ``[pos, pos + C)`` (``pos``
        a traced scalar, unlike the static ``pos=0`` whole-prompt
        prefill), returning the logits at chunk-local index ``last_idx``
        only (``[B, 1, vocab]``).

        This is the serving engine's chunked-prefill step
        (serving/engine.py): a long prompt runs as a sequence of these
        calls interleaved with decode ticks instead of one monolithic
        prefill, and a prefix-cache hit resumes prefill at the copied
        boundary.  Chunking is bit-exact against whole-prompt prefill:
        hidden states (and therefore K/V) at each position depend only
        on positions at or before it, every per-position computation is
        row-independent, and attention always runs against the
        full-length cache buffer with the same causal mask — masked
        slots contribute exactly-zero probability (docs/serving.md).
        ``last_idx`` exists for the final chunk of a right-padded
        prompt: the LM head reads the true last prompt token, never the
        padding (mid-chunk callers discard the logits).

        Requires a **dense** cache: at a traced ``pos`` attention reads
        the stored K/V, which under a quantized cache is already int8,
        while the static ``pos=0`` whole-prompt path attends the exact
        pre-quantization values — chunking a quantized cache would
        silently change first-token logits (``ServingEngine`` refuses
        the combination).  The *paged* int8 pool (``kv_dtype="int8"``)
        is the exception: there is no whole-prompt path — every attend
        runs at a traced position against the stored s8+scale blocks —
        so chunking an int8 paged cache is self-consistent and the
        engine allows it (docs/serving.md "int8 paged KV").
        """
        return self.decode(tokens, caches, pos, last_idx=last_idx)

    def decode_paged(self, tokens, pcaches, table, pos, last_only=False,
                     last_idx=None, hw_blocks=None, tp=1):
        """`decode` against a **paged** KV cache: one slot's contiguous
        cache rows are gathered from the per-layer block pools
        (``pcaches``: ``[n_blocks, block, ...]`` per layer) via the
        slot's block table (``table [max_blocks]`` int32, unallocated
        entries pointing at the null block), then the ordinary dense
        cached decode runs on the gathered ``[1, max_seq, ...]`` row.

        The gather moves stored bytes; it computes nothing — so this
        path is bit-exact against the contiguous cache by construction
        (one attention implementation, serving/blocks.py).  Returns
        ``(logits, new_rows)`` where ``new_rows`` are the gathered rows
        with this step's K/V written at ``[pos, pos + tq)``; the caller
        (the serving engine's jitted decode step) slices the written
        span back out and scatters it into the block pool.

        ``hw_blocks`` (static int) caps the gather at the slot's block
        high-water mark: only ``table[:hw_blocks]`` is gathered and the
        attention row is ``hw_blocks * block`` wide instead of
        ``max_seq`` — the XLA fallback stops streaming null-block /
        unwritten padding every tick.  Bit-exact for any ``hw_blocks``
        covering ``pos + tq``: the dropped tail is exactly the masked
        region whose scores contribute zero probability mass.

        ``tp`` (static int) gathers from tensor-parallel per-shard
        pools, reassembling the unsharded flat row exactly — see
        :func:`gather_paged_rows`; the caller slices the written span
        and re-splits it per shard at scatter time.
        """
        self._refuse_a_loop()
        rows = gather_paged_rows(pcaches, table, hw_blocks=hw_blocks,
                                 tp=tp)
        if tp > 1:
            rows = _regroup_tp_rows(self.cfg, rows)
        return self.decode(tokens, rows, pos, last_only=last_only,
                           last_idx=last_idx)

    def prefill_chunk_paged(self, tokens, pcaches, table, pos, last_idx,
                            tp=1):
        """``prefill_chunk`` over a paged cache: gather the slot's rows
        through its block table, run the position-offset chunk, return
        the written rows for the caller's scatter-back (see
        :meth:`decode_paged`)."""
        self._refuse_a_loop()
        rows = gather_paged_rows(pcaches, table, tp=tp)
        if tp > 1:
            rows = _regroup_tp_rows(self.cfg, rows)
        return self.prefill_chunk(tokens, rows, pos, last_idx)

    def decode_paged_fused(self, tokens, pcaches, tables, pos, wblk,
                           woff, last_only=False):
        """``decode`` against a paged cache WITHOUT the gather: every
        layer's attention writes the fresh K/V straight into the block
        pool at the host-computed ``(wblk, woff) [N, tq]`` targets and
        reads allocated, position-covered blocks in place through the
        per-slot block table (``tables [N, max_blocks]``) — the fused
        Pallas kernel path (ops/paged_attention.py).  ``pos [N]`` is a
        per-slot cursor vector: unlike :meth:`decode_paged` this method
        is NOT vmapped per slot — one kernel call serves the whole pool
        (the kernel's grid is (N, max_blocks)).

        Returns ``(logits [N, tq, vocab], new_pcaches)`` — the pool
        comes back updated; there is nothing to scatter."""
        self._refuse_a_loop()
        views = tuple(dict(c, table=tables, wblk=wblk, woff=woff)
                      for c in pcaches)
        logits, new = self.decode(tokens, views, pos,
                                  last_only=last_only)
        # strip the per-call routing (table/write targets), keep every
        # pool leaf — int8 pools carry k_scale/v_scale alongside k/v
        drop = ("table", "wblk", "woff")
        return logits, tuple(
            {n: c[n] for n in c if n not in drop} for c in new)

    def verify_tokens_paged_fused(self, tokens, pcaches, tables, pos,
                                  wblk, woff):
        """:meth:`decode_paged_fused` at ``k + 1`` query positions —
        the speculative verify on the fused kernel path.  Plain decode
        and verify ride the SAME kernel, whose per-row online-softmax
        accumulation is identical at every query width, so spec-on
        stays token-identical to spec-off (the one-implementation
        argument of :meth:`verify_tokens`, one indirection deeper)."""
        return self.decode_paged_fused(tokens, pcaches, tables, pos,
                                       wblk, woff)

    def verify_tokens(self, tokens, caches, pos):
        """Speculative-decoding verify: the decode step generalized from
        1 to ``k + 1`` query positions.  ``tokens [B, k+1]`` is the last
        emitted token followed by ``k`` proposed continuations, written
        into the caches at absolute positions ``[pos, pos + k + 1)``
        (``pos`` a traced scalar), returning the logits at EVERY
        position (``[B, k+1, vocab]``) so the caller can accept the
        longest proposal prefix the model itself would have produced.

        This is a pure delegation to :meth:`decode` — one attention
        implementation — so accepted tokens are bit-exact against the
        sequential one-token decode by construction: per-position
        computations are row-independent, attention always runs against
        the full-length cache buffer under the same causal mask, and
        masked slots (including the not-yet-accepted speculative
        positions themselves) contribute exactly-zero probability mass
        (the ``prefill_chunk`` argument, applied to decode).  Rejected
        positions' K/V lands beyond the caller's accepted cursor and is
        overwritten before the mask can ever admit it (docs/serving.md
        "Speculative decoding")."""
        return self.decode(tokens, caches, pos)

    def verify_tokens_paged(self, tokens, pcaches, table, pos,
                            hw_blocks=None, tp=1):
        """:meth:`verify_tokens` over a paged cache: gather the slot's
        rows through its block table, verify the ``k + 1`` positions in
        one pass, return ``(logits [B, k+1, vocab], written rows)`` for
        the caller's per-position scatter-back (see
        :meth:`decode_paged`; ``hw_blocks`` caps the gather at the
        high-water block, which must cover ``pos + k + 1``)."""
        self._refuse_a_loop()
        rows = gather_paged_rows(pcaches, table, hw_blocks=hw_blocks,
                                 tp=tp)
        if tp > 1:
            rows = _regroup_tp_rows(self.cfg, rows)
        return self.decode(tokens, rows, pos)


def _regroup_tp_rows(cfg, rows):
    """Reshape tp-gathered FLAT k/v rows ``[B, S, KV*D]`` to the
    grouped ``[B, S, KV, D]`` layout (scale leaves stay ``[B, S,
    KV]``).  The flat minor axis is head-major, so this reshape is a
    pure view — the regrouped row is byte-identical to a grouped
    gather.  It routes the tensor-parallel gather fallback onto the
    grouped dense attention branch (the exact program an unsharded
    grouped-layout engine runs) instead of the flat-row branch, whose
    single-token step takes the fused dense decode kernel — not a
    fallback path off-TPU."""
    KV, D = cfg.kv_heads, cfg.d_head
    return tuple(
        {n: (r[n].reshape(r[n].shape[:2] + (KV, D))
             if n in ("k", "v") else r[n]) for n in r}
        for r in rows)


def gather_paged_rows(pcaches, table, hw_blocks=None, tp=1):
    """Assemble one slot's contiguous cache view from paged per-layer
    block pools: ``c [n_blocks, block, ...]`` indexed by the slot's
    block table ``[max_blocks]`` -> ``[1, max_blocks * block, ...]``.

    Positions past the slot's write cursor gather arbitrary bytes (the
    null block, or a stale block's content) — exactly the dense pool's
    stale-rows situation, and safe for the same reason: the causal mask
    admits only positions below the cursor, and masked scores
    contribute exactly-zero probability mass (serving/slots.py).  The
    serving engine enforces ``max_blocks * block == max_seq`` so the
    gathered row is shape-identical to a dense cache row.

    ``hw_blocks`` (static int) gathers only ``table[:hw_blocks]`` — the
    per-tick block high-water mark.  Every gathered byte past the
    highest written position is pure waste (null-block padding or
    masked stale content), so the serving engine caps the gather at a
    bucketed high-water instead of streaming the full table width each
    tick; the shorter row stays value-identical over the admitted
    (masked-in) region.

    ``tp > 1`` gathers from **tensor-parallel** per-shard flat pools
    ``[tp, n_blocks, block, X]`` (init_paged_cache tp>1) and
    reassembles the unsharded FLAT row ``[1, S, tp*X]`` byte-for-byte:
    the flat minor axis is head-major and shard ``s`` holds exactly
    KV-head slice ``s``, so concatenating the shards' minor axes at
    each position IS the unsharded row (docs/parallel.md).  The dense
    attention the gathered row feeds is therefore the IDENTICAL
    program the unsharded gather path runs — tp gather parity needs no
    new attention code (the flat-row dense path already serves chunk
    prefill on fused engines)."""
    if hw_blocks is not None:
        table = table[..., :hw_blocks]
    out = []
    for layer in pcaches:
        row = {}
        for name, c in layer.items():
            if tp > 1:
                g = c[:, table]  # [tp, hw_blocks, block, X]
                row[name] = g.transpose(1, 2, 0, 3).reshape(
                    1, g.shape[1] * g.shape[2], tp * g.shape[3])
            else:
                g = c[table]  # [hw_blocks, block, ...]
                row[name] = g.reshape(
                    (1, g.shape[0] * g.shape[1]) + g.shape[2:])
        out.append(row)
    return tuple(out)


def init_cache(cfg: TransformerConfig, batch_size: int, max_len: int,
               quantized: bool = False, layout: str = "auto"):
    """Zeroed per-layer KV caches for ``Transformer.decode``.
    ``max_len`` must cover prompt + new tokens and stay within
    ``cfg.max_seq_len`` (position embeddings).  Under GQA
    (``cfg.num_kv_heads < num_heads``) the cache carries only the
    shared K/V heads — a num_heads/num_kv_heads shrink of decode's
    second-largest HBM stream.

    ``layout`` picks the decode data path (the cache is
    self-describing; ``Attention`` dispatches on its ndim):

    * ``"flat"`` — ``[B, max_len, kv_heads*D]``: the decode-native
      layout consumed by the fused Pallas decode kernel
      (ops/decode_attention.py) with zero per-step relayout.  Measured
      3.1x (MHA) / 1.4x (GQA kv=2) over the dense path at T=1024.
      With ``quantized=True`` the flat cache stores s8 values plus the
      per-(position, head) scales and the kernel dequantizes in VMEM —
      the s8 stream composes with the kernel's layout win.
    * ``"grouped"`` — ``[B, max_len, kv_heads, D]``: the dense
      mixed-dot path (the layout tensor-parallel decode shards over
      its head axis).
    * ``"auto"`` — flat on TPU for causal caches with a usable chunk
      size: always for bf16, and for int8 under MHA only (where the
      flat-s8 kernel won — a GQA-shrunken s8 cache's byte saving no
      longer pays for the kernel's in-VMEM dequant, so GQA int8 keeps
      the grouped dense path; ops/decode_attention.py).  Grouped
      otherwise (CPU tests keep the dense path — interpret-mode Pallas
      per decode step would crawl).

    **Tensor-parallel decode**: when ``cfg.mesh`` carries an active tp
    axis that divides ``kv_heads``, the cache is sharded over its
    KV-head axis — the grouped layout's explicit head dim
    (``P(dp?, None, tp, ...)``, ``_grouped_cache_sharding``) or the
    flat layout's head-major minor axis in whole-head slices
    (``P(dp?, None, tp)``, ``_flat_cache_sharding``) — so each tp
    shard holds, writes, and streams only its own KV heads: serving a
    model too big for one chip splits the cache (and its decode HBM
    stream) the same way it splits the weights; the o-projection's
    row-parallel annotation gives GSPMD the psum that merges the
    per-shard attention outputs.  When tp does NOT divide
    ``kv_heads`` (MQA under tp) the grouped cache stays replicated,
    matching the replicated k/v kernels ``Attention`` falls back to,
    and ``layout="flat"`` raises (there is no exact whole-head
    partition of its minor axis to express — pad ``kv_heads`` or use
    the grouped layout).  See docs/inference.md "Serving topology"
    for when dp- vs tp-sharding wins, and docs/parallel.md for the
    paged per-shard pools.

    ``quantized=True`` builds an int8 cache (s8 K/V plus f32
    per-(position, head) scales, grouped or flat): half the HBM bytes
    per decode step, quantization happens at write time inside
    ``Attention``.  Unwritten slots are masked out of attention, so the
    zero scales never feed the softmax."""
    if max_len > cfg.max_seq_len:
        raise ValueError(
            f"cache max_len {max_len} exceeds max_seq_len {cfg.max_seq_len}")
    KV, D = cfg.kv_heads, cfg.d_head
    if layout not in ("auto", "flat", "grouped"):
        raise ValueError(f"unknown cache layout {layout!r}")
    if layout == "flat" and cfg.mesh is not None:
        names = cfg.mesh.axis_names
        tp = cfg.tp_axis
        if (tp in names and cfg.mesh.shape[tp] > 1
                and KV % cfg.mesh.shape[tp]):
            # the flat [B, S, KV*D] minor axis is head-major, so it
            # shards over tp in whole-KV-head slices ONLY: when tp
            # divides kv_heads the flat cache tp-shards exactly like
            # the grouped one (each contiguous KV*D/tp chunk IS one
            # shard's head slice — _flat_cache_sharding below), but
            # when it doesn't there is no exact head partition to
            # express, so honoring the request would silently
            # replicate what the caller asked to shard — refuse with
            # the two honest ways out instead
            raise ValueError(
                f'layout="flat" under an active tensor-parallel axis '
                f'{tp!r} (size {cfg.mesh.shape[tp]}) requires the axis '
                f'to divide kv_heads={KV}: the flat [B, S, KV*D] minor '
                f'axis shards in whole KV-head slices only; use '
                f'layout="grouped" (replicated K/V cache, matching the '
                f'replicated k/v kernels Attention falls back to) or '
                f'pad kv_heads to a multiple of the tp size')
    if layout == "auto":
        from ..ops.decode_attention import decode_attention_usable

        # mesh guard: under a >1-device mesh the decode step's
        # pallas_call would meet sharded operands GSPMD cannot
        # partition (and tp decode shards the grouped head axis);
        # sharded decode keeps the dense grouped path
        unsharded = cfg.mesh is None or all(
            s == 1 for s in cfg.mesh.shape.values())
        use_flat = (cfg.causal and unsharded
                    and jax.default_backend() == "tpu"
                    and decode_attention_usable(
                        (batch_size, 1, cfg.num_heads, D), max_len,
                        quantized, kv_heads=KV))
        layout = "flat" if use_flat else "grouped"
    if layout == "flat":
        shape = (batch_size, max_len, KV * D)
        fshard = _flat_cache_sharding(cfg, batch_size)
        if quantized:
            # flat int8: s8 values in the kernel's contiguous stream
            # layout plus the per-(position, head) f32 scales — the
            # fused decode kernel dequantizes in VMEM
            # (ops/decode_attention.py k_scale/v_scale)
            flayer = lambda: {  # noqa: E731
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:2] + (KV,), jnp.float32),
                "v_scale": jnp.zeros(shape[:2] + (KV,), jnp.float32)}
        else:
            flayer = lambda: {  # noqa: E731
                "k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}
        return tuple(fshard(flayer()) for _ in range(cfg.num_layers))
    shape = (batch_size, max_len, KV, D)
    if quantized:
        layer = lambda: {  # noqa: E731
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:3], jnp.float32),
            "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    else:
        layer = lambda: {"k": jnp.zeros(shape, cfg.dtype),  # noqa: E731
                         "v": jnp.zeros(shape, cfg.dtype)}
    shard = _grouped_cache_sharding(cfg, batch_size)
    return tuple(shard(layer()) for _ in range(cfg.num_layers))


def _grouped_cache_sharding(cfg: TransformerConfig, batch_size: int):
    """Constraint mapping a grouped cache layer onto ``cfg.mesh`` for
    tensor-parallel decode (identity when no active tp axis divides the
    kv heads).  The head axis shards over tp so each shard streams only
    its own KV heads per step; the batch axis rides dp when it divides
    evenly.  Applied with ``with_sharding_constraint`` so one code path
    serves both eager cache construction and the jitted generate loop."""
    mesh = cfg.mesh
    if mesh is None:
        return lambda layer: layer
    names = mesh.axis_names
    tp = (cfg.tp_axis if cfg.tp_axis in names
          and mesh.shape[cfg.tp_axis] > 1
          and cfg.kv_heads % mesh.shape[cfg.tp_axis] == 0 else None)
    dp = (cfg.dp_axis if cfg.dp_axis in names
          and mesh.shape[cfg.dp_axis] > 1
          and batch_size % mesh.shape[cfg.dp_axis] == 0 else None)
    if tp is None and dp is None:
        return lambda layer: layer
    from jax.sharding import NamedSharding

    spec = {"k": P(dp, None, tp, None), "v": P(dp, None, tp, None),
            "k_scale": P(dp, None, tp), "v_scale": P(dp, None, tp)}

    def shard(layer):
        return {name: jax.lax.with_sharding_constraint(
                    val, NamedSharding(mesh, spec[name]))
                for name, val in layer.items()}

    return shard


def _flat_cache_sharding(cfg: TransformerConfig, batch_size: int):
    """Constraint mapping a FLAT cache layer onto ``cfg.mesh`` —
    identity when no active tp axis divides the kv heads.  The flat
    ``[B, S, KV*D]`` minor axis is head-major, so sharding it into tp
    contiguous chunks IS sharding the KV-head axis: chunk ``s`` holds
    exactly heads ``[s*KV/tp, (s+1)*KV/tp)`` (what ``init_cache``
    refused before the per-shard paged pools made the flat-under-tp
    story real; docs/parallel.md).  Scale rows ``[B, S, KV]`` shard
    the same head slices."""
    mesh = cfg.mesh
    if mesh is None:
        return lambda layer: layer
    names = mesh.axis_names
    tp = (cfg.tp_axis if cfg.tp_axis in names
          and mesh.shape[cfg.tp_axis] > 1
          and cfg.kv_heads % mesh.shape[cfg.tp_axis] == 0 else None)
    dp = (cfg.dp_axis if cfg.dp_axis in names
          and mesh.shape[cfg.dp_axis] > 1
          and batch_size % mesh.shape[cfg.dp_axis] == 0 else None)
    if tp is None and dp is None:
        return lambda layer: layer
    from jax.sharding import NamedSharding

    spec = {"k": P(dp, None, tp), "v": P(dp, None, tp),
            "k_scale": P(dp, None, tp), "v_scale": P(dp, None, tp)}

    def shard(layer):
        return {name: jax.lax.with_sharding_constraint(
                    val, NamedSharding(mesh, spec[name]))
                for name, val in layer.items()}

    return shard
