"""ResNet v1.5 family — the reference's headline benchmark model
(README.md:22-26: ResNet50 fp32 BS 64/GPU; example/pytorch/benchmark_byteps.py
uses torchvision models).  Re-implemented TPU-first:

  * NHWC layout (TPU conv native layout; XLA tiles the channel dim onto the
    MXU's 128 lanes),
  * configurable compute dtype (bf16 by default for benchmarks, fp32 params),
  * BatchNorm with mutable running stats collection; cross-replica stat sync
    is the caller's choice via ``axis_name`` (maps to the reference's
    data-parallel BN semantics: torchvision BN is per-replica, so default
    ``axis_name=None`` matches the reference benchmark exactly),
  * static shapes throughout, no data-dependent control flow.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck (ResNet50/101/152)."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # zero-init the last BN scale: standard v1.5 trick, keeps the
        # residual branch an identity at init (better large-batch training)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="conv_proj"
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 block (ResNet18/34)."""

    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides, name="conv_proj")(
                residual
            )
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    """ResNet v1.5, NHWC.

    Input: ``[N, H, W, 3]``.  ``dtype`` is the compute dtype (bf16 keeps the
    MXU fed at full rate); params stay fp32.
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32
    act: Callable = nn.relu
    axis_name: Any = None  # set to sync BN stats across a mesh axis
    # dtype of BN scale/bias and running stats (None = fp32, the safe
    # default).  bf16 halves the BN state stream and drops the
    # fp32<->bf16 converts around every BN (what that buys on the chip
    # is not measured on the current toolchain).
    # CAVEAT: flax stores stats in fp32 unless force_float32_reductions
    # is off, so bf16 here also computes the batch mean/var reductions
    # in bf16 — over ~800k elements at stage 1 that costs real variance
    # precision; an accuracy experiment, not a free lunch.
    norm_param_dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(
            nn.Conv, use_bias=False, dtype=self.dtype, padding="SAME"
        )
        norm_kw = {}
        if self.norm_param_dtype is not None:
            norm_kw = dict(param_dtype=self.norm_param_dtype,
                           force_float32_reductions=False)
        norm = functools.partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
            axis_name=self.axis_name,
            **norm_kw,
        )
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = self.act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    act=self.act,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)
