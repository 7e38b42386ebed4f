"""ResNet-34/50/152 (He 2015) and ResNet-50 V2 (pre-activation, He 2016).

Parity targets: ResNet/pytorch/models/resnet50.py (BottleneckBlock +
projection shortcut, Kaiming init at resnet50.py:84-93), resnet34.py (basic
blocks), resnet152.py (3/8/36/3), and the pre-activation
ResNet/tensorflow/models/resnet50v2.py:11-12. NHWC, he_normal init, BN with
global-batch statistics under pjit (synced BN by construction).

The flagship model of the framework: `resnet50` is the benchmark target
(BASELINE.json: top-1 >= 75.3% on v5e-8 at >= 0.9x A100x8 images/sec).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deep_vision_tpu.models import register_model
from deep_vision_tpu.nn.layers import ConvBN, FusedBatchNorm, global_avg_pool


class BasicBlock(nn.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = ConvBN(self.features, (3, 3), strides=self.strides, dtype=self.dtype)(x, train)
        # tail: BN + skip-add + ReLU fold into one expression (ConvBN's
        # residual arg -> nn/layers.scale_bias_act). Constructed before
        # the projection so flax auto-names (ConvBN_1 here, ConvBN_2
        # for the projection) — and with them every checkpoint — keep the
        # exact pre-fusion variable-tree paths.
        tail = ConvBN(self.features, (3, 3), act=nn.relu, dtype=self.dtype)
        if x.shape[-1] != self.features or self.strides != (1, 1):
            residual = ConvBN(
                self.features, (1, 1), strides=self.strides, act=None, dtype=self.dtype
            )(x, train)
        return tail(y, train, residual=residual)


class BottleneckBlock(nn.Module):
    features: int  # bottleneck width; output is 4x
    strides: Tuple[int, int] = (1, 1)
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        residual = x
        y = ConvBN(self.features, (1, 1), dtype=self.dtype)(x, train)
        y = ConvBN(self.features, (3, 3), strides=self.strides, dtype=self.dtype)(y, train)
        # zero-init the last BN scale so each block starts as identity
        # (standard TPU ResNet recipe; improves large-batch training)
        y = nn.Conv(self.features * 4, (1, 1), use_bias=False, dtype=self.dtype)(y)
        # the block tail — BN apply + skip-add + ReLU — is one expression
        # (nn/layers.scale_bias_act, through BatchNorm's act/residual
        # args). Constructed before the projection ConvBN so flax
        # auto-names (BatchNorm_0, ConvBN_2) keep the pre-fusion
        # variable-tree paths and checkpoints stay interchangeable.
        bn = FusedBatchNorm(
            use_running_average=not train,
            momentum=0.9,
            scale_init=nn.initializers.zeros_init(),
            act="relu",
        )
        if x.shape[-1] != self.features * 4 or self.strides != (1, 1):
            residual = ConvBN(
                self.features * 4, (1, 1), strides=self.strides, act=None, dtype=self.dtype
            )(x, train)
        return bn(y, residual=residual)


class PreActBottleneckBlock(nn.Module):
    """ResNet V2: BN-ReLU-Conv ordering (resnet50v2.py cites He 2016)."""

    features: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        pre = FusedBatchNorm(use_running_average=not train, momentum=0.9, dtype=self.dtype)(x)
        pre = nn.relu(pre)
        needs_proj = x.shape[-1] != self.features * 4 or self.strides != (1, 1)
        residual = (
            nn.Conv(self.features * 4, (1, 1), strides=self.strides, use_bias=False,
                    dtype=self.dtype)(pre)
            if needs_proj
            else x
        )
        y = nn.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype)(pre)
        y = ConvBN(self.features, (3, 3), strides=self.strides, dtype=self.dtype)(y, train)
        y = FusedBatchNorm(use_running_average=not train, momentum=0.9, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.features * 4, (1, 1), use_bias=False, dtype=self.dtype)(y)
        return y + residual


class SpaceToDepthStem(nn.Module):
    """The 7x7/s2 stem conv on space-to-depth input: MXU-efficient, math-equal.

    A 7x7 stride-2 conv on a 3-channel image is the least efficient conv on a
    TPU: the 3-channel input wastes the 128-wide lane tiling and the profiler
    shows it HBM-bound well below peak bandwidth. The MLPerf-ResNet trick:
    the host pipeline lays the image out as (H/2, W/2, 12) (space_to_depth,
    see data/transforms.py SpaceToDepth), and the stem becomes a 4x4 stride-1
    conv over 12 channels — *mathematically identical* to the 7x7/s2 conv
    because the 7x7 kernel zero-pads to 8x8 and reshuffles into (4, 4, 12).
    The parameter keeps the canonical (7, 7, 3, 64) shape: the kernel values
    are interchangeable with a conv7 stem's, though the variable-tree paths
    differ (SpaceToDepthStem_0/kernel vs ConvBN_0/Conv_0/kernel), so moving a
    checkpoint between stems requires remapping those two paths.
    """

    features: int = 64
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        c_in = x.shape[-1] // 4  # input is (H/2, W/2, 4*C)
        w = self.param(
            "kernel", nn.initializers.he_normal(), (7, 7, c_in, self.features),
            jnp.float32,
        )
        # pad 7x7 -> 8x8 at the top-left: kernel tap k maps to original
        # offset k-1, with k=0 the zero row (see derivation: original row
        # index = 2(i - 2) + k  vs  2i - 4 + k for the 7x7/s2 at pad 3)
        k8 = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
        w2 = (
            k8.reshape(4, 2, 4, 2, c_in, self.features)
            .transpose(0, 2, 1, 3, 4, 5)
            .reshape(4, 4, 4 * c_in, self.features)
        )
        dt = self.dtype or x.dtype
        return jax.lax.conv_general_dilated(
            x.astype(dt),
            w2.astype(dt),
            window_strides=(1, 1),
            padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block: type = BottleneckBlock
    num_classes: int = 1000
    width: int = 64
    preact: bool = False
    stem: str = "conv7"  # "conv7" (B,H,W,3) | "s2d" (B,H/2,W/2,12) input
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.stem == "s2d":
            x = SpaceToDepthStem(64, dtype=self.dtype)(x)
            if not self.preact:
                x = nn.relu(
                    FusedBatchNorm(use_running_average=not train, momentum=0.9)(x)
                )
        elif self.preact:
            x = nn.Conv(64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                        use_bias=False, dtype=self.dtype)(x)
        else:
            x = ConvBN(64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                       dtype=self.dtype)(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, n_blocks in enumerate(self.stage_sizes):
            features = self.width * (2**i)
            for j in range(n_blocks):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block(features, strides=strides, dtype=self.dtype)(x, train)
        if self.preact:
            x = nn.relu(FusedBatchNorm(use_running_average=not train, momentum=0.9,
                                     dtype=self.dtype)(x))
        x = global_avg_pool(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


@register_model("resnet34")
def resnet34(num_classes: int = 1000, dtype=None, stem: str = "conv7", **_):
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BasicBlock,
                  num_classes=num_classes, stem=stem, dtype=dtype)


@register_model("resnet50")
def resnet50(num_classes: int = 1000, dtype=None, stem: str = "conv7", **_):
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BottleneckBlock,
                  num_classes=num_classes, stem=stem, dtype=dtype)


@register_model("resnet152")
def resnet152(num_classes: int = 1000, dtype=None, stem: str = "conv7", **_):
    return ResNet(stage_sizes=(3, 8, 36, 3), block=BottleneckBlock,
                  num_classes=num_classes, stem=stem, dtype=dtype)


@register_model("resnet50v2")
def resnet50v2(num_classes: int = 1000, dtype=None, stem: str = "conv7", **_):
    return ResNet(stage_sizes=(3, 4, 6, 3), block=PreActBottleneckBlock,
                  num_classes=num_classes, preact=True, stem=stem, dtype=dtype)
