"""Shared flax building blocks for the whole model zoo.

The reference re-implements these per model (e.g. `BasicConv2d` at
Inception/pytorch/models/inception_v1.py, `DarknetConv` at
YOLO/tensorflow/yolov3.py:23-41, custom `SeparableConv2D` at
MobileNet/tensorflow/models/mobilenet_v1.py:7-26). Here they are written once,
NHWC, TPU-native:

- depthwise/group conv lowers to `lax.conv_general_dilated` with
  `feature_group_count` (the XLA-native form of torch's `groups=`);
- BatchNorm under pjit computes batch statistics over the *global* batch
  (XLA inserts the cross-replica psum), i.e. synced BN by construction —
  resolving the DataParallel+BN pitfall the reference documents at
  ResNet/pytorch/train.py:348-349;
- LocalResponseNorm (AlexNet V1, alexnet_v1.py:33-89) is a vectorized
  channel-window sum, fused by XLA.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

INITIALIZERS = {
    "he_normal": nn.initializers.he_normal(),
    "he_uniform": nn.initializers.he_uniform(),
    "xavier_normal": nn.initializers.xavier_normal(),
    "xavier_uniform": nn.initializers.xavier_uniform(),
    "lecun_normal": nn.initializers.lecun_normal(),
    "normal02": nn.initializers.normal(0.02),  # DCGAN init
}


def global_avg_pool(x):
    """NHWC -> NC global average pool (replaces AdaptiveAvgPool2d(1))."""
    return jnp.mean(x, axis=(1, 2))


def channel_shuffle(x, groups: int):
    """ShuffleNet channel shuffle: (B,H,W,g*c) -> transpose group/channel.

    The reference never implemented this (shufflenet_v1.py is a 0-byte file,
    SURVEY.md §2.9); written from the ShuffleNet paper (sec 3.1).
    """
    b, h, w, c = x.shape
    assert c % groups == 0, f"channels {c} not divisible by groups {groups}"
    x = x.reshape(b, h, w, groups, c // groups)
    x = jnp.swapaxes(x, 3, 4)
    return x.reshape(b, h, w, c)


class LocalResponseNorm(nn.Module):
    """AlexNet V1's LRN (alexnet_v1.py:42,52): across-channel normalization."""

    size: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    k: float = 2.0

    @nn.compact
    def __call__(self, x):
        half = self.size // 2
        sq = jnp.square(x)
        # sum over a channel window via padded cumulative trick
        padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
        window = sum(
            jax.lax.dynamic_slice_in_dim(padded, i, x.shape[-1], axis=x.ndim - 1)
            for i in range(self.size)
        )
        return x / jnp.power(self.k + self.alpha * window, self.beta)


def _scale_bias_act(x, scale, bias, residual, act: Optional[str]):
    y = x.astype(jnp.float32) * scale.astype(jnp.float32) + bias.astype(
        jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def _scale_bias_act_bwd(act, res, g):
    """-> (dx, dscale, dbias, dresidual) from the saved (x, scale, bias, y)."""
    x, scale, bias, y = res
    # masked in the io dtype g arrives in, widened after: the one array XLA
    # then stores for every consumer (scale_bias_act's docstring)
    gb = jnp.where(y > 0, g, 0) if act == "relu" else g
    gf = gb.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    dx = (gf * scale.astype(jnp.float32)).astype(x.dtype)
    dscale = jnp.sum(gf * x.astype(jnp.float32), axis=axes)
    dbias = jnp.sum(gf, axis=axes)
    return dx, dscale.astype(scale.dtype), dbias.astype(bias.dtype), gb


# one custom_vjp per arity, so `residual=None` never ships a zeros tensor
# through HBM just to satisfy a uniform signature

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tail3(x, scale, bias, act):
    return _scale_bias_act(x, scale, bias, None, act)


def _tail3_fwd(x, scale, bias, act):
    y = _scale_bias_act(x, scale, bias, None, act)
    return y, (x, scale, bias, y)


_tail3.defvjp(_tail3_fwd,
              lambda act, res, g: _scale_bias_act_bwd(act, res, g)[:3])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _tail4(x, scale, bias, residual, act):
    return _scale_bias_act(x, scale, bias, residual, act)


def _tail4_fwd(x, scale, bias, residual, act):
    y = _scale_bias_act(x, scale, bias, residual, act)
    return y, (x, scale, bias, y)


_tail4.defvjp(_tail4_fwd, _scale_bias_act_bwd)


def scale_bias_act(x, scale, bias, residual=None,
                   act: Optional[str] = "relu"):
    """y = act(x * scale + bias [+ residual]): BatchNorm's tail.

    x: (..., C) in its io dtype; scale/bias: (C,), the folded BN apply
    (scale = gamma * rsqrt(var + eps), bias = beta - mean * scale);
    residual: x's shape or None; act: 'relu' or None. The arithmetic is
    float32 whatever the io dtype, so bf16 activations lose nothing to the
    folding, and it is plain jax.numpy, so XLA fuses it into its
    neighbours in the convolutions' own layouts.

    The backward is written out (custom_vjp) and not left to autodiff: it
    masks on the saved io-dtype `y` and reduces dscale/dbias in one pass
    over (g, x, y), where autodiff of the float32 expression keeps float32
    tensors of the activation's size alive across the step (PERF.md §6,
    PR 30). At y == 0 the ReLU's slope is 0. The mask is applied to the
    cotangent in its io dtype, before anything widens it: masked in float32,
    XLA finds (unmasked bf16 g, `pred` mask) the cheaper pair to store, at
    three bytes an element, and repeats the select in each of g's three
    consumers (PERF.md §6, PR 35); masked in bf16, it stores that array.
    """
    if act not in ("relu", None):
        raise ValueError(f"unsupported act {act!r}")
    c = x.shape[-1]
    if scale.shape != (c,) or bias.shape != (c,):
        raise ValueError(
            f"scale/bias must be ({c},), got {scale.shape}/{bias.shape}")
    if residual is None:
        return _tail3(x, scale, bias, act)
    if residual.shape != x.shape:
        raise ValueError(
            f"residual shape {residual.shape} != x shape {x.shape}")
    return _tail4(x, scale, bias, residual, act)


class BatchNorm(nn.Module):
    """BatchNorm that never materializes the activation tensor in float32.

    flax's `nn.BatchNorm` promotes the full activation to f32 to compute
    statistics and to normalize; on a bandwidth-bound TPU that doubles the
    HBM traffic of every BN layer (measured: 12% of a ResNet bottleneck
    block's train-step time on v5e). Here the big tensor stays in its input
    dtype end to end: statistics accumulate in f32 inside the reduction
    (one fused E[x], E[x^2] pass), and normalization is folded to a single
    per-channel multiply-add `x * a + b` computed in the activation dtype.

    Semantics match `nn.BatchNorm(use_fast_variance=True)`: biased batch
    variance, EMA running stats under the same `batch_stats` names
    (`mean`, `var`), and global-batch statistics under pjit (the batch-axis
    `jnp.mean` spans the sharded global batch, so XLA inserts the
    cross-replica psum: synced BN by construction, resolving the
    DataParallel+BN pitfall at ResNet/pytorch/train.py:348-349).
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros
    dtype: Optional[jnp.dtype] = None  # output/compute dtype; None = x.dtype
    # act='relu' (and/or a `residual` call arg) folds the activation and the
    # skip-add into the normalize: `scale_bias_act` above, one expression
    # with a written-out backward, the same on every platform.
    act: Optional[str] = None

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None,
                 residual=None):
        use_ra = (
            self.use_running_average
            if use_running_average is None
            else use_running_average
        )
        c = x.shape[-1]
        reduce_axes = tuple(range(x.ndim - 1))
        scale = self.param("scale", self.scale_init, (c,), jnp.float32)
        bias = self.param("bias", self.bias_init, (c,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), jnp.float32)
        )
        if use_ra:
            mean, var = ra_mean.value, ra_var.value
        else:
            # one pass over x: f32 accumulation without an f32 materialization
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=reduce_axes)
            mean2 = jnp.mean(jnp.square(xf), axis=reduce_axes)
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
        inv = scale * jax.lax.rsqrt(var + self.epsilon)
        dt = self.dtype or x.dtype
        if self.act is not None or residual is not None:
            # the folded apply (x*a + b) is safe here: the tail computes in
            # f32, so the bf16-cancellation concern below does not apply
            y = scale_bias_act(x, inv, bias - mean * inv, residual=residual,
                               act=self.act)
            return y.astype(dt)
        # normalize in f32 *inside the fusion*: per-element upcast costs no
        # HBM traffic (XLA fuses the converts), and subtracting the mean
        # before scaling avoids the bf16 cancellation of a folded x*a + b
        # when |mean| >> std
        y = (x.astype(jnp.float32) - mean) * inv + bias
        return y.astype(dt)


# explicit-intent alias: `BatchNorm` keeps flax's auto-naming producing the
# same `BatchNorm_N` variable-tree paths as `nn.BatchNorm` did, so swapping
# the implementation never invalidates a checkpoint
FusedBatchNorm = BatchNorm


class ConvBN(nn.Module):
    """Conv + BatchNorm + activation, the universal CNN building block."""

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: str | Sequence[Tuple[int, int]] = "SAME"
    groups: int = 1
    use_bn: bool = True
    use_bias: bool = False
    act: Optional[Callable] = nn.relu
    kernel_init: Callable = nn.initializers.he_normal()
    bn_momentum: float = 0.9
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True, residual=None):
        x = nn.Conv(
            self.features,
            self.kernel,
            strides=self.strides,
            padding=self.padding,
            feature_group_count=self.groups,
            use_bias=self.use_bias or not self.use_bn,
            kernel_init=self.kernel_init,
            dtype=self.dtype,
        )(x)
        if self.use_bn:
            # ReLU (and a skip tensor, when the caller passes one) fold into
            # the BN apply (`scale_bias_act`)
            fuse_relu = self.act is nn.relu
            x = FusedBatchNorm(
                use_running_average=not train,
                momentum=self.bn_momentum,
                act="relu" if fuse_relu else None,
            )(x, residual=residual)
            if self.act is not None and not fuse_relu:
                x = self.act(x)
            return x
        if residual is not None:
            x = x + residual
        if self.act is not None:
            x = self.act(x)
        return x


class DepthwiseSeparableConv(nn.Module):
    """MobileNet's depthwise 3x3 + pointwise 1x1 (mobilenet_v1.py:109-122).

    Depthwise = grouped conv with feature_group_count == in_channels; XLA
    lowers this to a TPU-native depthwise convolution.
    """

    features: int  # pointwise output channels
    strides: Tuple[int, int] = (1, 1)
    act: Callable = nn.relu
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        in_ch = x.shape[-1]
        x = ConvBN(
            features=in_ch,
            kernel=(3, 3),
            strides=self.strides,
            groups=in_ch,
            act=self.act,
            dtype=self.dtype,
        )(x, train)
        x = ConvBN(
            features=self.features, kernel=(1, 1), act=self.act, dtype=self.dtype
        )(x, train)
        return x


# -- decoder blocks' parts (models/olmo_hybrid.py) ---------------------------

class RMSNorm(nn.Module):
    """`x * rsqrt(mean(x^2) + eps) * scale` over the last axis: statistic
    and product in float32, result in x's dtype; `scale` starts at one."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = x.astype(jnp.float32)
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


class SwiGLU(nn.Module):
    """`down(silu(gate x) * up x)`, no biases (Shazeer, arXiv:2002.05202)."""

    hidden: int
    dtype: Optional[jnp.dtype] = None
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  kernel_init=self.kernel_init)
        y = nn.silu(dense(self.hidden, name="gate")(x)) \
            * dense(self.hidden, name="up")(x)
        return dense(x.shape[-1], name="down")(y)
