"""Plain reference: ResNet (bottleneck, v1.5 strides) forward and loss.

Straightforward `jax.numpy` + `lax.conv_general_dilated` in float32; the
caller sets `jax.default_matmul_precision("highest")`. Follows He et al.,
arXiv:1512.03385 Table 1. Departures, both math-equal to the paper's net:
the image arrives space-to-depth packed (H/2, W/2, 4C) as the program's
input feed ships it and is unpacked here before a plain 7x7/2 stem; the
stride of a down-sampling block sits on its 3x3 conv (the "v1.5" form the
program and every public ResNet-50 recipe use).

Imports nothing of the program. The variable tree carries the names the
program's flax modules give their leaves, so one tree feeds both.
`q` rounds each matmul operand (identity for the reference itself; a
lower-precision rounding for the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BATCH_COUPLED = True  # batch norm: rows of a batch cannot be split
_DN = ("NHWC", "HWIO", "NHWC")
_EPS = 1e-5
_MOMENTUM = 0.9


def _he(key, shape, gain=2.0):
    fan_in = 1
    for s in shape[:-1]:
        fan_in *= s
    return jax.random.normal(key, shape, jnp.float32) * (gain / fan_in) ** 0.5


def _bn_vars(c):
    return ({"scale": jnp.ones((c,), jnp.float32),
             "bias": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def _block_plan(cfg):
    """[(name, bottleneck width, stride, has projection)] in forward order."""
    plan, c_in, i = [], cfg["stem_width"], 0
    for stage, n_blocks in enumerate(cfg["stage_sizes"]):
        f = cfg["width"] * 2 ** stage
        for j in range(n_blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            plan.append((f"BottleneckBlock_{i}", c_in, f, stride,
                         c_in != 4 * f or stride != 1))
            c_in, i = 4 * f, i + 1
    return plan, c_in


def init(cfg, key):
    """Seeded variables {"params", "batch_stats"}: He-normal kernels, every
    BN scale 1 but each block's last (`tail_bn_scale`), zero biases."""
    c_img = cfg["input_shape"][2]
    plan, c_out = _block_plan(cfg)
    keys = iter(jax.random.split(key, 4 * len(plan) + 2))
    params, stats = {}, {}
    params["SpaceToDepthStem_0"] = {
        "kernel": _he(next(keys), (7, 7, c_img, cfg["stem_width"]))}
    params["BatchNorm_0"], stats["BatchNorm_0"] = _bn_vars(cfg["stem_width"])
    for name, c_in, f, _stride, proj in plan:
        p, s = {}, {}
        shapes = {"ConvBN_0": (1, 1, c_in, f), "ConvBN_1": (3, 3, f, f)}
        if proj:
            shapes["ConvBN_2"] = (1, 1, c_in, 4 * f)
        for cb, shape in shapes.items():
            bn_p, bn_s = _bn_vars(shape[-1])
            p[cb] = {"Conv_0": {"kernel": _he(next(keys), shape)},
                     "BatchNorm_0": bn_p}
            s[cb] = {"BatchNorm_0": bn_s}
        p["Conv_0"] = {"kernel": _he(next(keys), (1, 1, f, 4 * f))}
        p["BatchNorm_0"], s["BatchNorm_0"] = _bn_vars(4 * f)
        p["BatchNorm_0"]["scale"] *= cfg["tail_bn_scale"]
        params[name], stats[name] = p, s
    params["Dense_0"] = {
        "kernel": _he(next(keys), (c_out, cfg["num_classes"]), gain=1.0),
        "bias": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return {"params": params, "batch_stats": stats}


def _conv(q, x, w, stride=1, padding="SAME"):
    return lax.conv_general_dilated(q(x), q(w), (stride, stride), padding,
                                    dimension_numbers=_DN)


def _bn(x, p, s):
    """Train-mode batch norm; returns (y, new running stats)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + _EPS) * p["scale"] + p["bias"]
    new = {"mean": _MOMENTUM * s["mean"] + (1 - _MOMENTUM) * mean,
           "var": _MOMENTUM * s["var"] + (1 - _MOMENTUM) * var}
    return y, new


def _conv_bn(q, x, p, s, stride=1):
    y, new = _bn(_conv(q, x, p["Conv_0"]["kernel"], stride),
                 p["BatchNorm_0"], s["BatchNorm_0"])
    return y, {"BatchNorm_0": new}


def _bottleneck(q, x, p, s, stride, proj):
    new = {}
    y, new["ConvBN_0"] = _conv_bn(q, x, p["ConvBN_0"], s["ConvBN_0"])
    y = jnp.maximum(y, 0.0)
    y, new["ConvBN_1"] = _conv_bn(q, y, p["ConvBN_1"], s["ConvBN_1"], stride)
    y = jnp.maximum(y, 0.0)
    y, new["BatchNorm_0"] = _bn(_conv(q, y, p["Conv_0"]["kernel"]),
                                p["BatchNorm_0"], s["BatchNorm_0"])
    if proj:
        x, new["ConvBN_2"] = _conv_bn(q, x, p["ConvBN_2"], s["ConvBN_2"],
                                      stride)
    return jnp.maximum(y + x, 0.0), new


def forward(cfg, variables, images, q=lambda x: x):
    """images: (B, H/2, W/2, 4C) space-to-depth packed -> (logits, stats)."""
    params, stats = variables["params"], variables["batch_stats"]
    b, h2, w2, c4 = images.shape
    c = c4 // 4
    # unpack: packed channel = (row parity, column parity, colour)
    x = images.astype(jnp.float32).reshape(b, h2, w2, 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, 2 * h2, 2 * w2, c)
    new = {}
    x = _conv(q, x, params["SpaceToDepthStem_0"]["kernel"], 2,
              ((3, 3), (3, 3)))
    x, new["BatchNorm_0"] = _bn(x, params["BatchNorm_0"],
                                stats["BatchNorm_0"])
    x = jnp.maximum(x, 0.0)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    plan, _ = _block_plan(cfg)
    for name, _c_in, _f, stride, proj in plan:
        x, new[name] = _bottleneck(q, x, params[name], stats[name], stride,
                                   proj)
    x = jnp.mean(x, axis=(1, 2))
    d = params["Dense_0"]
    return q(x) @ q(d["kernel"]) + d["bias"], new


def loss_fn(cfg, params, batch_stats, batch, q=lambda x: x):
    """Mean softmax cross entropy over the batch -> (loss, new batch_stats)."""
    logits, new = forward(cfg, {"params": params, "batch_stats": batch_stats},
                          batch["image"], q)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=1)[:, 0]
    return jnp.mean(nll), new
