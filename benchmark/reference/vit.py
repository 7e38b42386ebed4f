"""Plain reference: Vision Transformer forward and loss.

Straightforward `jax.numpy` in float32; the caller sets
`jax.default_matmul_precision("highest")`. Follows Dosovitskiy et al.,
arXiv:2010.11929 (pre-norm blocks, GELU MLP of 4x width, learned position
embedding). Departure, as the program's model: the mean over tokens is
classified in place of a class token. GELU is the tanh form, LayerNorm's
epsilon 1e-6, as flax's defaults that the program runs.

Imports nothing of the program; the variable tree carries the program's
leaf names. `q` rounds each matmul operand (identity for the reference).

A configuration whose file says `"reference_remat": true` has every
block recomputed in the backward pass (`jax.checkpoint`): the step then
keeps each block's input and one block's scores, not every block's, which
at 4096 tokens are 1.6 GB a block an image. Recomputation changes no
mathematics, and the FLOP count is taken without it (`flops.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

BATCH_COUPLED = False  # every row stands alone: the batch may be split
_EPS = 1e-6


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _ln_vars(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def init(cfg, key):
    """Seeded variables {"params", "batch_stats": {}}: LeCun-normal kernels,
    position embedding N(0, 0.02), zero biases, unit LayerNorm scales."""
    d, h, p = cfg["dim"], cfg["num_heads"], cfg["patch"]
    hh, ww, c = cfg["input_shape"]
    t = (hh // p) * (ww // p)
    hid = d * cfg["mlp_ratio"]
    keys = iter(jax.random.split(key, 4 * cfg["depth"] + 3))
    params = {
        "patch_embed": {"kernel": _normal(next(keys), (p, p, c, d), p * p * c),
                        "bias": jnp.zeros((d,), jnp.float32)},
        "pos_embed": 0.02 * jax.random.normal(next(keys), (1, t, d),
                                              jnp.float32),
        "LayerNorm_0": _ln_vars(d),
        "Dense_0": {"kernel": _normal(next(keys), (d, cfg["num_classes"]), d),
                    "bias": jnp.zeros((cfg["num_classes"],), jnp.float32)},
    }
    for i in range(cfg["depth"]):
        params[f"ViTBlock_{i}"] = {
            "LayerNorm_0": _ln_vars(d), "LayerNorm_1": _ln_vars(d),
            "Attention_0": {
                "qkv": {"kernel": _normal(next(keys), (d, 3, h, d // h), d),
                        "bias": jnp.zeros((3, h, d // h), jnp.float32)},
                "out": {"kernel": _normal(next(keys), (h, d // h, d), d),
                        "bias": jnp.zeros((d,), jnp.float32)}},
            "Mlp_0": {
                "Dense_0": {"kernel": _normal(next(keys), (d, hid), d),
                            "bias": jnp.zeros((hid,), jnp.float32)},
                "Dense_1": {"kernel": _normal(next(keys), (hid, d), hid),
                            "bias": jnp.zeros((d,), jnp.float32)}},
        }
    return {"params": params, "batch_stats": {}}


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + _EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(q, x, p):
    a = p["Attention_0"]
    y = _ln(x, p["LayerNorm_0"])
    qkv = jnp.einsum("btd,dchk->btchk", q(y), q(a["qkv"]["kernel"])) \
        + a["qkv"]["bias"]
    qq, kk, vv = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bthk,bshk->bhts", q(qq), q(kk)) * qq.shape[-1] ** -0.5
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshk->bthk", q(w), q(vv))
    x = x + jnp.einsum("bthk,hkd->btd", q(o), q(a["out"]["kernel"])) \
        + a["out"]["bias"]
    m = p["Mlp_0"]
    y = _ln(x, p["LayerNorm_1"])
    y = _gelu(q(y) @ q(m["Dense_0"]["kernel"]) + m["Dense_0"]["bias"])
    return x + q(y) @ q(m["Dense_1"]["kernel"]) + m["Dense_1"]["bias"]


def _block_of(cfg, q):
    """`_block` as the configuration's file has it run: as it stands, or
    recomputed in the backward pass."""
    block = functools.partial(_block, q)
    return jax.checkpoint(block) if cfg.get("reference_remat") else block


def forward(cfg, variables, images, q=lambda x: x):
    """images: (B, H, W, C) -> (logits, {})."""
    params = variables["params"]
    p = cfg["patch"]
    pe = params["patch_embed"]
    x = lax.conv_general_dilated(
        q(images.astype(jnp.float32)), q(pe["kernel"]), (p, p), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + pe["bias"]
    x = x.reshape(x.shape[0], -1, x.shape[-1]) + params["pos_embed"]
    block = _block_of(cfg, q)
    for i in range(cfg["depth"]):
        x = block(x, params[f"ViTBlock_{i}"])
    x = jnp.mean(_ln(x, params["LayerNorm_0"]), axis=1)
    d = params["Dense_0"]
    return q(x) @ q(d["kernel"]) + d["bias"], {}


def loss_fn(cfg, params, batch_stats, batch, q=lambda x: x):
    """Mean softmax cross entropy over the batch -> (loss, {})."""
    logits, new = forward(cfg, {"params": params, "batch_stats": batch_stats},
                          batch["image"], q)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=1)[:, 0]
    return jnp.mean(nll), new
