"""Rotary position embeddings: the default form (RoFormer, arXiv:2104.09864)
and YaRN's (arXiv:2309.00071), over all of a head's dims or its leading
part, as transformers' `_compute_default_rope_parameters`,
`_compute_yarn_parameters` and `apply_rotary_pos_emb` compute them.

A form is its inverse frequencies `inv_freq` (one a pair of rotated dims)
and the factor its cos and sin are multiplied by (`attention_factor`:
YaRN's, 1 for the default form). Position `t` rotates dim `i` and dim `i +
r/2` of the `r` rotated dims by the angle `t inv_freq_i` (NeoX's
`rotate_half` pairing); the dims past `r` pass through unrotated.

- default: `inv_freq_i = theta^(-2i/r)`, `i < r/2`.
- YaRN: the default frequencies, each divided by `factor` where its
  wavelength is long against `original_max_position_embeddings` and left
  where it is short, with a linear ramp between: `low = floor(r
  ln(L / (beta_fast 2 pi)) / (2 ln theta))`, `high = ceil(r ln(L / (beta_slow
  2 pi)) / (2 ln theta))`, both clamped to `[0, r - 1]`, `ramp_i = clip((i -
  low) / (high - low), 0, 1)`, `inv_freq_i <- inv_freq_i / factor * ramp_i
  + inv_freq_i (1 - ramp_i)`; cos and sin times `attention_factor`, so a
  score's rotated part carries its square.

The frequencies are host constants (float64, rounded once to float32);
the angles, cos and sin are float32. `apply_rotary` rounds cos and sin to
its input's dtype and rotates in that dtype, as transformers does (its
rotary module returns the tables in the activations' dtype).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class Rotary(NamedTuple):
    """A rotary form: `inv_freq` (r/2,) float64 and the factor on cos and
    sin."""

    inv_freq: np.ndarray
    scale: float = 1.0

    @property
    def dims(self) -> int:
        """How many leading dims of a head it rotates."""
        return 2 * len(self.inv_freq)


def default_inv_freq(dims: int, theta: float) -> np.ndarray:
    return theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)


def yarn_range(dims: int, theta: float, original: int, beta_fast: float,
               beta_slow: float):
    """(low, high): the dims below `low` keep their frequency, those from
    `high` on are divided by the factor."""
    dim = lambda rotations: (dims * math.log(
        original / (rotations * 2 * math.pi)) / (2 * math.log(theta)))
    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), dims - 1)
    return low, high


def yarn_inv_freq(dims: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    inv_freq = default_inv_freq(dims, theta)
    low, high = yarn_range(dims, theta, original, beta_fast, beta_slow)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0.0, 1.0)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def rotary(params: dict, head_dim: int) -> Rotary:
    """The form of one entry of a config's `rope_parameters` (`rope_type`
    "default" or "yarn", `rope_theta`, `partial_rotary_factor`, and
    YaRN's `factor`, `original_max_position_embeddings`, `beta_fast`,
    `beta_slow`, `attention_factor`)."""
    dims = int(head_dim * params.get("partial_rotary_factor", 1.0))
    theta = params["rope_theta"]
    kind = params.get("rope_type", "default")
    if kind == "default":
        return Rotary(default_inv_freq(dims, theta))
    if kind == "yarn":
        factor = params["factor"]
        scale = params.get("attention_factor") or 0.1 * math.log(factor) + 1
        return Rotary(yarn_inv_freq(
            dims, theta, factor, params["original_max_position_embeddings"],
            params.get("beta_fast", 32), params.get("beta_slow", 1)),
            float(scale))
    raise ValueError(f"rope_type {kind!r}: have 'default' and 'yarn'")


def tables(form: Rotary, positions):
    """(cos, sin), each (T, r) float32 for positions (T,): the angles of
    the r/2 pairs twice over, times the form's factor."""
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        form.inv_freq, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * form.scale, jnp.sin(angles) * form.scale


def apply_rotary(x, cos, sin):
    """x (B, T, H, d), cos and sin (T, r), r <= d: the first r dims of each
    head rotated, `x cos + rotate_half(x) sin` in x's dtype (cos and sin
    rounded to it), the rest as they are."""
    r = cos.shape[-1]
    cos, sin = (c.astype(x.dtype)[:, None, :] for c in (cos, sin))
    rot = x[..., :r]
    half = r // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    rot = rot * cos + turned * sin
    if r == x.shape[-1]:
        return rot
    return jnp.concatenate([rot, x[..., r:]], axis=-1)
