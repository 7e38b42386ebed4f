"""`ops/rotary.py` against the closed forms: the default form's and YaRN's
inverse frequencies (Laguna-XS.2's `rope_parameters`: YaRN over the first
64 of 128 dims, theta 5e5, factor 64 from 4096 positions, beta 64 and 1;
the default form over all 128, theta 1e4), the dims past the rotated part
passed through, `attention_factor` on cos and sin, and a score that
depends on the distance of its two positions alone."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.models.laguna import FULL, SLIDING
from deep_vision_tpu.models.laguna import ROPE_PARAMETERS as ROPE
from deep_vision_tpu.ops.rotary import (apply_rotary, rotary, tables,
                                        yarn_range)


def test_the_default_form_is_theta_to_the_minus_2i_over_d():
    form = rotary(ROPE[SLIDING], 128)
    assert form.dims == 128 and form.scale == 1.0
    for i in (0, 1, 31, 63):
        assert form.inv_freq[i] == pytest.approx(1e4 ** (-2 * i / 128),
                                                 rel=1e-12)


def test_yarn_divides_the_long_wavelengths_by_the_factor_past_its_ramp():
    yarn = ROPE[FULL]
    low = math.floor(64 * math.log(4096 / (64 * 2 * math.pi))
                     / (2 * math.log(5e5)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(5e5)))
    assert (low, high) == (5, 16) == yarn_range(64, 5e5, 4096, 64, 1)
    form = rotary(yarn, 128)
    assert form.dims == 64  # partial_rotary_factor 0.5
    assert form.scale == pytest.approx(1.4158883083359672)
    base = lambda i: 5e5 ** (-2 * i / 64)
    # below the ramp kept, past it divided by 64, inside it mixed linearly
    assert form.inv_freq[0] == pytest.approx(base(0), rel=1e-12)
    assert form.inv_freq[low] == pytest.approx(base(low), rel=1e-12)
    assert form.inv_freq[high] == pytest.approx(base(high) / 64, rel=1e-12)
    assert form.inv_freq[31] == pytest.approx(base(31) / 64, rel=1e-12)
    r = (10 - low) / (high - low)
    assert form.inv_freq[10] == pytest.approx(
        base(10) / 64 * r + base(10) * (1 - r), rel=1e-12)
    # the default attention factor where the entry gives none
    assert rotary({**yarn, "attention_factor": None}, 128).scale \
        == pytest.approx(0.1 * math.log(64) + 1)


def test_the_pass_through_half_is_unchanged_and_cos_sin_carry_the_factor():
    form = rotary(ROPE[FULL], 128)
    cos, sin = tables(form, jnp.arange(8))
    assert cos.shape == sin.shape == (8, 64)
    np.testing.assert_allclose(np.asarray(cos[0]), form.scale, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sin[0]), 0.0, atol=1e-7)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3, 128))
    y = apply_rotary(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(y[..., 64:]),
                                  np.asarray(x[..., 64:]))
    # NeoX's pairing: dim i turns with dim i + 32, by the angle t inv_freq_i
    t, i = 5, 3
    angle = np.float32(t) * np.float32(form.inv_freq[i])
    a, b = x[0, t, 0, i], x[0, t, 0, i + 32]
    want = form.scale * np.array([a * np.cos(angle) - b * np.sin(angle),
                                  b * np.cos(angle) + a * np.sin(angle)])
    np.testing.assert_allclose(np.asarray([y[0, t, 0, i], y[0, t, 0, i + 32]]),
                               want, rtol=1e-5)
    # in bfloat16 the rotation is bfloat16's (tables rounded to it): within
    # a few of its rounding steps of the float32 rotation of the same input
    xb = x.astype(jnp.bfloat16)
    yb = apply_rotary(xb, cos, sin)
    assert yb.dtype == jnp.bfloat16
    exact = apply_rotary(xb.astype(jnp.float32), cos, sin)
    np.testing.assert_allclose(np.asarray(yb, np.float32), np.asarray(exact),
                               atol=3 * 2.0 ** -8 * float(jnp.max(
                                   jnp.abs(exact))))
    assert not np.array_equal(np.asarray(yb, np.float32),
                              np.asarray(exact.astype(jnp.bfloat16),
                                         np.float32))


def test_a_rotated_score_depends_on_the_distance_alone():
    form = rotary(ROPE[SLIDING], 128)
    cos, sin = tables(form, jnp.arange(64))
    q = jax.random.normal(jax.random.PRNGKey(1), (128,))
    k = jax.random.normal(jax.random.PRNGKey(2), (128,))
    same = lambda v: jnp.broadcast_to(v, (1, 64, 1, 128))
    with jax.default_matmul_precision("highest"):
        rq = apply_rotary(same(q), cos, sin)[0, :, 0]
        rk = apply_rotary(same(k), cos, sin)[0, :, 0]
        score = lambda s, t: float(rq[s] @ rk[t])
        assert score(10, 3) == pytest.approx(score(40, 33), rel=1e-4)
        assert score(0, 0) == pytest.approx(float(q @ k), rel=1e-5)
