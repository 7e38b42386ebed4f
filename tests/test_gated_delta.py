"""The chunked gated delta rule (`ops/gated_delta.py`) against the
recurrence it stands for, token by token, and the short convolution
against its definition. float32 on the CPU: both sides are the same
mathematics in another order of sums, so they part by float32 rounding
alone (1e-7 to 2e-6 of a norm at these sizes); the tolerance, 2e-5, is
ten times that and a thousand times under what a wrong decay, a missing
`T` or an off-by-one mask reads (order 1e-2 to 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import lax

from deep_vision_tpu.ops.gated_delta import gated_delta_rule, short_conv

def gated_delta_recurrent(q, k, v, g, beta):
    """The recurrence itself, token by token, float32: `S_t = a_t S_{t-1}
    (I - b_t k_t k_t^T) + b_t v_t k_t^T`, `o_t = S_t q_t`. Same arguments
    and result as `gated_delta_rule`."""
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)

    def step(s, x):
        q, k, v, g, beta = x  # (B, H, ...)
        s = jnp.exp(g)[..., None, None] * s
        written = beta[..., None] * (v - jnp.einsum("bhvk,bhk->bhv", s, k))
        s = s + written[..., :, None] * k[..., None, :]
        return s, jnp.einsum("bhvk,bhk->bhv", s, q)

    b, _, h, dk = q.shape
    s0 = jnp.zeros((b, h, v.shape[-1], dk), jnp.float32)
    _, o = lax.scan(step, s0, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


B, T, H, DK, DV = 2, 32, 3, 8, 16
TOL = 2e-5


def inputs(decay: float, beta_scale: float, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, DK)))
    v = jax.random.normal(ks[2], (B, T, H, DV))
    # beta up to 2: above 1 the state's eigenvalue along k is negative
    beta = 2 * jax.nn.sigmoid(beta_scale * jax.random.normal(ks[3], (B, T, H)))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[4], (B, T, H)))
    cotangent = jax.random.normal(ks[5], (B, T, H, DV))
    return (q, k, v, g, beta), cotangent


def apart(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("chunk", [4, 8, 16])
@pytest.mark.parametrize("decay,beta_scale", [
    (0.01, 1.0),  # weak decay: the state remembers the whole sequence
    (1.0, 3.0),   # beta pressed towards 0 and 2
    (5.0, 1.0),   # strong decay: e^y underflows within a chunk
], ids=["weak_decay", "beta_to_2", "strong_decay"])
def test_chunked_is_the_recurrence_outputs_and_all_gradients(chunk, decay,
                                                             beta_scale):
    args, ct = inputs(decay, beta_scale)
    assert float(jnp.max(args[4])) > 1.5
    with jax.default_matmul_precision("highest"):
        chunked = lambda *a: gated_delta_rule(*a, chunk=chunk)
        assert apart(chunked(*args), gated_delta_recurrent(*args)) < TOL
        got = jax.grad(lambda *a: jnp.sum(chunked(*a) * ct),
                       argnums=range(5))(*args)
        want = jax.grad(lambda *a: jnp.sum(gated_delta_recurrent(*a) * ct),
                        argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert apart(a, b) < TOL, name


def test_bfloat16_operands_stay_near_the_float32_rule():
    """The chip's path: operands rounded to bfloat16 into float32 sums.
    Eight bits of mantissa leave each product 4e-3 off; over these sums
    the output lies 2e-3 to 6e-3 of its norm away, never 1e-1."""
    args, _ = inputs(1.0, 1.0)
    exact = gated_delta_rule(*args, chunk=8)
    rounded = gated_delta_rule(*args, chunk=8, mm_dtype=jnp.bfloat16)
    assert rounded.dtype == jnp.float32
    assert 1e-4 < apart(rounded, exact) < 2e-2


def test_one_token_a_chunk_and_one_chunk_a_sequence_agree():
    args, _ = inputs(1.0, 1.0, seed=3)
    with jax.default_matmul_precision("highest"):
        assert apart(gated_delta_rule(*args, chunk=1),
                     gated_delta_rule(*args, chunk=T)) < TOL
    with pytest.raises(AssertionError, match="do not divide"):
        gated_delta_rule(*args, chunk=5)


def test_short_conv_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    y = short_conv(x, kernel)
    want = np.zeros((2, 10, 6), np.float32)
    for t in range(10):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(kernel[i]) * np.asarray(
                    x[:, t - 3 + i])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # causal: a later token changes no earlier output
    moved = short_conv(x.at[:, 7].add(1.0), kernel)
    np.testing.assert_array_equal(moved[:, :7], y[:, :7])
    assert not np.allclose(moved[:, 7:], y[:, 7:])
