"""The chunked gated delta rule (`ops/gated_delta.py`) against the
recurrence it stands for, token by token, and the short convolution
against its definition. float32 on the CPU: both sides are the same
mathematics in another order of sums, so they part by float32 rounding
alone (1e-7 to 2e-6 of a norm at these sizes); the tolerance, 2e-5, is
ten times that and a thousand times under what a wrong decay, a missing
`T` or an off-by-one mask reads (order 1e-2 to 1).

Since PR 37 `T = (I + A)^-1` is a value of its own: the kernel that makes
it (`ops/pallas/tril_inverse.py`, interpreted here) against
`numpy.linalg.inv`, and the solve's `custom_vjp`, which reads `T` where
autodiff through `solve_triangular` would invert again, against exactly
that."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import lax

from deep_vision_tpu.ops.gated_delta import (
    _solve,
    _unit_lower_inverse,
    from_chunks,
    gated_delta_chunks,
    gated_delta_rule,
    short_conv,
    to_chunks,
)
from deep_vision_tpu.ops.pallas.tril_inverse import (
    tril_inverse,
    tril_inverse_fits,
)

def gated_delta_recurrent(q, k, v, g, beta):
    """The recurrence itself, token by token, float32: `S_t = a_t S_{t-1}
    (I - b_t k_t k_t^T) + b_t v_t k_t^T`, `o_t = S_t q_t`; with `g` of (B,
    T, H, dk) a decay per key channel, `S_t = S_{t-1} Diag(e^{g_t}) (I -
    b_t k_t k_t^T) + b_t v_t k_t^T` (KDA's rule, S transposed). Same
    arguments and result as `gated_delta_rule`."""
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)

    def step(s, x):
        q, k, v, g, beta = x  # (B, H, ...)
        decay = jnp.exp(g)
        s = (decay[..., None, :] if g.ndim == 3 else decay[..., None, None]) \
            * s
        written = beta[..., None] * (v - jnp.einsum("bhvk,bhk->bhv", s, k))
        s = s + written[..., :, None] * k[..., None, :]
        return s, jnp.einsum("bhvk,bhk->bhv", s, q)

    b, _, h, dk = q.shape
    s0 = jnp.zeros((b, h, v.shape[-1], dk), jnp.float32)
    _, o = lax.scan(step, s0, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


B, T, H, DK, DV = 2, 32, 3, 8, 16
TOL = 2e-5


def inputs(decay: float, beta_scale: float, seed: int = 0, t: int = T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, t, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (B, t, H, DK)))
    v = jax.random.normal(ks[2], (B, t, H, DV))
    # beta up to 2: above 1 the state's eigenvalue along k is negative
    beta = 2 * jax.nn.sigmoid(beta_scale * jax.random.normal(ks[3], (B, t, H)))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[4], (B, t, H)))
    cotangent = jax.random.normal(ks[5], (B, t, H, DV))
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
    is_the_recurrence(args, ct, chunk)


def is_the_recurrence(args, ct, chunk):
    with jax.default_matmul_precision("highest"):
        chunked = lambda *a: gated_delta_rule(*a, chunk=chunk)
        assert apart(chunked(*args), gated_delta_recurrent(*args)) < TOL
        got = jax.grad(lambda *a: jnp.sum(chunked(*a) * ct),
                       argnums=range(5))(*args)
        want = jax.grad(lambda *a: jnp.sum(gated_delta_recurrent(*a) * ct),
                        argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert apart(a, b) < TOL, name


def test_the_cells_chunk_of_64_is_the_recurrence():
    """Two chunks of 64, the size the kernel inverts on the chip."""
    assert tril_inverse_fits(64)
    is_the_recurrence(*inputs(1.0, 3.0, seed=1, t=128), chunk=64)


def test_alike_keys_and_beta_near_2_stay_the_recurrence():
    """Where `I + A` is worst conditioned: every key of a head the same,
    beta 1.99, hardly any decay. `A`'s entries are then all near 1.99 and
    `T`'s alternate in sign up to 1.99 (a Neumann product's `A^32` would
    pass 1e27 on the way): substitution's intermediates are `T`'s own."""
    (q, k, v, g, beta), ct = inputs(1e-4, 1.0, seed=2, t=128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 1.99)
    is_the_recurrence((q, k, v, g, beta), ct, chunk=64)


def per_channel(args, decay: float, seed: int = 3):
    """The same operands with a decay per key channel, `-decay *
    softplus(normal)` a token a channel."""
    q, k, v, _, beta = args
    g = -decay * jax.nn.softplus(jax.random.normal(
        jax.random.PRNGKey(seed), q.shape))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay", [0.01, 1.0, 5.0],
                         ids=["weak_decay", "unit_decay", "strong_decay"])
def test_a_decay_per_channel_is_the_recurrence_outputs_and_all_gradients(
        chunk, decay):
    """KDA's rule (`g` of (B, T, H, dk)): at the cell's chunk of 64, four
    sub-chunks of 16 with the exponents between them factored through a
    boundary, and at 16, one sub-chunk whose exponents are all pairwise."""
    args, ct = inputs(1.0, 3.0, seed=4, t=128)
    is_the_recurrence(per_channel(args, decay), ct, chunk)


def test_decays_down_to_minus_30_a_token_stay_finite_and_the_recurrence():
    """Down to -30 a token a channel, `y` falls to about -1,300 along a
    chunk: `e^{y_i}` alone underflows and `e^{-y_j}` alone overflows, which
    the factoring through each sub-chunk's first token never forms. The
    decay's own gradient is the sum of contributions of `e^{-20}` and less,
    and parts from the recurrence's by up to 3e-5 of its norm; the rest by
    the float32 rounding of the other tests."""
    (q, k, v, _, beta), ct = inputs(1.0, 3.0, seed=5, t=128)
    g = -30.0 * jax.random.uniform(jax.random.PRNGKey(6), q.shape)
    assert float(jnp.min(g)) < -29.9
    args = (q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        chunked = lambda *a: gated_delta_rule(*a, chunk=64)
        got = chunked(*args)
        grads = jax.grad(lambda *a: jnp.sum(chunked(*a) * ct),
                         argnums=range(5))(*args)
        want = jax.grad(lambda *a: jnp.sum(gated_delta_recurrent(*a) * ct),
                        argnums=range(5))(*args)
        assert apart(got, gated_delta_recurrent(*args)) < TOL
    for name, a, b in zip("q k v g beta".split(), grads, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert apart(a, b) < (1e-4 if name == "g" else TOL), name
    assert bool(jnp.all(jnp.isfinite(got)))


def test_a_scalar_decay_broadcast_over_the_keys_is_the_scalar_path():
    """The shape of `g` selects the form: a scalar decay broadcast over
    `dk` runs the per-channel form and gives the scalar form's numbers to
    float32 rounding (the per-channel form multiplies `e^{y_i - y_r}
    e^{y_r - y_j}` where the scalar one takes `e^{y_i - y_j}`, so not to
    the bit), outputs and gradients."""
    args, ct = inputs(1.0, 3.0, seed=7, t=128)
    q, k, v, g, beta = args
    wide = jnp.broadcast_to(g[..., None], q.shape)
    with jax.default_matmul_precision("highest"):
        rule = lambda g: gated_delta_rule(q, k, v, g, beta, chunk=64)
        assert apart(rule(wide), rule(g)) < 2e-6
        d_wide = jax.grad(lambda g: jnp.sum(rule(g) * ct))(wide)
        d_g = jax.grad(lambda g: jnp.sum(rule(g) * ct))(g)
    assert apart(jnp.sum(d_wide, axis=-1), d_g) < 1e-5


def test_each_form_runs_under_its_own_scope():
    """The per-channel form's ops are `kda`'s, the scalar form's
    `gated_delta`'s: a trace's ops are counted apart by them."""
    args, _ = inputs(1.0, 1.0, t=64)
    text = lambda *a: jax.jit(lambda *a: gated_delta_rule(
        *a, chunk=64)).lower(*a).compile().as_text()
    scalar, wide = text(*args), text(*per_channel(args, 1.0))
    assert "gated_delta/" in scalar and "kda/" not in scalar
    assert "kda/" in wide and "gated_delta/" not in wide


def lower_triangles(c: int, lead=(2, 2, 3), seed: int = 0):
    a = jax.random.normal(jax.random.PRNGKey(seed), lead + (c, c))
    return jnp.tril(a, -1)


# 1 to 16: the parametrised cases' chunks; 32: one chunk a sequence; 64: the
# cell's. From 16 on the kernel's row-by-row substitution, below it the solve
@pytest.mark.parametrize("c", [1, 4, 8, 16, 32, 64])
def test_unit_lower_inverse_is_numpys(c):
    a = lower_triangles(c)
    want = np.linalg.inv(np.asarray(a, np.float64) + np.eye(c))
    got = np.asarray(_unit_lower_inverse(a))
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()
    # unit lower triangular to the bit: nothing above the diagonal
    np.testing.assert_array_equal(np.triu(got, 1), 0.0)
    np.testing.assert_array_equal(np.diagonal(got, axis1=-2, axis2=-1), 1.0)


@pytest.mark.parametrize("rows", [5, 128, 200])
def test_inverse_kernel_pads_its_last_tile(rows):
    """128 matrices a grid step: fewer, exactly one tile, one and a part."""
    a = lower_triangles(16, lead=(rows,), seed=rows)
    want = np.linalg.inv(np.asarray(a, np.float64) + np.eye(16))
    got = np.asarray(tril_inverse(a, interpret=True))
    assert got.shape == a.shape
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()


@pytest.mark.parametrize("c", [8, 64])
def test_solve_vjp_is_autodiff_through_the_solve(c):
    """`_solve`'s backward reads `T`: `d rhs = T^T g`, `dA = -(d rhs) WU^T`
    below the diagonal. The same numbers as autodiff through
    `solve_triangular`, and `dA` exactly zero where `A` has no entry."""
    a = 0.5 * lower_triangles(c, seed=3)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    rhs = jax.random.normal(ks[0], a.shape[:-1] + (12,))
    ct = jax.random.normal(ks[1], rhs.shape)

    def plain(a, rhs):
        return jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c), rhs, lower=True, unit_diagonal=True)

    with jax.default_matmul_precision("highest"):
        assert apart(_solve(a, rhs), plain(a, rhs)) < 2e-6
        got = jax.grad(lambda *x: jnp.sum(_solve(*x) * ct), (0, 1))(a, rhs)
        want = jax.grad(lambda *x: jnp.sum(plain(*x) * ct), (0, 1))(a, rhs)
    np.testing.assert_array_equal(np.triu(np.asarray(got[0])), 0.0)
    # autodiff hands back a full matrix: `a`'s own mask is upstream of it
    assert apart(got[0], jnp.tril(want[0], -1)) < 2e-6
    assert apart(got[1], want[1]) < 2e-6


def test_solve_counts_its_inversions_while_tracing():
    from deep_vision_tpu.obs.registry import get_registry

    counter = get_registry().counter("delta_rule_inverse_sites_total")
    before = counter.value
    args, _ = inputs(1.0, 1.0)
    jax.jit(lambda *a: gated_delta_rule(*a, chunk=8)).lower(*args)
    assert counter.value - before == 1


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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("trailing", [(), (8,), (4, 4)],
                         ids=["rank3", "rank4", "rank5"])
def test_the_two_crossings_are_inverses_and_keep_the_dtype(trailing, dtype):
    """`to_chunks`: token t = n C + c of (B, T, H, ...) lands at [n, b, h,
    c]; `from_chunks` brings it back; neither widens what it moves (g and
    beta are rank 3, q, k, v rank 4, a triangle would be rank 5)."""
    shape = (B, T, H) + trailing
    x = jnp.arange(np.prod(shape)).reshape(shape).astype(dtype)
    there = to_chunks(x, 8)
    assert there.shape == (T // 8, B, H, 8) + trailing
    assert there.dtype == x.dtype
    np.testing.assert_array_equal(there[2, 1, 0, 3], x[1, 2 * 8 + 3, 0])
    back = from_chunks(there)
    assert back.dtype == x.dtype
    np.testing.assert_array_equal(back, x)
    np.testing.assert_array_equal(to_chunks(back, 8), there)
    with pytest.raises(AssertionError, match="do not divide"):
        to_chunks(x, 5)


def test_the_token_major_rule_is_the_core_between_the_crossings():
    """`gated_delta_rule` is `from_chunks(core(to_chunks(...)))`: the core
    takes chunk-major operands in any float dtype and widens them itself,
    where its row math first needs float32."""
    args, _ = inputs(1.0, 1.0, seed=5)
    there = [to_chunks(x, 8) for x in args]
    o = gated_delta_chunks(*there)
    assert o.shape == (T // 8, B, H, 8, DV) and o.dtype == jnp.float32
    np.testing.assert_array_equal(from_chunks(o),
                                  gated_delta_rule(*args, chunk=8))
    # bfloat16 operands: the values the core sees are the rounded ones
    rounded = [x.astype(jnp.bfloat16) for x in there[:3]] + there[3:]
    widened = [x.astype(jnp.float32) for x in rounded[:3]] + there[3:]
    assert gated_delta_chunks(*rounded).dtype == jnp.float32
    np.testing.assert_array_equal(gated_delta_chunks(*rounded),
                                  gated_delta_chunks(*widened))


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
