"""BatchNorm's tail `y = act(x*scale + bias [+ residual])` (nn/layers.py
`scale_bias_act`): one jax.numpy expression with a written-out backward,
the same on every platform. Held here to an independent float32
expression under autodiff, to plain BatchNorm -> add -> relu inside the
ResNet blocks, and to staying plain: no `shard_map`, no `pallas_call`.
(What the v5e compiler makes of it: tests/test_tpu_lowering.py, the one
file that loads libtpu.)
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.models.resnet import BasicBlock, BottleneckBlock
from deep_vision_tpu.nn import layers
from deep_vision_tpu.nn.layers import ConvBN, FusedBatchNorm, scale_bias_act

_TIE_MARGIN = 0.1


def _inputs(c, dtype, residual):
    """x, scale, bias, residual, cotangent: every big tensor already
    rounded to `dtype`, so both sides see the same numbers, and no
    pre-activation within `_TIE_MARGIN` of the ReLU's kink."""
    rng = np.random.RandomState(c)
    shape = (2, 3, 3, c)
    a = (rng.rand(c) + 0.5).astype(np.float32)
    b = rng.randn(c).astype(np.float32)
    r = rng.randn(*shape).astype(np.float32) if residual else None

    def rounded(v):
        return np.asarray(jnp.asarray(v).astype(dtype).astype(jnp.float32))

    x = rng.randn(*shape).astype(np.float32)
    pre = rounded(x) * a + b + (rounded(r) if residual else 0.0)
    near = np.abs(pre) < _TIE_MARGIN
    x = np.where(near, x + np.where(pre >= 0, 4.0, -4.0) * _TIE_MARGIN / a, x)
    pre = rounded(x) * a + b + (rounded(r) if residual else 0.0)
    assert np.abs(pre).min() > _TIE_MARGIN / 2
    g = rounded(rng.randn(*shape).astype(np.float32))

    def cast(v):
        return None if v is None else jnp.asarray(v).astype(dtype)

    return cast(x), jnp.asarray(a), jnp.asarray(b), cast(r), jnp.asarray(g)


def _plain_float32(x, a, b, r, act):
    y = x.astype(jnp.float32) * a + b
    if r is not None:
        y = y + r.astype(jnp.float32)
    return jnp.maximum(y, 0.0) if act == "relu" else y


# ResNet-50's five stage widths, and one that divides no tile
@pytest.mark.parametrize("c", [64, 256, 512, 1024, 2048, 24])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("residual", [True, False])
@pytest.mark.parametrize("act", ["relu", None])
def test_tail_value_and_gradients_match_plain_float32(c, dtype, residual, act):
    x, a, b, r, g = _inputs(c, dtype, residual)
    args = (x, a, b) + ((r,) if residual else ())
    argnums = tuple(range(len(args)))

    def loss(fn):
        def f(x, a, b, r=None):
            return jnp.sum(fn(x, a, b, r).astype(jnp.float32) * g)
        return f

    y = scale_bias_act(x, a, b, residual=r, act=act)
    assert y.dtype == x.dtype  # bf16 in, bf16 out: nothing widens in HBM
    want_y = _plain_float32(x, a, b, r, act)
    got = jax.grad(loss(lambda x, a, b, r: scale_bias_act(
        x, a, b, residual=r, act=act)), argnums)(*args)
    want = jax.grad(loss(lambda x, a, b, r: _plain_float32(
        x, a, b, r, act)), argnums)(*args)
    # one rounding of the io dtype apart where a big tensor is written;
    # the per-channel sums only reorder
    io = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want_y),
                               rtol=io, atol=io)
    for name, u, v in zip(("dx", "dscale", "dbias", "dresidual"), got, want):
        assert u.dtype == v.dtype and u.shape == v.shape, name
        tol = io if name in ("dx", "dresidual") else 1e-5
        np.testing.assert_allclose(
            np.asarray(u, np.float32), np.asarray(v, np.float32),
            rtol=tol, atol=tol * float(jnp.abs(v).max()), err_msg=name)


def _bwd_before_pr35(act, res, g):
    """`_scale_bias_act_bwd` as PR 30 wrote it and PR 35 found it: the
    oracle. The cotangent is widened to float32 first and masked there."""
    x, scale, bias, y = res
    gf = g.astype(jnp.float32)
    if act == "relu":
        gf = jnp.where(y > 0, gf, 0.0)
    axes = tuple(range(x.ndim - 1))
    dx = (gf * scale.astype(jnp.float32)).astype(x.dtype)
    dscale = jnp.sum(gf * x.astype(jnp.float32), axis=axes)
    dbias = jnp.sum(gf, axis=axes)
    return (dx, dscale.astype(scale.dtype), dbias.astype(bias.dtype),
            gf.astype(x.dtype))


@pytest.mark.parametrize("c", [256, 24])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("residual", [True, False])
@pytest.mark.parametrize("act", ["relu", None])
def test_backward_is_bit_equal_to_the_one_before_the_stored_gradient(
        c, dtype, residual, act):
    """PR 35 masks the cotangent in the io dtype it arrives in and widens
    it after (so that XLA stores the masked array and no `pred` beside the
    unmasked one: tests/test_tpu_lowering.py). A select commutes with an
    exact cast: no value may change, jitted as the step runs it."""
    x, a, b, r, g = _inputs(c, dtype, residual)
    y = scale_bias_act(x, a, b, residual=r, act=act)
    # ties too: a dead position, and one the mask must not let through
    y = y.at[0, 0, 0, :4].set(0)
    res, g = (x, a, b, y), g.astype(dtype)
    got = jax.jit(layers._scale_bias_act_bwd, static_argnums=0)(act, res, g)
    want = jax.jit(_bwd_before_pr35, static_argnums=0)(act, res, g)
    for name, u, v in zip(("dx", "dscale", "dbias", "dresidual"), got, want):
        assert u.dtype == v.dtype, name
        np.testing.assert_array_equal(np.asarray(u, np.float32),
                                      np.asarray(v, np.float32), err_msg=name)
    if act == "relu":
        assert not np.asarray(got[0], np.float32)[0, 0, 0, :4].any()


@pytest.mark.parametrize("residual", [True, False])
def test_relu_slope_at_an_exact_tie_is_zero(residual):
    """The written backward masks on `y > 0`: a pre-activation of exactly 0
    passes no gradient, where `jnp.maximum` would pass half (ROADMAP C10:
    the benchmark's float32 reference is `jnp.maximum`)."""
    x = jnp.zeros((1, 1, 2, 4))
    r = jnp.zeros_like(x) if residual else None
    a, b = jnp.ones((4,)), jnp.zeros((4,))
    grads = jax.grad(lambda x, a, b: jnp.sum(scale_bias_act(
        x, a, b, residual=r)), argnums=(0, 1, 2))(x, a, b)
    for g in grads:
        np.testing.assert_array_equal(np.asarray(g), 0.0)
    half = jax.grad(lambda x: jnp.sum(jnp.maximum(x, 0.0)))(x)
    np.testing.assert_array_equal(np.asarray(half), 0.5)


def test_tail_refuses_what_it_cannot_fold():
    x = jnp.ones((2, 2, 2, 8))
    p = jnp.ones((8,))
    with pytest.raises(ValueError, match="unsupported act"):
        scale_bias_act(x, p, p, act="gelu")
    with pytest.raises(ValueError, match="scale/bias"):
        scale_bias_act(x, jnp.ones((4,)), p)
    with pytest.raises(ValueError, match="residual shape"):
        scale_bias_act(x, p, p, residual=jnp.ones((2, 2, 2, 4)))


# -- inside the ResNet blocks: the tail against BatchNorm -> add -> relu -----

class _PlainBasic(nn.Module):
    """`BasicBlock` with every tail unfolded; same auto-names, same tree."""
    features: int
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x, train=True):
        y = nn.relu(ConvBN(self.features, (3, 3), strides=self.strides,
                           act=None)(x, train))
        y = ConvBN(self.features, (3, 3), act=None)(y, train)
        if x.shape[-1] != self.features or self.strides != (1, 1):
            x = ConvBN(self.features, (1, 1), strides=self.strides,
                       act=None)(x, train)
        return nn.relu(y + x)


class _PlainBottleneck(nn.Module):
    features: int
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x, train=True):
        y = nn.relu(ConvBN(self.features, (1, 1), act=None)(x, train))
        y = nn.relu(ConvBN(self.features, (3, 3), strides=self.strides,
                           act=None)(y, train))
        y = nn.Conv(self.features * 4, (1, 1), use_bias=False)(y)
        y = FusedBatchNorm(use_running_average=not train)(y)
        if x.shape[-1] != self.features * 4 or self.strides != (1, 1):
            x = ConvBN(self.features * 4, (1, 1), strides=self.strides,
                       act=None)(x, train)
        return nn.relu(y + x)


@pytest.mark.parametrize("train", [True, False])
@pytest.mark.parametrize("block, plain, c_in, strides", [
    (BottleneckBlock, _PlainBottleneck, 32, (1, 1)),  # identity skip
    (BottleneckBlock, _PlainBottleneck, 16, (2, 2)),  # projection
    (BasicBlock, _PlainBasic, 8, (1, 1)),
    (BasicBlock, _PlainBasic, 16, (2, 2)),
])
def test_resnet_block_agrees_with_plain_batchnorm_add_relu(
        block, plain, c_in, strides, train):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 8, 8, c_in).astype(np.float32))
    w = jnp.asarray(rng.randn(4, 8 // strides[0], 8 // strides[0],
                              32 if block is BottleneckBlock else 8)
                    .astype(np.float32))
    fused, unfused = block(8, strides), plain(8, strides)
    v = fused.init(jax.random.PRNGKey(0), x, train=False)
    assert (jax.tree.structure(v) == jax.tree.structure(
        unfused.init(jax.random.PRNGKey(0), x, train=False)))
    # a BN scale of exactly 0 (the bottleneck's recipe) and of 1 hide the
    # scale's own gradient path; running statistics off their init
    v = jax.tree.map(lambda p: p + 0.3 * jnp.asarray(
        rng.rand(*p.shape).astype(np.float32)), v)

    def run(module):
        def loss(params, x):
            out = module.apply({**v, "params": params}, x, train=train,
                               mutable=["batch_stats"] if train else False)
            y, stats = out if train else (out, {})
            return jnp.sum(y * w), (y, stats)
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            v["params"], x)

    got, want = run(fused), run(unfused)
    for u, t in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(t), rtol=2e-4,
                                   atol=2e-4 * float(jnp.abs(t).max()))


# -- it stays plain jax.numpy, whatever mesh is in context -------------------

@pytest.mark.parametrize("residual", [True, False])
def test_tail_under_a_mesh_is_no_shard_map_and_no_pallas_call(
        mesh8, residual):
    x = jnp.ones((8, 1, 1, 64), jnp.bfloat16)
    p = jnp.ones((64,), jnp.float32)

    def fwd_bwd(x, a, b):
        return jax.value_and_grad(lambda x, a, b: jnp.sum(scale_bias_act(
            x, a, b, residual=x if residual else None)
            .astype(jnp.float32)), argnums=(0, 1, 2))(x, a, b)

    with jax.set_mesh(mesh8):
        jaxpr = str(jax.make_jaxpr(fwd_bwd)(x, p, p))
    assert "pallas_call" not in jaxpr and "shard_map" not in jaxpr
