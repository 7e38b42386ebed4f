"""Attention kernels: interpret-mode CPU tests against dense golden.

The streaming kernel's older tests are jit-heavy and stay out of the fast
tier (`-m "not slow"`), and their unit-normal inputs cannot see a wrong
`delta` (PERF.md §6, PR 34): `test_flash_bwd_where_tokens_are_alike` is in
the fast tier for that. The single-block kernel's tests, below them, are in
it too: every ViT on a TPU takes that path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.ops.pallas.flash_attention import (
    FUSED_MAX_TOKENS,
    _dense_reference,
    flash_attention,
    flash_attention_with_lse,
    fused_attention,
)

slow = pytest.mark.slow


def _qkv(b=2, t=64, h=2, d=32, seed=0, tk=None):
    rng = np.random.RandomState(seed)
    tk = tk or t
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    return q, k, v


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = _dense_reference(q, k, v, causal, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@slow
def test_flash_cross_attention_shapes():
    q, k, v = _qkv(t=32, tk=64)
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    want = _dense_reference(q, k, v, False, q.shape[-1] ** -0.5)
    assert got.shape == (2, 32, 2, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@slow
def test_flash_single_block():
    q, k, v = _qkv(t=16)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = _dense_reference(q, k, v, True, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@slow
def test_flash_extreme_scores_stable():
    q, k, v = _qkv(seed=3)
    q = q * 120.0  # rows with true max << 0 must survive online softmax
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = _dense_reference(q, k, v, True, q.shape[-1] ** -0.5)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4)


@slow
def test_flash_grads_match_dense():
    q, k, v = _qkv(b=1, t=32, h=1, d=16)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=16, block_k=16) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, True, q.shape[-1] ** -0.5) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-4)


@slow
def test_flash_bf16_io():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(t=32))
    got = flash_attention(q, k, v, block_q=16, block_k=16)
    assert got.dtype == jnp.bfloat16
    want = _dense_reference(q, k, v, False, q.shape[-1] ** -0.5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_kernel_matches_dense(causal):
    """The Pallas backward (dq + dkv kernels) vs autodiff of dense attention,
    including non-square blocks and multi-block grids."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(2, 64, 2, 8), jnp.float32) for _ in range(3))
    g = jnp.asarray(rng.randn(2, 64, 2, 8), jnp.float32)

    def f_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=causal,
                                        block_q=16, block_k=32), g)

    def f_dense(q, k, v):
        return jnp.vdot(_dense_reference(q, k, v, causal, q.shape[-1] ** -0.5), g)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@slow
def test_flash_bwd_cross_attention():
    """Tq != Tk exercises the independent q/k grid extents in both kernels."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 64, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 64, 2, 8), jnp.float32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=16, block_k=16) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, False, q.shape[-1] ** -0.5) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@slow
def test_flash_bwd_bf16():
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 32, 1, 8), jnp.bfloat16)
               for _ in range(3))
    grads = jax.grad(
        lambda q, k, v: float(0) + jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
            .astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    dense = jax.grad(
        lambda q, k, v: jnp.sum(
            _dense_reference(q, k, v, True, q.shape[-1] ** -0.5)
            .astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, dense):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.1, atol=0.1)


@slow
def test_flash_with_lse_grads_include_lse_cotangent():
    """A loss that uses BOTH outputs must differentiate exactly (the ring
    merge depends on lse; its cotangent shifts the delta term)."""
    from deep_vision_tpu.ops.pallas.flash_attention import (
        flash_attention_with_lse,
    )

    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
               for _ in range(3))
    scale = 8 ** -0.5

    def f_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, block_q=16, block_k=16)
        return jnp.sum(out ** 2) + jnp.sum(lse[:, :, 0] ** 2)

    def f_dense(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
        lse = jax.scipy.special.logsumexp(s, axis=-1)  # (B,H,T)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
        return jnp.sum(out ** 2) + jnp.sum(
            lse.transpose(0, 2, 1).reshape(1, 32, 2).reshape(-1) ** 2
        )

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def _rel(got, want):
    """Distance of `got` from `want`, as a share of `want`'s norm."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("with_lse", [False, True], ids=["out", "out+lse"])
@pytest.mark.parametrize("causal", [False, True],
                         ids=["bidirectional", "causal"])
@pytest.mark.parametrize("c", [0, 3, 10])
def test_flash_bwd_where_tokens_are_alike(c, causal, with_lse):
    """dq, dk and dv in bf16 against autodiff of the float32 dense
    expression on the same bf16 values, where every token is a common
    vector (c times a unit normal one) plus its own unit normal part, in q,
    k and v alike. In ds = P (dP - delta) what is common to a row of dP must
    cancel, and the true dq is what the keys' deviations from their mean
    leave: with `delta` taken from the bf16-rounded output, dq read 47% off
    at c = 3 and 292% at c = 10 (17%, 130% causal), dk 1.3%, and nothing at
    c = 0, where every older test sits. The dense bf16 path reads <= 1.7%.
    With an lse cotangent, `delta_shift` rides the same sum (the ring's
    merge, parallel/ring_attention.py)."""
    b, t, h, dh = 1, 1024, 2, 64
    rng = np.random.RandomState(c)
    q, k, v = (jnp.asarray(c * rng.randn(1, 1, h, dh) + rng.randn(b, t, h, dh),
                           jnp.bfloat16) for _ in range(3))
    g = jnp.asarray(rng.randn(b, t, h, dh), jnp.float32)
    g_lse = jnp.asarray(rng.randn(b * h, t), jnp.float32)
    scale = 0.1 * dh ** -0.5

    def f_flash(q, k, v):
        if not with_lse:
            out = flash_attention(q, k, v, causal=causal, scale=scale,
                                  block_q=256, block_k=256)
            return jnp.vdot(out.astype(jnp.float32), g)
        out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                            scale=scale, block_q=256,
                                            block_k=256)
        return (jnp.vdot(out.astype(jnp.float32), g)
                + jnp.vdot(lse[:, :, 0], g_lse))

    def f_dense(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision="highest") * scale
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v,
                         precision="highest")
        loss = jnp.vdot(out, g)
        if with_lse:
            lse = jax.scipy.special.logsumexp(s, axis=-1)  # (B, H, T)
            loss = loss + jnp.vdot(lse.reshape(b * h, t), g_lse)
        return loss

    got = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16
        assert _rel(a, w) < 0.01, (name, _rel(a, w))


# -- a sequence that fits one block: fused_attention --------------------------

def _dense_from_qkv(qkv, heads):
    """models/vit.py Attention's dense expression on the projection's output."""
    b, t, d3 = qkv.shape
    dh = d3 // 3 // heads
    q, k, v = (qkv.reshape(b, t, 3, heads, dh)[:, :, i] for i in range(3))
    s = jnp.einsum("bthd,bshd->bhts", q, k) * dh ** -0.5
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v).reshape(b, t, heads * dh)


@pytest.mark.parametrize("with_bias", [False, True], ids=["qkv", "qkv+bias"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("heads", [12, 6])
@pytest.mark.parametrize("t", [196, 50, 256])  # unaligned, tiny, aligned
def test_fused_matches_dense_fwd_and_grad(t, heads, dtype, tol, with_bias):
    """Forward, and the gradient with respect to q, k and v, through the
    qkv-in / o-out interface, against the dense expression. With a bias:
    the projection's output in its own (B, T, 3, H, Dh) and its bias, added
    before the dense expression; the bias's gradient is what the backward
    kernel sums over an image's tokens in VMEM, where rows 197-208 and up
    of its tiles are padding and must add nothing."""
    rng = np.random.RandomState(t + heads)
    qkv = jnp.asarray(rng.randn(2, t, 3 * heads * 64), dtype)
    g = jnp.asarray(rng.randn(2, t, heads * 64), dtype)
    bias = None
    if with_bias:
        qkv = qkv.reshape(2, t, 3, heads, 64)
        bias = jnp.asarray(rng.randn(3, heads, 64), jnp.float32)

    def dense(x, b):
        x = x if b is None else x + b.astype(x.dtype)
        return _dense_from_qkv(x.reshape(2, t, -1), heads)

    got, vjp = jax.vjp(lambda x, b: fused_attention(x, heads, b), qkv, bias)
    want, ref_vjp = jax.vjp(dense, qkv, bias)
    assert got.shape == want.shape and got.dtype == dtype
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(got), f32(want), rtol=tol, atol=tol)
    (dqkv, dbias), (ref, ref_bias) = vjp(g), ref_vjp(g)
    assert dqkv.shape == qkv.shape and dqkv.dtype == dtype
    for name, a, b in zip("qkv", np.split(f32(dqkv).reshape(2, t, -1), 3, -1),
                          np.split(f32(ref).reshape(2, t, -1), 3, -1)):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max(),
                                   err_msg=f"d{name}")
    if with_bias:
        # sums of 2 t rounded terms: held by the norm, not element by
        # element; over all three parts, because k's is nought but for
        # rounding (a vector added to every key moves each row of scores
        # by a constant)
        assert dbias.shape == bias.shape and dbias.dtype == jnp.float32
        assert _rel(dbias, ref_bias) < tol
        with pytest.raises(ValueError, match="takes a bias of"):
            fused_attention(qkv, heads, bias.reshape(-1))
    # the inference primal (no log-sum-exp written) is the same forward
    np.testing.assert_array_equal(f32(fused_attention(qkv, heads, bias)),
                                  f32(got))


def test_fused_padded_keys_carry_no_probability():
    """196 keys pad to 208 rows of zeros in VMEM, whose logits would be 0.
    With every real logit at -128 an unmasked pad would take all of the
    probability and the output would be V's padding, 0; masked before the
    row max, the output is the mean of the 196 real values."""
    t, heads = 196, 2
    rng = np.random.RandomState(0)
    v = rng.randn(2, t, heads * 64).astype(np.float32)
    qkv = jnp.asarray(np.concatenate(
        [np.full_like(v, 4.0), np.full_like(v, -4.0), v], axis=-1))
    out = fused_attention(qkv, heads)
    want = np.broadcast_to(v.mean(axis=1, keepdims=True), v.shape)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


def test_fused_refuses_what_does_not_fit_one_block():
    x = jnp.zeros((1, FUSED_MAX_TOKENS + 1, 3 * 128), jnp.float32)
    with pytest.raises(ValueError, match="fused_attention takes"):
        fused_attention(x, 2)
    with pytest.raises(ValueError, match="fused_attention takes"):
        fused_attention(jnp.zeros((1, 16, 3 * 192), jnp.float32), 4)  # Dh 48


def _fake_platform(monkeypatch, name):
    from deep_vision_tpu.core import backend

    monkeypatch.setattr(backend, "current_platform", lambda: name)


@pytest.mark.parametrize("t, tpu", [
    (FUSED_MAX_TOKENS, "fused"), (FUSED_MAX_TOKENS + 1, "dense"),
    (196, "fused"), (1024, "streaming"), (1536, "dense")])
def test_attention_routes_by_shape(monkeypatch, t, tpu):
    from deep_vision_tpu.models.vit import attention_path

    assert attention_path(t, 12, 768) == "dense"  # the CPU: no compiled Pallas
    _fake_platform(monkeypatch, "tpu")
    assert attention_path(t, 12, 768) == tpu
    # heads that do not fill 128-lane slabs keep the dense expression
    assert attention_path(196, 4, 192) == "dense"


def test_attention_stays_dense_where_the_mesh_splits_the_heads(
        monkeypatch, mesh8, mesh4x2):
    """Data-parallel meshes run the kernel per shard; under tensor
    parallelism XLA partitions the dense expression by heads, which a
    Mosaic call would have gathered."""
    from deep_vision_tpu.models.vit import attention_path

    _fake_platform(monkeypatch, "tpu")
    with jax.set_mesh(mesh8):
        assert attention_path(196, 12, 768) == "fused"
    with jax.set_mesh(mesh4x2):
        assert attention_path(196, 12, 768) == "dense"


@pytest.mark.parametrize("platform, want", [
    ("tpu", {"fused": 12, "streaming": 0, "dense": 0}),
    ("cpu", {"fused": 0, "streaming": 0, "dense": 12})])
def test_attention_sites_are_counted_by_path(monkeypatch, platform, want):
    """`attention_sites_total{path=...}`: one increment per Attention site
    traced. vit_b16 has 12; with compiled Pallas all take the fused path."""
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.obs.registry import get_registry

    model = get_model("vit_b16", dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype),
                           train=False))
    count = lambda path: get_registry().counter(
        "attention_sites_total", labels={"path": path}).value
    _fake_platform(monkeypatch, platform)
    before = {path: count(path) for path in want}
    jax.eval_shape(lambda v, x: model.apply(v, x, train=False), variables, x)
    assert {path: count(path) - before[path] for path in want} == want


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["f32", "bf16"])
def test_attention_keeps_dense_generals_parameters(dtype):
    """`Attention` projects through `QkvProjection`, which hands its bias to
    the attention rather than adding it. Its parameters are those of the
    `nn.DenseGeneral((3, H, Dh), name="qkv")` it replaced: the same tree, and
    from a seed the same values to the bit, so that a seeded reference
    (benchmark/reference/vit.py) starts from the program's weights and a
    checkpoint of either loads into the other. On the dense path the output
    is DenseGeneral's too."""
    import flax.linen as nn

    from deep_vision_tpu.models.vit import Attention

    class Before(nn.Module):
        @nn.compact
        def __call__(self, x):
            qkv = nn.DenseGeneral((3, 4, 64), dtype=dtype, name="qkv")(x)
            q, k, v = (qkv[:, :, i] for i in range(3))
            s = jnp.einsum("bthd,bshd->bhts", q, k) * 64 ** -0.5
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            o = jnp.einsum("bhts,bshd->bthd", p, v)
            return nn.DenseGeneral(256, axis=(-2, -1), dtype=dtype,
                                   name="out")(o)

    x = jnp.asarray(np.random.RandomState(0).randn(2, 20, 256), jnp.float32)
    want = Before().init(jax.random.PRNGKey(7), x)
    got = Attention(4, dtype=dtype).init(jax.random.PRNGKey(7), x)
    assert jax.tree.map(jnp.shape, got) == {"params": {
        "qkv": {"kernel": (256, 3, 4, 64), "bias": (3, 4, 64)},
        "out": {"kernel": (4, 64, 256), "bias": (256,)}}}
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    jax.tree.map(np.testing.assert_array_equal, got, want)
    # a bias that is not nought, so that the add is seen
    want["params"]["qkv"]["bias"] = jnp.asarray(
        np.random.RandomState(1).randn(3, 4, 64), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(Attention(4, dtype=dtype).apply(want, x), np.float32),
        np.asarray(Before().apply(want, x), np.float32))
