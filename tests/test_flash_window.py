"""Causal sliding-window attention by block skipping
(`ops/pallas/flash_attention.py`), interpreted on the CPU: the forward and
the gradients of q, k and v against the dense windowed expression, with the
window under, at and over a block and the sequence 4 blocks and more; the
grids' sizes and the key blocks they visit against the closed form; the
causal kernels untouched where no window is given; and the decoder's
routing and counters.

Float32 at "highest" on both sides: the kernels' online softmax and its
recomputation part from the dense softmax by float32 rounding (a few 1e-7
of an element of order 1); 2e-5 stands far under what one key in or out of
the window moves (1e-2 at these shapes)."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.ops.pallas.flash_attention import (
    flash_attention, window_key_blocks, window_span)


def dense_window(q, k, v, window):
    t = q.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
    i = jnp.arange(t)
    seen = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def inputs(t, seed=0, b=1, h=2, d=128):
    return [jax.random.normal(k, (b, t, h, d))
            for k in jax.random.split(jax.random.PRNGKey(seed), 4)]


@pytest.mark.parametrize("t,block_q,block_k,window", [
    (512, 128, 128, 100),   # under a block
    (512, 128, 128, 128),   # a block
    (512, 128, 128, 300),   # over a block, not a multiple
    (640, 128, 128, 256),   # five blocks, two a band
    (512, 128, 64, 200),    # query blocks over key blocks
    (512, 64, 128, 200),    # key blocks over query blocks
])
def test_forward_and_all_three_gradients_are_the_dense_windows(
        t, block_q, block_k, window):
    q, k, v, g = inputs(t)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=block_q,
        block_k=block_k, interpret=True)
    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(flash, q, k, v)
        want, pull_want = jax.vjp(
            lambda q, k, v: dense_window(q, k, v, window), q, k, v)
        grads, want_grads = pull(g), pull_want(g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for name, a, b in zip("qkv", grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=name)
    # one key more (a window one wider) is seen
    wider = dense_window(q, k, v, window + 1)
    assert float(jnp.max(jnp.abs(wider - want))) > 1e-3


@pytest.mark.parametrize("t,block,window", [
    (4096, 512, 512), (4096, 256, 512), (2048, 128, 300), (1024, 512, 2048)])
def test_the_grids_follow_the_window_not_the_sequence(t, block, window):
    """A query block's row visits the key blocks that meet its band and a
    key block's column the query blocks that see it; the forward visits
    min(i + 1, W / B + 1) key blocks of query block i where W is a whole
    number of blocks B, and a causal grid i + 1."""
    rows, cols = window_span(t, block, block, window)
    n = t // block
    visited, causal = window_key_blocks(t, block, block, window)
    assert causal == n * (n + 1) // 2
    if window % block == 0:
        per = window // block + 1
        assert rows == cols == min(per, n)
        assert visited == sum(min(i + 1, per) for i in range(n))
    else:
        assert rows == cols == min(-(-(window - 1) // block) + 1, n)
    if window < t:
        assert visited < causal
    # at the cell's shape: 2 key blocks a row of 512, against 8 causal
    assert window_span(4096, 512, 512, 512) == (2, 2)
    assert window_key_blocks(4096, 512, 512, 512) == (15, 36)


def test_no_window_is_the_causal_kernels_as_they_were():
    """The jaxpr of the causal forward and backward, kernels included, is
    the one these kernels had before windows came (its digest taken then,
    at these shapes and this JAX)."""
    spec = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    f = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128,
        interpret=True).astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(
        spec, spec, spec))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "a47fb7c4179ea1199b0311d7f5ebbbd16d4ed05a8d0f8e0633320b12b2e08ceb")
    assert "flash_window" not in text and "flash_fwd" in text


def test_the_windowed_calls_are_named_apart():
    spec = jax.ShapeDtypeStruct((1, 256, 1, 128), jnp.float32)
    f = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=128, block_q=128, block_k=128,
        interpret=True))
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(spec, spec, spec))
    for name in ("flash_window_fwd", "flash_window_bwd_dq",
                 "flash_window_bwd_dkv"):
        assert f"name={name}" in text
    assert "name=flash_fwd" not in text
    with pytest.raises(ValueError):
        flash_attention(*inputs(256)[:3], causal=False, window=64,
                        interpret=True)


def test_the_decoder_routes_a_window_to_the_kernel_and_counts_its_blocks(
        monkeypatch):
    from deep_vision_tpu.models import decoder
    from deep_vision_tpu.obs.registry import get_registry

    def counts():
        return {(m.name, tuple(sorted(m.labels.items()))): m.value
                for m in get_registry().metrics() if m.kind == "counter"}

    q, k, v, _ = inputs(1024, seed=1, h=1)
    before = counts()
    with jax.default_matmul_precision("highest"):
        dense = decoder.causal_attention(q, k, v, window=512)
        monkeypatch.setattr(decoder, "attention_path",
                            lambda *a: "streaming")
        kernel = decoder.causal_attention(q, k, v, window=512)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(dense),
                               np.asarray(dense_window(q, k, v, 512)),
                               atol=2e-5)
    grew = lambda key: counts().get(key, 0) - before.get(key, 0)
    assert grew(("attention_sites_total", (("path", "dense"),))) == 1
    assert grew(("attention_sites_total", (("path", "window"),))) == 1
    # two query blocks of 512: 1 + 2 key blocks visited, 1 + 2 causal
    assert grew(("attention_key_blocks_total", (("kind", "visited"),))) == 3
    assert grew(("attention_key_blocks_total", (("kind", "causal"),))) == 3
