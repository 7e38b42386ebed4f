"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464) in
chunked form, and the short causal depthwise convolution in front of it.

Per head, with a state `S` of `dv x dk` that starts at zero:

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T,    o_t = S_t q_t

`a_t = exp(g_t)` the decay (g <= 0), `b_t` in (0, 2) the writing strength
(above 1 the state's eigenvalue along `k_t` is negative:
`linear_allow_neg_eigval`). Token by token that is `T` dependent rank-one
updates. The chunked form (chunk C) does everything that needs no state as
batched matmuls over all chunks at once, and leaves three products a chunk
in a `lax.scan`. Within a chunk, `y_i` the running sum of `g` up to token
i, rows are tokens:

    A = tril_(b_i (k_i . k_j) e^{y_i - y_j})      strictly below the diagonal
    T = (I + A)^{-1}                              unit lower triangular
    W = T (b e^{y} K),   U = T (b V)

and with `M = S^T` (`dk x dv`) the state at the chunk's start:

    V' = U - W M
    O  = (Q e^{y}) M + tril(Q K^T e^{y_i - y_j}) V'      diagonal included
    M <- e^{y_C} M + (K e^{y_C - y})^T V'

(`V'` are the values the delta rule really writes: `b_t (v_t - a_t S_{t-1}
k_t)`; substituting them into `M_t = a_t M_{t-1} + k_t v'_t^T` gives the
three lines; checked against the recurrence, token by token, in
`tests/test_gated_delta.py`.)
Every exponent is of `y_i - y_j` with `j <= i`, so it never overflows. The
state, the decays and `T` are float32; the matmuls take operands in
`mm_dtype` (bfloat16 on the chip) and accumulate in float32. The backward
pass is autodiff's, with the scan's body recomputed (`jax.checkpoint`): a
step keeps each chunk's `M`, 74 KB a head, and not what the body makes of it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64


def short_conv(x, kernel):
    """Causal depthwise convolution over tokens: `y_t = sum_i kernel[i] *
    x_{t-K+1+i}`, zeros before the first token. x: (B, T, D); kernel:
    (K, D), the last tap on the current token (torch's `Conv1d(D, D, K,
    groups=D, padding=K-1)` cut to T). As shifted multiply-adds: K is 4."""
    with jax.named_scope("short_conv"):
        taps, t = kernel.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        kernel = kernel.astype(x.dtype)
        return sum(padded[:, i:i + t] * kernel[i] for i in range(taps))


def _mm(spec, a, b, mm_dtype):
    return jnp.einsum(spec, a.astype(mm_dtype), b.astype(mm_dtype),
                      preferred_element_type=jnp.float32)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK,
                     mm_dtype=jnp.float32):
    """q, k: (B, T, H, dk); v: (B, T, H, dv); g (log-decay, <= 0) and beta:
    (B, T, H). -> o (B, T, H, dv) float32. T divides into chunks."""
    with jax.named_scope("gated_delta"):
        return _chunked(q, k, v, g, beta, chunk, jnp.dtype(mm_dtype))


def _chunked(q, k, v, g, beta, chunk, mm_dtype):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    assert t % chunk == 0, f"{t} tokens do not divide into chunks of {chunk}"
    n = t // chunk
    mm = functools.partial(_mm, mm_dtype=mm_dtype)

    def chunks(x):  # (B, T, H, ...) -> (N, B, H, C, ...)
        x = x.astype(jnp.float32).reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    y = jnp.cumsum(g, axis=-1)  # (N, B, H, C)
    diff = y[..., :, None] - y[..., None, :]  # y_i - y_j
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # masked before the exponential: above the diagonal y_i - y_j >= 0
    decay = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))
    a = jnp.where(row > col, beta[..., None] * decay
                  * mm("nbhid,nbhjd->nbhij", k, k), 0.0)
    rhs = jnp.concatenate([(beta * jnp.exp(y))[..., None] * k,
                           beta[..., None] * v], axis=-1)
    wu = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(chunk, dtype=a.dtype), rhs, lower=True,
        unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    qk = decay * mm("nbhid,nbhjd->nbhij", q, k)
    q_in = q * jnp.exp(y)[..., None]
    k_out = k * jnp.exp(y[..., -1:] - y)[..., None]
    carry_decay = jnp.exp(y[..., -1])[..., None, None]  # (N, B, H, 1, 1)

    @jax.checkpoint
    def body(m, xs):
        w, u, q_in, qk, k_out, carry_decay = xs
        written = u - mm("bhik,bhkv->bhiv", w, m)
        o = mm("bhik,bhkv->bhiv", q_in, m) \
            + mm("bhij,bhjv->bhiv", qk, written)
        m = carry_decay * m + mm("bhik,bhiv->bhkv", k_out, written)
        return m, o

    _, o = lax.scan(body, jnp.zeros((b, h, dk, dv), jnp.float32),
                    (w, u, q_in, qk, k_out, carry_decay))
    # (N, B, H, C, dv) -> (B, T, H, dv)
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t, h, dv)
