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

`T` is a value of the program, made once a layer a step (PR 37). It comes
from row-by-row forward substitution with the chunks in the lanes, one
Pallas call (`ops/pallas/tril_inverse.py`, `gdn_inverse`; below its sizes,
a tiny test's chunk, from one `solve_triangular` against the identity),
under the scope `delta_inverse`, and carries the name `INVERSE_NAME`, by
which a recomputed block keeps it (`models/decoder.KEPT`): 64 x 64
float32 a chunk, 31.5 MB a layer at 2 x 2048 tokens and 30 heads. `W, U =
T rhs` is then a product, and so is the backward pass (`_solve`'s
`custom_vjp`), which reads `T` where autodiff through a solve would invert
again: from `G = d(W, U)`,

    X = T^T G,    d rhs = X,    dA = -tril_(X (W, U)^T)

Those three products are float32 in and out at `Precision.HIGHEST`, as the
solve's own were: `W` and `U` are rounded to `mm_dtype` only where the
scan's body takes them.

Where the crossing stands (PR 39). The model's tensors are token-major,
`(B, T, H, d)`; everything above is chunk-major, `(N, B, H, C, d)`, and
the change between the two is a pass over HBM (on a TPU two: the minor
dimension changes under the tiling, a `reshape`, and the chunks come to
the front, a `copy`). `to_chunks` and `from_chunks` are that change and
nothing else: they move a tensor in the dtype it arrives in, so a caller
crosses where the tensor is narrowest (`models/olmo_hybrid.GatedDeltaNet`:
q, k, v as the bf16 their convolutions leave, `o` as the bf16 the gate
multiplies) and does its float32 row math (the l2 norm of q and k, the
output's RMSNorm) on the chunk side, where it costs no pass of its own.
`gated_delta_chunks` is the rule between the crossings and widens its
operands where its row math first needs float32; `gated_delta_rule` is
`from_chunks(gated_delta_chunks(to_chunks(...)))` for a token-major caller.
`to_chunks` pins its result (`lax.optimization_barrier`): left free, XLA
fuses the consumer's widening `convert` into the producer in front of the
crossing and moves float32 after all (one layer, compiled for a v5e: 11
float32 movers of 1.20 GB with the barrier left out, 1 of 0.06 GB with
it; `tests/test_tpu_lowering.py` holds the count). The barrier's transpose
is a barrier, so the cotangents cross back as narrow as autodiff's
transpose of the caller's `astype` rounds them.

A decay per key channel (Kimi Delta Attention, arXiv:2510.26692: `S_t =
(I - b_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + b_t k_t v_t^T` with `g_t` in
R^{dk}) is the same rule with `y` of `(C, dk)`, the running sum per
channel. The shape of `g` selects it: `(N, B, H, C)` is the scalar decay
above, `(N, B, H, C, dk)` the decay per channel (scope `kda`). Then

    A_ij = b_i sum_d k_id k_jd e^{y_id - y_jd}           j < i
    W = T (b K e^{y}),   U = T (b V),   V' = U - W M
    O = (Q e^{y}) M + tril(sum_d q_id k_jd e^{y_id - y_jd}) V'
    M <- Diag(e^{y_C}) M + (K e^{y_C - y})^T V'

and a `y` broadcast over `dk` gives the lines above. The sum over `d` no
longer splits into a decay times one product, and `e^{y_i}` alone may
underflow along a chunk where `e^{-y_j}` alone overflows. So `A` and the
`Q K^T` term are taken in sub-chunks of `SUB` tokens: between two of
them through the first token `r` of the later one, `e^{y_i - y_r}
e^{y_r - y_j}`, both exponents <= 0, as one product; on the diagonal
blocks the exponent pairwise, summed over `d` in one fusion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deep_vision_tpu.obs.registry import get_registry
from deep_vision_tpu.ops.pallas.tril_inverse import (
    tril_inverse,
    tril_inverse_fits,
)

CHUNK = 64
SUB = 16  # a per-channel decay's sub-chunk: its exponents taken pairwise
# the name `T` carries to a recomputation's policy (`models/decoder.KEPT`)
INVERSE_NAME = "delta_rule_inverse"
_EXACT = lax.Precision.HIGHEST


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


def _unit_lower_inverse(a):
    """`(I + a)^-1` for `a` of `(N, B, H, C, C)`, strictly lower triangular,
    float32. Where the kernel takes the size (C a multiple of 8, C^2 of
    128) it is row-by-row substitution with the chunks in the lanes, the
    batch in front for its partitioning; a smaller chunk (a tiny test's) is
    one `solve_triangular` against the identity."""
    c = a.shape[-1]
    if tril_inverse_fits(c):
        return jnp.moveaxis(tril_inverse(jnp.moveaxis(a, 1, 0)), 0, 1)
    eye = jnp.eye(c, dtype=a.dtype)
    return jax.scipy.linalg.solve_triangular(
        a + eye, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)


@jax.custom_vjp
def _solve(a, rhs):
    """`(I + a)^-1 rhs`, float32: `a` (N, B, H, C, C) strictly lower
    triangular, `rhs` (N, B, H, C, d)."""
    return _solve_fwd(a, rhs)[0]


def _solve_fwd(a, rhs):
    get_registry().counter(
        "delta_rule_inverse_sites_total",
        "Inversions of a delta-rule layer's chunk triangles traced").inc()
    with jax.named_scope("delta_inverse"):
        t = checkpoint_name(_unit_lower_inverse(a), INVERSE_NAME)
    wu = jnp.einsum("...ij,...jd->...id", t, rhs, precision=_EXACT)
    return wu, (t, wu)


def _solve_bwd(residuals, d_wu):
    t, wu = residuals
    x = jnp.einsum("...ji,...jd->...id", t, d_wu, precision=_EXACT)
    d_a = -jnp.tril(jnp.einsum("...id,...jd->...ij", x, wu,
                               precision=_EXACT), -1)
    return d_a, x


_solve.defvjp(_solve_fwd, _solve_bwd)


def to_chunks(x, chunk: int):
    """Token-major `(B, T, H, ...)` -> chunk-major `(N, B, H, C, ...)`, in
    x's dtype: the crossing moves what it is given and widens nothing, and
    its result is a value of the program (the barrier: XLA may not fuse a
    consumer's widening back in front of the move)."""
    b, t, h = x.shape[:3]
    assert t % chunk == 0, f"{t} tokens do not divide into chunks of {chunk}"
    x = x.reshape(b, t // chunk, chunk, h, *x.shape[3:])
    return lax.optimization_barrier(jnp.moveaxis(x, (1, 3), (0, 2)))


def from_chunks(x):
    """`to_chunks`'s inverse: `(N, B, H, C, ...)` -> `(B, T, H, ...)`."""
    n, b, h, c = x.shape[:4]
    return jnp.moveaxis(x, (0, 2), (1, 3)).reshape(b, n * c, h, *x.shape[4:])


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK,
                     mm_dtype=jnp.float32):
    """q, k: (B, T, H, dk); v: (B, T, H, dv); g (log-decay, <= 0): (B, T,
    H), or (B, T, H, dk) a decay per key channel; beta: (B, T, H). -> o
    (B, T, H, dv) float32. T divides into chunks."""
    operands = (to_chunks(x, chunk) for x in (q, k, v, g, beta))
    return from_chunks(gated_delta_chunks(*operands, mm_dtype=mm_dtype))


def gated_delta_chunks(q, k, v, g, beta, *, mm_dtype=jnp.float32):
    """The rule on chunk-major operands (`to_chunks`'s): q, k (N, B, H, C,
    dk); v (N, B, H, C, dv); g (N, B, H, C), or (N, B, H, C, dk) a decay
    per key channel; beta (N, B, H, C); any float dtype. -> o (N, B, H, C,
    dv) float32."""
    if g.ndim == q.ndim:
        with jax.named_scope("kda"):
            return _chunked_per_channel(q, k, v, g, beta,
                                        jnp.dtype(mm_dtype))
    with jax.named_scope("gated_delta"):
        return _chunked(q, k, v, g, beta, jnp.dtype(mm_dtype))


def _chunked(q, k, v, g, beta, mm_dtype):
    mm = functools.partial(_mm, mm_dtype=mm_dtype)
    # the row math below is float32; the products round to `mm_dtype`
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    chunk, dk = q.shape[-2:]
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
    wu = _solve(a, rhs)
    w, u = wu[..., :dk], wu[..., dk:]
    qk = decay * mm("nbhid,nbhjd->nbhij", q, k)
    q_in = q * jnp.exp(y)[..., None]
    k_out = k * jnp.exp(y[..., -1:] - y)[..., None]
    carry_decay = jnp.exp(y[..., -1])[..., None, None]  # (N, B, H, 1, 1)
    return _scan(w, u, q_in, qk, k_out, carry_decay, v.shape[-1], mm)


def _scan(w, u, q_in, qk, k_out, carry_decay, dv, mm):
    """The three products a chunk that need the state, chunk after chunk:
    `M` (dk x dv) starts at zero. `carry_decay` is `e^{y_C}`, (N, B, H, 1,
    1) or (N, B, H, dk, 1)."""
    _, b, h, _, dk = w.shape

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
    return o


def _chunked_per_channel(q, k, v, g, beta, mm_dtype):
    """The rule with `g` of (N, B, H, C, dk): `A` and `Q K^T` by sub-chunks
    of `SUB` (module docstring), the rest as `_chunked`'s."""
    mm = functools.partial(_mm, mm_dtype=mm_dtype)
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    chunk, dk = q.shape[-2:]
    sub = SUB if chunk % SUB == 0 else chunk
    n_sub = chunk // sub
    y = jnp.cumsum(g, axis=-2)  # (N, B, H, C, dk)
    by_sub = lambda x: x.reshape(*x.shape[:-2], n_sub, sub, dk)
    ys, ks, qs = by_sub(y), by_sub(k), by_sub(q)
    # the diagonal blocks: e^{y_i - y_j} pairwise, masked before the
    # exponential (above the diagonal the exponent is >= 0), summed over d
    row = lax.broadcasted_iota(jnp.int32, (sub, sub, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (sub, sub, 1), 1)
    pair = jnp.exp(jnp.where(row >= col, ys[..., :, None, :]
                             - ys[..., None, :, :], -jnp.inf))
    a_diag = jnp.sum(jnp.where(row > col, ks[..., :, None, :]
                               * ks[..., None, :, :] * pair, 0.0), axis=-1)
    qk_diag = jnp.sum(qs[..., :, None, :] * ks[..., None, :, :] * pair,
                      axis=-1)  # (N, B, H, n_sub, sub, sub)
    # between sub-chunks: through the first token r of the later one, both
    # factors' exponents <= 0; the rows of sub-chunk I meet columns < r
    into_r = jnp.exp(ys - ys[..., :, :1, :])
    off_a = off_qk = [jnp.zeros(q.shape[:-2] + (sub, chunk))]
    for i in range(1, n_sub):
        r = i * sub
        right = k[..., :r, :] * jnp.exp(y[..., r:r + 1, :] - y[..., :r, :])
        side = lambda x: jnp.pad(
            mm("...sd,...jd->...sj", x[..., i, :, :] * into_r[..., i, :, :],
               right), [(0, 0)] * (x.ndim - 3) + [(0, 0), (0, chunk - r)])
        off_a, off_qk = off_a + [side(ks)], off_qk + [side(qs)]
    # the diagonal blocks in: (N, B, H, n_sub, sub, sub) -> (N, B, H, C, C)
    diag = jnp.eye(n_sub, dtype=jnp.float32)[:, None, :, None]
    whole = lambda off, d: (jnp.concatenate(off, axis=-2)
                            + (d[..., :, :, None, :] * diag).reshape(
                                *d.shape[:-3], chunk, chunk))
    a = beta[..., None] * whole(off_a, a_diag)
    qk = whole(off_qk, qk_diag)
    rhs = jnp.concatenate([beta[..., None] * jnp.exp(y) * k,
                           beta[..., None] * v], axis=-1)
    wu = _solve(a, rhs)
    w, u = wu[..., :dk], wu[..., dk:]
    q_in = q * jnp.exp(y)
    k_out = k * jnp.exp(y[..., -1:, :] - y)
    carry_decay = jnp.exp(y[..., -1, :])[..., None]  # (N, B, H, dk, 1)
    # what the scan's body only multiplies goes in rounded, as it would be
    w, q_in, qk, k_out = (x.astype(mm_dtype) for x in (w, q_in, qk, k_out))
    return _scan(w, u, q_in, qk, k_out, carry_decay, v.shape[-1], mm)
