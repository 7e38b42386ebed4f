"""Ring attention: sequence/context parallelism over the mesh's data axis.

The reference is a CNN zoo with no attention or sequence dimension anywhere
(SURVEY.md §5 'long-context: N/A'), but this framework treats long-context as
first-class so attention workloads scale past one chip's HBM. Design follows
the blockwise-parallel / ring-attention recipe (Liu et al. 2023): shard the
sequence across devices, keep Q resident, rotate K/V blocks around the ring
with `ppermute` (one ICI hop per step, compute overlapping communication),
and merge per-block attention with a numerically-stable online softmax — the
same log-sum-exp accumulation flash attention uses, so the result is exact,
not approximate.

Layout contract: (batch, seq, heads, head_dim) with seq sharded over
`axis_name`. Collectives ride ICI inside a slice, DCN across hosts — no
NCCL/MPI analog needed (cf. SURVEY.md §2.5 comm-backend row).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deep_vision_tpu.parallel.mesh import DATA_AXIS


def _block_attend(q, k, v, scale, mask):
    """Scores + masked stable-softmax pieces for one (q_blk, kv_blk) pair.

    Returns (numerator (B,T,H,D), TRUE row max (B,H,T) — -inf for rows with
    no visible keys in this block — and row sumexp (B,H,T)). Carrying the
    true max (not a 0-clamped one) keeps the online-softmax merge exact even
    when every real score is far below zero.
    """
    # upcast K/V here (not in the ring carry: ppermute should move the
    # narrow input dtype, half the ICI bytes per hop for bf16)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * scale  # (B,H,Tq,Ts)
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # (B,H,Tq); -inf when fully masked
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])  # fully-masked rows: exp(-inf) = 0
    l = jnp.sum(p, axis=-1)  # (B,H,Tq)
    o = jnp.einsum("bhts,bshd->bthd", p, v)
    return o, m, l


NEG = -1e30  # "no visible keys" marker: finite, so exp/logaddexp never NaN


def _flash_block(q, k_blk, v_blk, scale, causal: bool):
    """One ring step through the fused Pallas kernel.

    Returns (normalized out (B,T,H,D) f32, lse (B,H,T) f32). Normalized-form
    merging (out, lse) is algebraically identical to the (numerator, m, l)
    online softmax: lse' = logaddexp(lse_a, lse_b), out' = sum of outs
    reweighted by exp(lse - lse').
    """
    from deep_vision_tpu.ops.pallas.flash_attention import (
        flash_attention_with_lse,
    )

    b, t, h, d = q.shape
    out, lse = flash_attention_with_lse(
        q, k_blk.astype(q.dtype), v_blk.astype(q.dtype),
        causal=causal, scale=scale,
        block_q=min(512, t), block_k=min(1024, k_blk.shape[1]),
    )
    lse = lse[:, :, 0].reshape(b, h, t)
    return out.astype(jnp.float32), lse


def _ring_attention_local_flash(q, k, v, *, axis_name: str, causal: bool,
                                scale: Optional[float]):
    """Flash-kernel per-shard body: O(T_loc) memory per ring step.

    The dense body materializes a (T_loc, T_loc) score block per step; with
    long local shards that is exactly the quadratic buffer ring attention
    exists to avoid. Here each step runs the fused flash kernel
    (ops/pallas/flash_attention.py) and merges normalized (out, lse) pairs.
    """
    out_dtype = q.dtype
    q = q.astype(jnp.float32)
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    t_loc = q.shape[1]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    perm = [(j, (j + 1) % n) for j in range(n)]
    b, _, h, d = q.shape

    def attend(src, k_blk, v_blk):
        if not causal:
            return _flash_block(q, k_blk, v_blk, scale, causal=False)
        zeros = (
            jnp.zeros((b, t_loc, h, d), jnp.float32),
            jnp.full((b, h, t_loc), NEG, jnp.float32),
        )
        # src == my: the aligned diagonal block (causal within);
        # src < my: entirely in the past (full); src > my: invisible
        return jax.lax.cond(
            src == my,
            lambda: _flash_block(q, k_blk, v_blk, scale, causal=True),
            lambda: jax.lax.cond(
                src < my,
                lambda: _flash_block(q, k_blk, v_blk, scale, causal=False),
                lambda: zeros,
            ),
        )

    def step(i, carry):
        out, lse, k_blk, v_blk = carry
        src = (my - i) % n
        out_i, lse_i = attend(src, k_blk, v_blk)
        lse_new = jnp.logaddexp(lse, lse_i)
        a = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
        b_w = jnp.exp(lse_i - lse_new).transpose(0, 2, 1)[..., None]
        out = out * a + out_i * b_w
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return out, lse_new, k_blk, v_blk

    out0 = jnp.zeros((b, t_loc, h, d), jnp.float32)
    lse0 = jnp.full((b, h, t_loc), NEG, jnp.float32)
    out0 = jax.lax.pcast(out0, (axis_name,), to="varying")
    lse0 = jax.lax.pcast(lse0, (axis_name,), to="varying")
    out, _, _, _ = jax.lax.fori_loop(0, n, step, (out0, lse0, k, v))
    return out.astype(out_dtype)


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          scale: Optional[float]):
    """Per-shard body (runs under shard_map). q/k/v: (B, T_loc, H, D)."""
    # accumulate in f32: the online-softmax state (m, l, o) sums exp() terms
    # over the whole ring, and bf16 accumulation loses real precision there
    # (the flash kernel upcasts to f32 VMEM scratch for the same reason).
    # K/V stay in the input dtype — they ride the ring and _block_attend
    # upcasts per block, so ppermute moves the narrow dtype.
    out_dtype = q.dtype
    q = q.astype(jnp.float32)
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    t_loc = q.shape[1]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    q_pos = my * t_loc + jnp.arange(t_loc)  # global positions of local queries

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o, m, l, k_blk, v_blk = carry
        src = (my - i) % n  # which shard this K/V block came from
        k_pos = src * t_loc + jnp.arange(t_loc)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # (Tq, Ts)
        else:
            mask = jnp.ones((t_loc, t_loc), bool)
        o_i, m_i, l_i = _block_attend(q, k_blk, v_blk, scale,
                                      mask[None, None, :, :])
        # online-softmax merge of (o, m, l) with the new block; maxes are the
        # TRUE row maxes (possibly -inf), so guard the -inf - -inf case
        m_new = jnp.maximum(m, m_i)
        m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        a = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new_safe), 0.0)
        b = jnp.where(jnp.isfinite(m_i), jnp.exp(m_i - m_new_safe), 0.0)
        o = o * a.transpose(0, 2, 1)[..., None] + o_i * b.transpose(0, 2, 1)[..., None]
        l = l * a + l_i * b
        # rotate K/V one hop around the ring (overlaps with next block's FLOPs)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, m_new, l, k_blk, v_blk

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((q.shape[0], q.shape[2], t_loc), -jnp.inf, q.dtype)
    l0 = jnp.zeros((q.shape[0], q.shape[2], t_loc), q.dtype)
    # constants start axis-unvarying under shard_map; mark them varying so the
    # loop carry type is stable across iterations
    m0 = jax.lax.pcast(m0, (axis_name,), to="varying")
    l0 = jax.lax.pcast(l0, (axis_name,), to="varying")
    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o0, m0, l0, k, v))
    denom = jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(out_dtype)


def _default_use_flash(t_loc: int) -> bool:
    """Flash-kernel routing for a ring shard of `t_loc` local tokens:
    TPU only, at or above the shared `flash_min_tokens()` floor
    (ops/pallas/flash_attention.py; DVT_FLASH_MIN_TOKENS overrides it
    per platform — the ring path must honor the same knob as ViT, not
    a hard-coded 1024), AND block-divisible: `_flash_block` runs the
    kernel at block_q=512 / block_k=1024, whose grid asserts
    `t % block == 0` — a lowered floor must route a 768-token shard to
    the dense body, not into the kernel's shape assert (the same
    `t % 1024 == 0` guard models/vit.py keeps)."""
    from deep_vision_tpu.core.backend import get_backend
    from deep_vision_tpu.ops.pallas.flash_attention import flash_min_tokens

    return (get_backend().pallas_compiled
            and t_loc >= flash_min_tokens()
            and t_loc % 1024 == 0)


def ring_attention(
    q, k, v, mesh: Mesh, *, causal: bool = False,
    axis_name: str = DATA_AXIS, scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
):
    """Exact attention over a sequence sharded across `axis_name`.

    q, k, v: (B, T, H, D) global shapes, T divisible by the axis size.
    Returns (B, T, H, D) with the same sharding.

    `use_flash` routes each ring step through the fused Pallas kernel
    (O(T_loc) memory instead of a dense (T_loc, T_loc) score block); default
    None auto-enables it on TPU for long local shards (the
    `flash_min_tokens()` floor, DVT_FLASH_MIN_TOKENS-overridable — the
    same knob that governs the ViT backbone's routing).
    """
    if use_flash is None:
        use_flash = _default_use_flash(q.shape[1] // mesh.shape[axis_name])
    spec = P(None, axis_name, None, None)
    body = _ring_attention_local_flash if use_flash else _ring_attention_local
    fn = functools.partial(
        body, axis_name=axis_name, causal=causal, scale=scale
    )
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call outputs carry no varying-mesh-axes annotation, so the
        # flash body opts out of the vma check (the dense body keeps it)
        check_vma=not use_flash,
    )
    return mapped(q, k, v)


def dense_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None):
    """Single-device reference implementation (golden for tests)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if causal:
        t, s_ = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(s_)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)
