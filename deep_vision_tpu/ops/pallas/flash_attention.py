"""Fused blockwise (flash) attention as Pallas TPU kernels, fwd + bwd.

Why a kernel: naive attention materializes the (T, T) score matrix in HBM —
at T=16k that is 1GB per head in fp32, and the op is HBM-bandwidth-bound.
The fused kernels stream K/V blocks through VMEM, keep the online-softmax
running (max, sumexp, accumulator) state in VMEM scratch across grid steps,
and never write scores to HBM: O(T) memory, MXU-bound.

This is the single-chip sibling of `parallel/ring_attention.py` (same online
softmax); ring attention distributes the sequence across chips, this kernel
fuses the per-chip block loop. The reference framework has no attention op
anywhere (SURVEY.md §5) — this is net-new capability for long-context
workloads.

Backward pass (FlashAttention-2 recipe): the forward additionally writes the
per-row logsumexp L = m + log(l); the backward recomputes score blocks from
(q, k, L) in VMEM — still O(T) HBM — in two kernels that match the TPU's
sequential grid:
  - dq kernel: grid (BH, q_blocks, k_blocks), dq accumulates in scratch
    across the inner k loop;
  - dkv kernel: grid (BH, k_blocks, q_blocks), dk/dv accumulate across the
    inner q loop.
Both use delta = rowsum(dO * O), computed outside (one fused XLA pass).

Grid layout note: TPU executes the grid sequentially (last dim fastest), so
VMEM scratch legally carries accumulators across the innermost dimension —
init at inner==0, write out at inner==last.

Measured on one v5e chip (B4 T4096 H8 D64, causal): fwd 7.7 ms vs 14.1 ms
for XLA's fused dense attention (1.8x, fp32 io); fwd+bwd 17.2 ms vs 41.0 ms
(2.4x, bf16 io), and fwd+bwd at T=16384 runs in 117 ms where dense would
materialize ~4GB of score gradients. Defaults (block_q=512, block_k=1024)
come from that sweep.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deep_vision_tpu.core import backend as dvt_backend
from deep_vision_tpu.core import knobs
from deep_vision_tpu.ops.pallas.partition import over_data_axis

NEG_INF = -1e30

# below this many tokens the dense einsum beats the flash kernel (and the
# kernel's 128-lane tiling would need padding anyway). The floor is a
# per-platform tuning knob — the crossover sits elsewhere on a v5e than
# on a v4 — so DVT_FLASH_MIN_TOKENS overrides it at trace time, the
# DVT_NMS_IMPL convention (a routing knob must never no-op on a typo).
# Lives with the kernel so BOTH consumers — the ViT backbone
# (models/vit.py) and ring attention's per-shard compute
# (parallel/ring_attention.py) — route through the same floor.
FLASH_MIN_TOKENS = 1024


def flash_min_tokens() -> int:
    """The routing floor, env-overridable; a mistyped value raises
    instead of silently running the default (knobs.get_int)."""
    env = knobs.get_int("DVT_FLASH_MIN_TOKENS", default=None)
    return FLASH_MIN_TOKENS if env is None else env


def _causal_mask(s, qi, ki, block_q, block_k):
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _block_visible(causal: bool, qi, ki, block_q: int, block_k: int):
    """False only for blocks strictly above the causal diagonal."""
    return jnp.logical_or(
        jnp.logical_not(causal), ki * block_k <= qi * block_q + block_q - 1
    )


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  need_lse: bool):
    if need_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # with causality, blocks strictly above the diagonal contribute nothing
    visible = _block_visible(causal, qi, ki, block_q, block_k)

    @pl.when(visible)
    def _attend():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)

        m_prev = m_scr[:, :1]  # (bq, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk); rows w/o keys: exp(NEG_INF)≈0
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # finalize on the last k step (beyond-diagonal steps were masked no-ops)
    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-20)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        if need_lse:
            lse = m_scr[:, :1] + jnp.log(l)  # (bq, 1)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _flash_forward(q, k, v, **kw):
    """`_flash_forward_shard` per data-axis shard of a multi-device program
    (partition.py): attention is per (batch, head), so the split is exact,
    and the b-major (B*H, ...) lse rows concatenate back in order."""
    return over_data_axis(functools.partial(_flash_forward_shard, **kw),
                          (True, True, True))(q, k, v)


def _flash_forward_shard(q, k, v, *, causal: bool, scale: float, block_q: int,
                         block_k: int, interpret: bool, need_lse: bool = True):
    """Returns (out (B,T,H,D), lse (B*H, T, 128) f32 lane-broadcast).

    With need_lse=False (the inference-only primal) the lse output and its
    HBM write are elided entirely and None is returned for it."""
    b, t, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    assert t % block_q == 0 and tk % block_k == 0, (
        f"seq lens ({t}, {tk}) must divide blocks ({block_q}, {block_k})"
    )
    # (B, T, H, D) -> (B*H, T, D): each grid row owns one (batch, head) pair
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, need_lse=need_lse,
    )
    out_shape = [jax.ShapeDtypeStruct((b * h, t, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))]
    if need_lse:
        # lse broadcast across a 128-lane minor dim: Mosaic requires
        # (8, 128)-aligned blocks, so per-row residuals ride 128 lanes
        # (the layout the official TPU flash kernels use as well)
        out_shape.append(jax.ShapeDtypeStruct((b * h, t, 128), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0))
        )
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(b * h, t // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sumexp
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    out, lse = res if need_lse else (res[0], None)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3), lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale: float, causal: bool, block_q: int,
               block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    visible = _block_visible(causal, qi, ki, block_q, block_k)

    @pl.when(visible)
    def _accum():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]      # (bq, 1)
        delta = delta_ref[0][:, :1]  # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse)  # (bq, bk); masked entries -> 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _write():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, block_q: int, block_k: int):
    ki = pl.program_id(1)  # note: k is the OUTER loop here
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    visible = _block_visible(causal, qi, ki, block_q, block_k)

    @pl.when(visible)
    def _accum():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse)  # (bq, bk)
        # dV += P^T dO
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale  # (bq, bk)
        # dK += dS^T Q
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    # the last q block is on/below the diagonal for every k block, so the
    # write step always executes
    @pl.when(qi == nq - 1)
    def _write():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, delta_shift=None, **kw):
    """`_flash_backward_shard` per data-axis shard (see _flash_forward); a
    None delta_shift is an empty pytree its spec does not touch."""
    return over_data_axis(functools.partial(_flash_backward_shard, **kw),
                          (True,) * 7)(q, k, v, out, lse, g, delta_shift)


def _flash_backward_shard(q, k, v, out, lse, g, delta_shift=None, *,
                          causal: bool, scale: float, block_q: int,
                          block_k: int, interpret: bool):
    b, t, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    dor = g.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    outr = out.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    # delta = rowsum(dO * O): one fused elementwise+reduce pass in XLA,
    # broadcast across the 128-lane residual layout (see _flash_forward).
    # `delta_shift` (an lse cotangent, _flash_lse_bwd) subtracts in here.
    delta_row = jnp.sum(
        dor.astype(jnp.float32) * outr.astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    if delta_shift is not None:
        delta_row = delta_row - delta_shift[..., None]
    delta = jnp.broadcast_to(delta_row, (b * h, t, 128))

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0))
    row_spec = pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        grid=(b * h, t // block_q, tk // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, dor, lse, delta)

    # swapped grid: k blocks outer, q blocks inner
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 128),
                             lambda bh, ki, qi: (bh, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
        ],
        grid=(b * h, tk // block_k, t // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[k_spec2, k_spec2],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, dor, lse, delta)

    unshape = lambda x, tt: x.reshape(b, h, tt, d).transpose(0, 2, 1, 3)
    return unshape(dq, t), unshape(dk, tk), unshape(dv, tk)


def _dense_reference(q, k, v, causal, scale):
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t, s_ = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(s_)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    # primal (inference) path: skip computing/writing the lse residual
    out, _ = _flash_forward(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret, need_lse=False)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, need_lse=True)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret, need_lse=True)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    """Backward when BOTH outputs carry cotangents (the ring-attention merge
    differentiates through lse).

    d lse / d s_j = p_j, so the lse cotangent enters the score gradient as
    ds += p * g_lse — algebraically a shift of the delta term:
    ds = p (dp - (delta - g_lse)) scale. The kernels take delta as an input,
    so the shift needs no kernel change.
    """
    q, k, v, out, lse = res
    g_out, g_lse = cts
    b, t, h, d = q.shape
    # cotangent of the 128-lane broadcast = sum over lanes
    g_lse_row = jnp.sum(g_lse.astype(jnp.float32), axis=-1)  # (BH, T)
    return _flash_backward(
        q, k, v, out, lse, g_out, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        delta_shift=g_lse_row,
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(
    q, k, v, *, causal: bool = False, scale: Optional[float] = None,
    block_q: int = 512, block_k: int = 1024,
    interpret: Optional[bool] = None,
):
    """flash_attention that also returns the per-row logsumexp.

    lse comes back as (B*H, T, 128) f32 with the value broadcast across the
    lane dim (take `[:, :, 0]`). Differentiable in both outputs — the
    building block for blockwise merges (parallel/ring_attention.py).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = dvt_backend.pallas_interpret()
    return _flash_lse(q, k, v, causal, float(scale), int(block_q),
                      int(block_k), bool(interpret))


def flash_attention(
    q, k, v, *, causal: bool = False, scale: Optional[float] = None,
    block_q: int = 512, block_k: int = 1024,
    interpret: Optional[bool] = None,
):
    """Fused attention. q: (B, Tq, H, D); k, v: (B, Tk, H, D).

    Differentiable: the backward runs the Pallas dq / dkv kernels above
    (O(T) memory), so the op is safe for long-sequence *training*, not just
    inference.

    `interpret=None` auto-selects: compiled on TPU, interpreter elsewhere
    (the CPU test path; `conftest.py` meshes run it interpreted).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = dvt_backend.pallas_interpret()
    return _flash(q, k, v, causal, float(scale), int(block_q), int(block_k),
                  bool(interpret))
