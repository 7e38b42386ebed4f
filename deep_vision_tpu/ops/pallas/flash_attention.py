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
Both take delta = rowsum(dO * O), computed outside (one fused XLA pass) from
the forward's float32 output, which is that pass's residual: the output
rounded to bf16 put dq 47-292% off where tokens are alike
(`_flash_backward_shard`; PERF.md §6, PR 34).

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
import math
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
    """Returns (out (B,T,H,D), lse (B*H, T, 128) f32 lane-broadcast, o32
    (B,T,H,D) float32).

    o32 is the output before it is rounded to the io dtype: the backward
    takes `delta` from it (`_flash_backward_shard` says why), so with
    need_lse the kernel writes float32 and `out` is XLA's cast of it, inside
    the transpose the output pays anyway. Kept in the model's layout, not
    the kernel's (B*H, T, D): 64 lanes of a head pad to 128 in HBM, and 12
    padded float32 residuals are 1.2 GB more peak at 8 x 4096 tokens where
    these are 0.5 GB less than the rounded ones were (PERF.md §6, PR 34).
    With need_lse=False (the inference-only primal) the kernel rounds, lse
    and its HBM write are elided entirely, and None is returned for both."""
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
    out_shape = [jax.ShapeDtypeStruct(
        (b * h, t, d), jnp.float32 if need_lse else q.dtype)]
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
    o = res[0].reshape(b, h, t, d).transpose(0, 2, 1, 3)
    if not need_lse:
        return o, None, None
    return o.astype(q.dtype), res[1], o


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


def _flash_backward(q, k, v, o32, lse, g, *, delta_shift=None, **kw):
    """`_flash_backward_shard` per data-axis shard (see _flash_forward); a
    None delta_shift is an empty pytree its spec does not touch."""
    return over_data_axis(functools.partial(_flash_backward_shard, **kw),
                          (True,) * 7)(q, k, v, o32, lse, g, delta_shift)


def _flash_backward_shard(q, k, v, o32, lse, g, delta_shift=None, *,
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
    # delta = rowsum(dO * O): one fused elementwise+reduce pass in XLA,
    # broadcast across the 128-lane residual layout (see _flash_forward).
    # O is the forward's float32 output, NOT the one rounded to the io
    # dtype: in ds = P (dP - delta) the part of dP common to a row cancels
    # only if delta is that row's sum_j P dP. An O rounded to bf16 leaves
    # its rounding error times the row's MEAN key in dq, and the true dq is
    # made of the keys' deviations from that mean: where tokens are alike
    # the error wins (dq 47-292% off, PERF.md §6, PR 34).
    # `delta_shift` (an lse cotangent, _flash_lse_bwd) subtracts in here.
    delta_row = jnp.sum(g.astype(jnp.float32) * o32, axis=-1).transpose(
        0, 2, 1).reshape(b * h, t, 1)
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


# -- a sequence that fits one block ------------------------------------------
#
# The streaming kernels above keep running (max, sum) state because a key
# range does not fit VMEM. At ViT's 196 tokens it does: the scores of one
# image and head are 196 x 196. `fused_attention` runs such a sequence with
# one grid step an image, all heads: no running state, no (T, T) tensor in
# HBM, and operands in the layouts the neighbouring projections write and
# read: in, the qkv projection's output (B, T, 3*H*Dh), heads picked out in
# the kernel; out, (B, T, H*Dh), which the out projection contracts over.
# The backward saves only the float32 log-sum-exp (B, H, T) and recomputes P;
# it takes delta as sum_k P dP from the float32 tiles it holds, and hands back
# the projection bias's gradient (d(qkv) summed over an image's tokens) from
# the same tiles, which is why `fused_attention` adds that bias itself.
#
# Shaped by the v5e's schedule (bundle dumps, PERF.md §6 PR 32):
# - Scores are held KEY-major, S^T (keys on sublanes, queries on lanes): a
#   reduction over keys is then elementwise over vregs (a lane reduction
#   keeps an XLU busy for cycles), and the row statistics are lane-dense
#   (1, T) vectors, which is the (B, H, T) layout of the saved log-sum-exp.
# - Heads sit `128 // Dh` to a 128-lane slab. A head is picked out by ROWS
#   of the slab's transpose (whole vregs: `_head_rows`), never by a lane
#   select on packed bf16; the matmul contracts over all 128 lanes, which
#   costs the MXU what a 64-deep contraction costs it.
# - Each phase goes MXU -> VMEM -> VPU -> VMEM -> MXU in one basic block:
#   loads issue three a bundle and stores one, and a score tile (52 vregs
#   a head) does not fit the register file, so it is stored once and
#   streamed back rather than spilled.
#
# FUSED_MAX_TOKENS, from the backward kernel's VMEM at T tokens (Tp = T
# rounded up to 128), H heads of Dh, D = H*Dh, 2-byte operands:
#   score scratch, per head: S^T and dP^T float32, P^T and dS^T bf16
#                                            12 * H * Tp * Tp bytes
#   zero-padded copies of qkv and dO         8 * D * Tp bytes
#   pipelined blocks, two buffers each: qkv, d(qkv) (3D) and dO (D)
#                                            28 * D * T bytes
# At T = 256, H = 16, Dh = 64 (ViT-L): 12.6 + 2.1 + 7.3 = 22 MB, inside the
# 32 MiB these kernels ask of the v5e's 128 MiB; at T = 384 the score
# scratch alone is 28 MB. So 256: ViT at 224 px (196 tokens) and every
# shorter sequence; 384 px (576 tokens) and up go dense or streaming.
FUSED_MAX_TOKENS = 256
_FUSED_VMEM_BYTES = 32 * 2 ** 20
_LOG2E = 1.4426950408889634
_NN = (((1,), (0,)), ((), ()))


def fused_attention_fits(t: int, num_heads: int, dim: int) -> bool:
    """Can `fused_attention` take T tokens of `num_heads` heads over `dim`
    features? The sequence within one block, whole heads to a 128-lane
    slab."""
    return (t <= FUSED_MAX_TOKENS and dim % num_heads == 0
            and dim % 128 == 0 and 128 % (dim // num_heads) == 0)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _stage(dst, src, t):
    """dst[:t] = src[0] and zero rows below: the padding lives in VMEM,
    never in HBM. The zeros go first, from an aligned row."""
    rows = dst.shape[0]
    lo = (t // 16) * 16
    if lo < rows:
        dst[lo:, :] = jnp.zeros((rows - lo, dst.shape[1]), dst.dtype)
    dst[0:t, :] = src[0]


def _head_rows(xt, i, dh):
    """A transposed slab (128, n) with every row but head i's zeroed."""
    parts = [jnp.zeros((i * dh, xt.shape[1]), xt.dtype),
             xt[i * dh:(i + 1) * dh],
             jnp.zeros((128 - (i + 1) * dh, xt.shape[1]), xt.dtype)]
    return jnp.concatenate([x for x in parts if x.shape[0]], axis=0)


def _take_head_lanes(new, old, i, dh):
    """`old` with head i's lanes of its 128-lane slab taken from `new`."""
    if old is None:
        return new
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return jnp.where((lane >= i * dh) & (lane < (i + 1) * dh), new, old)


def _per_image(*dims):
    """A block of one image's (*dims) out of (B, *dims), a grid step each."""
    return pl.BlockSpec((1, *dims), lambda i: (i, 0, 0))


def _mask_padded_keys(s, t):
    """S^T (key rows, queries): rows of padded keys to NEG_INF, before the
    max. Only the last vregs of rows hold any."""
    lo = (t // 8) * 8
    if t == s.shape[0]:
        return s
    real = lo + jax.lax.broadcasted_iota(
        jnp.int32, (s.shape[0] - lo, 1), 0) < t
    tail = jnp.where(real, s[lo:], NEG_INF)
    return tail if lo == 0 else jnp.concatenate([s[:lo], tail], axis=0)


def _fused_fwd_kernel(qkv_ref, o_ref, *rest, heads: int, dh: int, t: int,
                      scale: float, need_lse: bool):
    if need_lse:
        lse_ref, pad_scr, s_scr, e_scr, inv_scr = rest
    else:
        pad_scr, s_scr, e_scr, inv_scr = rest
    d = heads * dh
    g = 128 // dh
    tp, tr = pad_scr.shape[0], s_scr.shape[1]
    _stage(pad_scr, qkv_ref, t)
    if tr < tp:  # key rows the P V contraction reads and the softmax skips
        e_scr[:, tr:, :] = jnp.zeros((heads, tp - tr, tp), e_scr.dtype)
    # 1: S^T = K Q^T of every head, MXU -> VMEM
    for j in range(d // 128):
        qt = pad_scr[:, j * 128:(j + 1) * 128].T
        kp = pad_scr[0:tr, d + j * 128:d + (j + 1) * 128]
        for i in range(g):
            s = jax.lax.dot_general(kp, _head_rows(qt, i, dh), _NN,
                                    preferred_element_type=jnp.float32)
            s_scr[j * g + i] = _mask_padded_keys(s, t)
    # 2: float32 softmax over the keys, 128 queries of a head at a time
    for h in range(heads):
        for c in range(tp // 128):
            cols = slice(c * 128, (c + 1) * 128)
            s = s_scr[h, :, cols]
            m = jnp.max(s, axis=0, keepdims=True)
            e = jnp.exp2((s - m) * (scale * _LOG2E))
            l = jnp.sum(e, axis=0, keepdims=True)
            e_scr[h, 0:tr, cols] = e.astype(e_scr.dtype)
            inv_scr[h, 0:1, cols] = 1.0 / l
            if need_lse:
                inv_scr[h, 1:2, cols] = m * scale + jnp.log(l)
    if need_lse:
        for h in range(heads):
            lse_ref[0, h:h + 1, :] = inv_scr[h, 1:2, 0:t]
    # 3: O^T = V^T E^T / l, transposed into the out projection's layout
    for j in range(d // 128):
        vt = pad_scr[:, 2 * d + j * 128:2 * d + (j + 1) * 128].T
        o_t = []
        for i in range(g):
            h = j * g + i
            o_t.append(jax.lax.dot_general(
                vt[i * dh:(i + 1) * dh], e_scr[h], _NN,
                preferred_element_type=jnp.float32) * inv_scr[h, 0:1, :])
        o = jnp.concatenate(o_t, axis=0).T
        o_ref[0, :, j * 128:(j + 1) * 128] = o[0:t].astype(o_ref.dtype)


# jitted: a model traces the same kernel once a block, forward and backward,
# and the unrolled bodies take 0.4 s to trace and 0.4 s to lower a pair
# (9.5 s of every ViT-B/16 start); an inner jit traces and lowers each once.
@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret",
                                             "need_lse"))
def _fused_forward_shard(qkv, *, heads: int, scale: float, interpret: bool,
                         need_lse: bool):
    """-> (o (B, T, D), lse (B, H, T) float32 or None)."""
    b, t, d3 = qkv.shape
    d = d3 // 3
    tp, tr = _round_up(t, 128), _round_up(t, 16)
    out_shape = [jax.ShapeDtypeStruct((b, t, d), qkv.dtype)]
    out_specs = [_per_image(t, d)]
    if need_lse:
        out_shape.append(jax.ShapeDtypeStruct((b, heads, t), jnp.float32))
        out_specs.append(_per_image(heads, t))
    res = pl.pallas_call(
        functools.partial(_fused_fwd_kernel, heads=heads, dh=d // heads, t=t,
                          scale=scale, need_lse=need_lse),
        out_shape=out_shape,
        grid=(b,),
        in_specs=[_per_image(t, d3)],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((tp, d3), qkv.dtype),            # qkv, zero-padded
            pltpu.VMEM((heads, tr, tp), jnp.float32),   # S^T
            pltpu.VMEM((heads, tp, tp), qkv.dtype),     # exp(S^T - max)
            pltpu.VMEM((heads, 8, tp), jnp.float32),    # 1 / sum; lse
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_FUSED_VMEM_BYTES),
        interpret=interpret,
        name="attn_fused_fwd",
    )(qkv)
    return (res[0], res[1]) if need_lse else (res[0], None)


def _fused_bwd_kernel(qkv_ref, do_ref, lse_ref, dqkv_ref, dbias_ref, pad_scr,
                      do_scr, lse_scr, s_scr, dp_scr, p_scr, ds_scr, *,
                      heads: int, dh: int, t: int, scale: float):
    d = heads * dh
    g = 128 // dh
    tp, tr = pad_scr.shape[0], s_scr.shape[1]
    _stage(pad_scr, qkv_ref, t)
    _stage(do_scr, do_ref, t)
    # a padded query's row of P is exp2(0 - 0) = 1 and finite; its dO is 0
    lse_scr[...] = jnp.zeros(lse_scr.shape, jnp.float32)
    for h in range(heads):
        lse_scr[h, 0:1, 0:t] = lse_ref[0, h:h + 1, :] * _LOG2E
    if tr < tp:  # key rows the dQ contraction reads and phase 2 skips
        ds_scr[:, tr:, :] = jnp.zeros((heads, tp - tr, tp), ds_scr.dtype)
    # 1: S^T = K Q^T and dP^T = V dO^T of every head, MXU -> VMEM
    for j in range(d // 128):
        sl = slice(j * 128, (j + 1) * 128)
        kp = pad_scr[0:tr, d + j * 128:d + (j + 1) * 128]
        vp = pad_scr[0:tr, 2 * d + j * 128:2 * d + (j + 1) * 128]
        qt = pad_scr[:, sl].T
        dot = do_scr[:, sl].T
        for i in range(g):
            h = j * g + i
            s = jax.lax.dot_general(kp, _head_rows(qt, i, dh), _NN,
                                    preferred_element_type=jnp.float32)
            s_scr[h] = _mask_padded_keys(s, t)
            dp_scr[h] = jax.lax.dot_general(
                vp, _head_rows(dot, i, dh), _NN,
                preferred_element_type=jnp.float32)
    # 2: P^T from the saved log-sum-exp, and dS^T = P^T (dP^T - sum_k P dP)
    for h in range(heads):
        for c in range(tp // 128):
            cols = slice(c * 128, (c + 1) * 128)
            dp = dp_scr[h, :, cols]
            p = jnp.exp2(s_scr[h, :, cols] * (scale * _LOG2E)
                         - lse_scr[h, 0:1, cols])
            delta = jnp.sum(p * dp, axis=0, keepdims=True)
            p_scr[h, :, cols] = p.astype(p_scr.dtype)
            ds_scr[h, 0:tr, cols] = (p * (dp - delta)).astype(ds_scr.dtype)
    # 3: dV = P^T dO, dK = dS^T Q, dQ^T = K^T dS^T, into d(qkv)'s layout
    for j in range(d // 128):
        sl = slice(j * 128, (j + 1) * 128)
        qp = pad_scr[:, sl]
        dop = do_scr[:, sl]
        kt = pad_scr[:, d + j * 128:d + (j + 1) * 128].T
        dq_t, dk, dv = [], None, None
        for i in range(g):
            h = j * g + i
            dv = _take_head_lanes(jax.lax.dot_general(
                p_scr[h], dop, _NN, preferred_element_type=jnp.float32),
                dv, i, dh)
            dk = _take_head_lanes(jax.lax.dot_general(
                ds_scr[h, 0:tr], qp, _NN,
                preferred_element_type=jnp.float32), dk, i, dh)
            dq_t.append(jax.lax.dot_general(
                kt[i * dh:(i + 1) * dh], ds_scr[h], _NN,
                preferred_element_type=jnp.float32))
        dq = jnp.concatenate(dq_t, axis=0).T
        # The qkv bias's gradient is d(qkv) summed over tokens: taken here
        # from the float32 tiles, a pass over d(qkv) in HBM otherwise. Whole
        # tiles are summed, padded rows too, which hold exact zeros: a
        # padded query's dO is 0, so are its dP, delta and dS column, hence
        # its dQ row; a padded key's P and dS rows are exp2(-huge) = 0 or
        # zeroed above, hence its dK and dV rows.
        for part, tile, by in ((0, dq, scale), (1, dk, scale), (2, dv, None)):
            lanes = slice(part * d + j * 128, part * d + (j + 1) * 128)
            rows, total = tile[0:t], jnp.sum(tile, axis=0, keepdims=True)
            if by is not None:
                rows, total = rows * by, total * by
            dqkv_ref[0, :, lanes] = rows.astype(dqkv_ref.dtype)
            dbias_ref[0, :, lanes] = total


@functools.partial(jax.jit, static_argnames=("heads", "scale", "interpret"))
def _fused_backward_shard(qkv, lse, g, *, heads: int, scale: float,
                          interpret: bool):
    """-> (d(qkv) (B, T, 3D), its sum over tokens an image (B, 1, 3D)
    float32: per-image partials, so that the grid stays `parallel`)."""
    b, t, d3 = qkv.shape
    d = d3 // 3
    tp, tr = _round_up(t, 128), _round_up(t, 16)
    return pl.pallas_call(
        functools.partial(_fused_bwd_kernel, heads=heads, dh=d // heads, t=t,
                          scale=scale),
        out_shape=[jax.ShapeDtypeStruct((b, t, d3), qkv.dtype),
                   jax.ShapeDtypeStruct((b, 1, d3), jnp.float32)],
        grid=(b,),
        in_specs=[_per_image(t, d3), _per_image(t, d),
                  _per_image(heads, t)],
        out_specs=[_per_image(t, d3), _per_image(1, d3)],
        scratch_shapes=[
            pltpu.VMEM((tp, d3), qkv.dtype),            # qkv, zero-padded
            pltpu.VMEM((tp, d), qkv.dtype),             # dO, zero-padded
            pltpu.VMEM((heads, 8, tp), jnp.float32),    # lse * log2(e)
            pltpu.VMEM((heads, tr, tp), jnp.float32),   # S^T
            pltpu.VMEM((heads, tr, tp), jnp.float32),   # dP^T
            pltpu.VMEM((heads, tr, tp), qkv.dtype),     # P^T
            pltpu.VMEM((heads, tp, tp), qkv.dtype),     # dS^T
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_FUSED_VMEM_BYTES),
        interpret=interpret,
        name="attn_fused_bwd",
    )(qkv, g, lse)


def _biased(qkv, bias):
    """(B, T, *bias.shape) + bias -> the kernels' (B, T, 3D). The add is made
    in the projection's own shape, BEFORE the reshape: there XLA puts it in
    the projection matmul's epilogue; after it, it is a pass of its own
    over the activation (PERF.md §6, PR 34)."""
    b, t = qkv.shape[:2]
    return (qkv + bias.astype(qkv.dtype)).reshape(b, t, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused(qkv, bias, heads, scale, interpret):
    # primal (inference) path: no log-sum-exp is computed or written
    return over_data_axis(functools.partial(
        _fused_forward_shard, heads=heads, scale=scale, interpret=interpret,
        need_lse=False), (True,))(_biased(qkv, bias))[0]


def _fused_fwd(qkv, bias, heads, scale, interpret):
    x = _biased(qkv, bias)
    o, lse = over_data_axis(functools.partial(
        _fused_forward_shard, heads=heads, scale=scale, interpret=interpret,
        need_lse=True), (True,))(x)
    return o, (x, lse, bias)


def _fused_bwd(heads, scale, interpret, res, g):
    x, lse, bias = res
    dx, dbias = over_data_axis(functools.partial(
        _fused_backward_shard, heads=heads, scale=scale,
        interpret=interpret), (True, True, True))(x, lse, g)
    return (dx.reshape(*x.shape[:2], *bias.shape),
            jnp.sum(dbias, axis=(0, 1)).reshape(bias.shape).astype(bias.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_attention(qkv, num_heads: int, bias=None, *,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """Self-attention of a sequence that fits one block (see
    `fused_attention_fits`), fused, in the projections' layouts.

    qkv: (B, T, 3*H*Dh), the last dimension ordered [q | k | v][head][Dh]
    as `DenseGeneral((3, H, Dh))` writes it, or that projection's own
    (B, T, 3, H, Dh). bias: the projection's, of qkv's shape past (B, T);
    it is added here and not by the caller, because the backward kernel
    holds d(qkv) in VMEM and hands back the bias's gradient from there,
    where XLA would read d(qkv) from HBM once more to sum it. Returns
    (B, T, H*Dh). The arithmetic is the dense expression's: bf16 (the io
    dtype's) operands into the MXU with float32 accumulation, softmax in
    float32 on the float32 scores, probabilities in the io dtype for P V.
    Differentiable in qkv and bias; the backward recomputes P from the
    saved float32 log-sum-exp.
    """
    d3 = math.prod(qkv.shape[2:])
    if d3 % 3 or not fused_attention_fits(qkv.shape[1], num_heads, d3 // 3):
        raise ValueError(
            f"fused_attention takes qkv of (B, T <= {FUSED_MAX_TOKENS}, "
            f"3*H*Dh) with H*Dh a multiple of 128 and Dh dividing 128; got "
            f"{qkv.shape} with {num_heads} heads")
    if bias is None:
        bias = jnp.zeros(qkv.shape[2:], qkv.dtype)
    if bias.shape != qkv.shape[2:]:
        raise ValueError(f"fused_attention takes a bias of qkv's shape past "
                         f"(B, T), {qkv.shape[2:]}; got {bias.shape}")
    if scale is None:
        scale = (d3 // 3 // num_heads) ** -0.5
    if interpret is None:
        interpret = dvt_backend.pallas_interpret()
    return _fused(qkv, bias, int(num_heads), float(scale), bool(interpret))


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
    return _flash_forward(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, need_lse=False)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse, o32 = _flash_forward(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)
    return out, (q, k, v, o32, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, o32, lse = res
    return _flash_backward(q, k, v, o32, lse, g, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, need_lse=True)[:2]


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse, o32 = _flash_forward(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret, need_lse=True)
    return (out, lse), (q, k, v, o32, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    """Backward when BOTH outputs carry cotangents (the ring-attention merge
    differentiates through lse).

    d lse / d s_j = p_j, so the lse cotangent enters the score gradient as
    ds += p * g_lse — algebraically a shift of the delta term:
    ds = p (dp - (delta - g_lse)) scale. The kernels take delta as an input,
    so the shift needs no kernel change.
    """
    q, k, v, o32, lse = res
    g_out, g_lse = cts
    # cotangent of the 128-lane broadcast = sum over lanes
    g_lse_row = jnp.sum(g_lse.astype(jnp.float32), axis=-1)  # (BH, T)
    return _flash_backward(
        q, k, v, o32, lse, g_out, causal=causal, scale=scale,
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
