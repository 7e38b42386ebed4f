"""Fused per-channel scale-bias + activation (+ residual add) Pallas kernel.

The ResNet/VGG hot path applies BatchNorm, adds the skip tensor, and takes a
ReLU — three elementwise passes XLA usually fuses into the conv epilogue,
but the profiled flagship step still shows separate normalize/add/relu
fusions around the residual joins (the bf16 activation crosses HBM once per
pass). This kernel does the whole tail in ONE pass through VMEM:

    y = act(x * scale + bias [+ residual])

with `scale`/`bias` per channel (the folded BN apply: scale = gamma *
rsqrt(var + eps), bias = beta - mean * scale). The big tensor is read once
and written once; compute happens in f32 inside the kernel regardless of the
io dtype, so bf16 activations lose no precision to the folding.

Three implementations, one contract:
  - the Pallas TPU kernel (compiled on TPU, `interpret=True` elsewhere so
    CPU tier-1 tests exercise the real kernel code);
  - `reference_scale_bias_act`, the pure-lax twin used for parity tests and
    as the fallback when the channel layout can't tile (C not a power-of-two
    multiple/divisor of the 128-lane width);
  - the unfused module path in nn/layers.py, which stays byte-identical to
    the pre-kernel code when fusion is disabled.

Differentiable via custom_vjp: the forward is the Pallas kernel, the
backward is a handful of lax reductions (dx = g*mask*scale is elementwise;
dscale/dbias are per-channel sums XLA reduces well — the win is the fwd
pass, which runs once more in recompute-free form because y is saved).

Enable/disable: `fusion_enabled()` — on by default on TPU backends, off
elsewhere; `DVT_PALLAS_FUSED=1/0` forces either way (the config flag the
bench A/B and a suspicious-numerics triage reach for).
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
from deep_vision_tpu.ops.pallas.partition import data_shards, over_data_axis

_LANES = 128
# elements of ONE operand's block per grid step. The pipeline holds x, the
# residual and the output double-buffered (6 io blocks) and the kernel body
# computes in f32 whatever the io dtype, so the budget is counted in
# elements, not io bytes: 256 Ki is 1 MiB per f32 block or temporary, well
# inside the ~16 MiB scoped-VMEM default at every width and dtype (compiling
# for a v5e, 512 Ki-element bf16 blocks still fit and 1 Mi-element ones run
# out of VMEM). A fixed ROW count cannot do this: 256 rows is 512 Ki
# elements at C=2048 and 32 Ki (8x the grid steps) at C=64.
_BLOCK_ELEMS = 256 * 1024
_ROW_ALIGN = 32  # a sublane-tile multiple for every io dtype (f32 8, bf16 16)


def _block_rows(rows: int, lane_c: int) -> int:
    """Rows of the (R, lane_c) view per grid step: ~_BLOCK_ELEMS elements,
    a sublane-tile multiple, never more than the array."""
    r = _BLOCK_ELEMS // lane_c
    return min(max(_ROW_ALIGN, r - r % _ROW_ALIGN), rows)


def fusion_enabled() -> bool:
    """Should the fused Pallas path run? Pallas-compiled backends: yes
    unless DVT_PALLAS_FUSED=0; elsewhere: only if DVT_PALLAS_FUSED=1
    (tests force it; the default CPU path keeps the exact pre-kernel
    arithmetic so goldens never drift)."""
    forced = knobs.get_flag("DVT_PALLAS_FUSED")
    if forced is not None:
        return forced
    return dvt_backend.get_backend().pallas_compiled


def reference_scale_bias_act(x, scale, bias, residual=None,
                             act: Optional[str] = "relu"):
    """Pure-lax reference: same folded arithmetic as the kernel (f32
    compute, io dtype out). The parity target AND the non-tileable-layout
    fallback."""
    y = x.astype(jnp.float32) * scale.astype(jnp.float32) + bias.astype(
        jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    elif act is not None:
        raise ValueError(f"unsupported act {act!r}")
    return y.astype(x.dtype)


def _kernel(x_ref, a_ref, b_ref, o_ref, *, act: Optional[str],
            has_residual: bool, r_ref=None):
    x = x_ref[...].astype(jnp.float32)
    y = x * a_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if has_residual:
        y = y + r_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def _kernel_res(x_ref, r_ref, a_ref, b_ref, o_ref, *, act):
    _kernel(x_ref, a_ref, b_ref, o_ref, act=act, has_residual=True,
            r_ref=r_ref)


def _lane_layout(c: int):
    """(lane_c, repeat): reshape the flat (R*C,) stream to rows of
    `lane_c = lcm-ish` channels so per-channel params are constant per lane.

    C a multiple of 128 -> rows of C; C a divisor of 128 -> rows of 128
    covering 128//C samples each (params tiled across the lanes). Returns
    None when neither holds — caller falls back to the lax reference.
    """
    if c % _LANES == 0:
        return c, 1
    if _LANES % c == 0:
        return _LANES, _LANES // c
    return None


def _pallas_apply(x, scale, bias, residual, act: str | None,
                  interpret: bool):
    """The kernel over x, one call per data-axis shard of a multi-device
    program (partition.py; row-wise, so the split is exact)."""
    rows_fn = functools.partial(_pallas_rows, act=act, interpret=interpret)
    # a None residual is an empty pytree: its spec applies to no leaf
    return over_data_axis(rows_fn, (True, False, False, True))(
        x, scale, bias, residual)


def _pallas_rows(x, scale, bias, residual, *, act: str | None,
                 interpret: bool):
    """Run the kernel on the (R, lane_c) row view; assumes _lane_layout
    accepted C and total elements divide lane_c."""
    c = x.shape[-1]
    lane_c, repeat = _lane_layout(c)
    total = x.size
    rows = total // lane_c
    x2 = x.reshape(rows, lane_c)
    a2 = jnp.tile(scale.astype(jnp.float32), repeat).reshape(1, lane_c)
    b2 = jnp.tile(bias.astype(jnp.float32), repeat).reshape(1, lane_c)
    block_r = _block_rows(rows, lane_c)
    grid = (pl.cdiv(rows, block_r),)
    row_spec = pl.BlockSpec((block_r, lane_c), lambda i: (i, 0))
    par_spec = pl.BlockSpec((1, lane_c), lambda i: (0, 0))
    if residual is not None:
        out = pl.pallas_call(
            functools.partial(_kernel_res, act=act),
            out_shape=jax.ShapeDtypeStruct((rows, lane_c), x.dtype),
            grid=grid,
            in_specs=[row_spec, row_spec, par_spec, par_spec],
            out_specs=row_spec,
            interpret=interpret,
            name="bn_act_res_fwd",
        )(x2, residual.reshape(rows, lane_c), a2, b2)
    else:
        out = pl.pallas_call(
            functools.partial(_kernel, act=act, has_residual=False),
            out_shape=jax.ShapeDtypeStruct((rows, lane_c), x.dtype),
            grid=grid,
            in_specs=[row_spec, par_spec, par_spec],
            out_specs=row_spec,
            interpret=interpret,
            name="bn_act_fwd",
        )(x2, a2, b2)
    return out.reshape(x.shape)


# -- differentiable wrappers (one per arity so `residual=None` never ships a
# zeros tensor through HBM just to satisfy a uniform signature) -------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused3(x, scale, bias, act, interpret):
    return _pallas_apply(x, scale, bias, None, act, interpret)


def _fused3_fwd(x, scale, bias, act, interpret):
    y = _pallas_apply(x, scale, bias, None, act, interpret)
    return y, (x, scale, bias, y)


def _bwd_common(x, scale, y, g, act):
    gf = g.astype(jnp.float32)
    if act == "relu":
        gf = jnp.where(y > 0, gf, 0.0)
    axes = tuple(range(x.ndim - 1))
    dx = (gf * scale.astype(jnp.float32)).astype(x.dtype)
    dscale = jnp.sum(gf * x.astype(jnp.float32), axis=axes)
    dbias = jnp.sum(gf, axis=axes)
    return gf, dx, dscale.astype(scale.dtype), dbias


def _fused3_bwd(act, interpret, res, g):
    x, scale, bias, y = res
    _, dx, dscale, dbias = _bwd_common(x, scale, y, g, act)
    return dx, dscale, dbias.astype(bias.dtype)


_fused3.defvjp(_fused3_fwd, _fused3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused4(x, scale, bias, residual, act, interpret):
    return _pallas_apply(x, scale, bias, residual, act, interpret)


def _fused4_fwd(x, scale, bias, residual, act, interpret):
    y = _pallas_apply(x, scale, bias, residual, act, interpret)
    return y, (x, scale, bias, y)


def _fused4_bwd(act, interpret, res, g):
    x, scale, bias, y = res
    gf, dx, dscale, dbias = _bwd_common(x, scale, y, g, act)
    return dx, dscale, dbias.astype(bias.dtype), gf.astype(x.dtype)


_fused4.defvjp(_fused4_fwd, _fused4_bwd)


def fused_scale_bias_act(x, scale, bias, residual=None,
                         act: Optional[str] = "relu",
                         interpret: Optional[bool] = None):
    """y = act(x * scale + bias [+ residual]), one fused pass.

    x: (..., C); scale/bias: (C,) — the folded BN apply; residual: same
    shape as x or None. act: 'relu' or None. Differentiable in x, scale,
    bias, residual. Layouts whose C neither divides nor is divided by the
    128-lane width fall back to the lax reference (same math, same vjp
    structure via jax autodiff).
    """
    if act not in ("relu", None):
        raise ValueError(f"unsupported act {act!r}")
    if interpret is None:
        interpret = dvt_backend.pallas_interpret()
    c = x.shape[-1]
    if scale.shape != (c,) or bias.shape != (c,):
        raise ValueError(
            f"scale/bias must be ({c},), got {scale.shape}/{bias.shape}")
    lane = _lane_layout(c)
    n = data_shards()  # the kernel sees one data-axis shard of x
    if lane is None or x.shape[0] % n != 0 or (x.size // n) % lane[0] != 0:
        return reference_scale_bias_act(x, scale, bias, residual, act)
    if residual is not None:
        if residual.shape != x.shape:
            raise ValueError(
                f"residual shape {residual.shape} != x shape {x.shape}")
        return _fused4(x, scale, bias, residual, act, bool(interpret))
    return _fused3(x, scale, bias, act, bool(interpret))


def fused_bn_act(x, mean, var, gamma, beta, *, epsilon: float = 1e-5,
                 residual=None, act: Optional[str] = "relu",
                 interpret: Optional[bool] = None):
    """BN-apply + act (+ residual) from raw statistics: folds (mean, var,
    gamma, beta) to per-channel (scale, bias) — two (C,)-sized ops — then
    runs the fused kernel over the big tensor."""
    inv = gamma.astype(jnp.float32) * jax.lax.rsqrt(
        var.astype(jnp.float32) + epsilon)
    b = beta.astype(jnp.float32) - mean.astype(jnp.float32) * inv
    return fused_scale_bias_act(x, inv, b, residual=residual, act=act,
                                interpret=interpret)
