"""`(I + A)^-1` for many small strictly-lower-triangular `A` at once: the
gated delta rule's `T` (`ops/gated_delta.py`), one Pallas call, `gdn_inverse`.

A chunk's triangle is C x C with C = 64, far under the MXU's 128 x 128,
and there are thousands of them (1920 a layer at 2 x 2048 tokens, 30
heads), so the kernel puts the *matrices* in the lanes: a grid step takes
128 of them as rows of `(128, C*C)`, transposes the tile once on the way
in and once on the way out, and in between every vector op works on 128
matrices at a time. The inverse is plain forward substitution, row by row:

    X_i = e_i - sum_{j < i} A_ij X_j

`X_j` a row of the inverse, C columns in the sublanes by 128 lanes. Every
intermediate is an entry of the inverse itself, all float32 on the VPU: no
MXU pass rounds anything (the Neumann product `(I - A)(I + A^2)...` would
be five products, and its `A^32` reaches 1e27 before it cancels where beta
is near 2 and keys are alike). A row sums over all C rows, those not yet
made standing at zero under `A`'s own zeros: one loop, one body. Taking
both triangles by shape (rows in blocks of 8, `X_j`'s sublane groups up to
its own) does a quarter of the multiplies in eight loop bodies and read
0.095 ms a call where this reads 0.212, of a 254 ms step; it took four
times as long to interpret on the CPU (PERF.md §6, PR 37).

XLA's `solve_triangular` inverts the same matrices through
`InvertDiagBlocksLowerTriangular` in 5.1 ms a layer on a v5e (there too).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deep_vision_tpu.core import backend as dvt_backend
from deep_vision_tpu.ops.pallas.partition import over_data_axis

_LANES = 128   # matrices a grid step
_GROUP = 8     # float32 sublanes a vreg: a row of X is whole vregs
# the tile in and out, double-buffered, and both scratches: 12 MB at C = 64
_VMEM_BYTES = 32 << 20


def tril_inverse_fits(c: int) -> bool:
    """Can the kernel take C x C matrices? Rows of whole vregs, and a
    matrix a whole number of lane tiles when it lies along a row."""
    return c % _GROUP == 0 and (c * c) % _LANES == 0 \
        and 6 * c * c * _LANES * 4 <= _VMEM_BYTES


def _inverse_kernel(a_ref, t_ref, a_scr, x_scr, *, c):
    # (128, C*C) -> [i, j, matrix]
    a_scr[...] = a_ref[...].T.reshape(c, c, _LANES)
    x_scr[...] = jnp.zeros_like(x_scr)
    col = lax.broadcasted_iota(jnp.int32, (c, _LANES), 0)

    def row(i, carry):
        x_i = (col == i).astype(jnp.float32)
        for j in range(c):  # a_ij is 0 from the diagonal on, X_j still 0
            x_i = x_i - a_scr[i, j:j + 1, :] * x_scr[j]
        x_scr[i] = x_i
        return carry

    lax.fori_loop(0, c, row, 0)
    t_ref[...] = x_scr[...].reshape(c * c, _LANES).T


def _inverse_rows(a, *, interpret):
    """a: (M, C*C) float32, M a multiple of 128."""
    m, cc = a.shape
    c = int(round(cc ** 0.5))
    tile = pl.BlockSpec((_LANES, cc), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_inverse_kernel, c=c),
        out_shape=jax.ShapeDtypeStruct((m, cc), jnp.float32),
        grid=(m // _LANES,),
        in_specs=[tile],
        out_specs=tile,
        scratch_shapes=[pltpu.VMEM((c, c, _LANES), jnp.float32),
                        pltpu.VMEM((c, c, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="gdn_inverse",
    )(a)


def tril_inverse(a, interpret: bool | None = None):
    """a: (B, ..., C, C) float32, strictly lower triangular (what lies on
    and above the diagonal must be zero) -> `(I + a)^-1`, the same shape.
    The leading dimension is the batch's: under a mesh each shard of the
    data axis inverts its own rows (`partition.py`)."""
    if interpret is None:
        interpret = dvt_backend.pallas_interpret()
    c = a.shape[-1]
    assert a.shape[-2] == c and tril_inverse_fits(c), a.shape

    def shard(a):
        rows = a.reshape(-1, c * c).astype(jnp.float32)
        m = rows.shape[0]
        rows = jnp.pad(rows, ((0, -m % _LANES), (0, 0)))
        return _inverse_rows(rows, interpret=bool(interpret))[:m].reshape(
            a.shape)

    return over_data_axis(shard, [True])(a)
