"""The expert layer's passes over its buffer, over the live rows alone.

`parallel/moe.held_experts` sorts a layer's (token, choice) pairs into a
buffer of `R = T k` rows, the held experts' groups first: rows `[0, live)`
are live, `live = sum(group_sizes)`, and the rest are rows the grouped
products never write. At 8 held experts of 320 about 3% of the buffer is
live, yet every XLA op around the products has a static shape and so reads
and writes all `R` rows. These Pallas calls take the live count on the
device and visit the live rows alone:

- `moe_gather_rows` fills the buffer: row `p` is `scale[p] * src[idx[p]]`
  for `p < live`. Its grid is the buffer's live 128-row tiles, a number the
  device computes (`megablox.gmm` sizes its grid the same way); the rows of
  the last live tile past `live` are written as zeros, so that a grouped
  product reading the whole tile never reads garbage there; the tiles past
  it are neither read nor written.
- `moe_sum_rows` goes the other way, one tile of tokens a grid step: token
  `t` is `sum w[p] buf[p]` over the live rows `p` of its pairs, in float32;
  or, for the routing weights' gradient, `<buf[p], g[t]>` at the pair's
  choice. A held expert's rows for a tile of tokens are one contiguous
  range of the buffer (the pairs are sorted stably, so a group keeps token
  order): the caller hands the ranges, `[lo, hi)` a tile and held expert.
- `moe_gate` is the experts' `silu(gate) * up` over the live tiles, and its
  backward.

Rows move by DMA between the HBM arrays and VMEM. Mosaic slices an HBM
array only along whole (8, 128) tiles, so a row comes in its aligned block
of 8 and a sublane rotation puts it where it goes: eight times the bytes of
the row, a few tens of MB a call against the 0.3-0.5 GB of a pass over the
buffer. On a v5e at the Solar cell's shapes a call takes 0.02-0.31 ms where
XLA's passes took 0.37-2.5 ms (PERF.md section 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deep_vision_tpu.core import backend as dvt_backend

TILE = 128  # the buffer's row tile: the grouped products' (`moe.GMM_ROWS`)
_SUB = 8    # rows of an aligned HBM block, sublanes of a float32 vreg
_VMEM_BYTES = 48 << 20


def _lanes(d: int) -> int:
    """Columns a vector op takes at a time: eight vregs wide or fewer."""
    for c in (1024, 512, 256, 128):
        if d % c == 0:
            return c
    return d


def _each_lane(d: int, body):
    """`body(lane offset)` over `d` columns, a chunk a trip of a loop: the
    kernel's code stays one chunk long, so that it compiles in a fraction
    of a second however wide the rows are."""
    c = _lanes(d)

    def trip(i, carry):
        body(pl.multiple_of(i * c, c))
        return carry

    lax.fori_loop(0, d // c, trip, 0)


def token_tile(t: int) -> int:
    """Tokens a grid step of `moe_sum_rows`: the largest of 128, 64, ...
    8 that divides `t`. A row moves in its aligned block of 8, so `t` has
    to be a multiple of 8."""
    for tt in (128, 64, 32, 16, _SUB):
        if t % tt == 0:
            return tt
    raise ValueError(f"{t} tokens: the expert layer's row passes take a "
                     f"multiple of {_SUB}")


def _interpret(interpret):
    return dvt_backend.pallas_interpret() if interpret is None else interpret


def _live_tiles(live):
    return (live + TILE - 1) // TILE


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_BYTES)


def _block(src_hbm, row, slot, sem):
    """The DMA of the aligned block of `_SUB` rows of `src_hbm` that holds
    `row`, into `slot`."""
    first = pl.multiple_of(row // _SUB * _SUB, _SUB)
    return pltpu.make_async_copy(src_hbm.at[pl.ds(first, _SUB)], slot, sem)


def _moved(block, row, to):
    """`block` (8, c) float32 rotated so that its row `row % 8` stands at
    sublane `to % 8`."""
    return pltpu.roll(block, (to - row) % _SUB, 0)


# -- the buffer from token-side rows -----------------------------------------

def _gather_kernel(live_ref, idx_ref, *refs, scaled: bool):
    if scaled:
        scale_ref, src_hbm, out_ref, rows, slots, sem = refs
    else:
        src_hbm, out_ref, rows, slots, sem = refs
    d = out_ref.shape[-1]
    c = _lanes(d)
    base = pl.program_id(0) * TILE
    n = jnp.minimum(live_ref[0] - base, TILE)

    def start(j, carry):
        _block(src_hbm, idx_ref[base + j], slots.at[j], sem).start()
        return carry

    def wait(j, carry):
        _block(src_hbm, 0, slots.at[0], sem).wait()
        return carry

    lax.fori_loop(0, n, start, 0)
    lax.fori_loop(0, n, wait, 0)
    sub = lax.broadcasted_iota(jnp.int32, (_SUB, c), 0)

    def row(j, carry):
        top = pl.multiple_of(j // _SUB * _SUB, _SUB)
        q = idx_ref[base + j]

        def chunk(lane):
            got = _moved(slots[j, :, pl.ds(lane, c)].astype(jnp.float32), q,
                         j)
            if scaled:
                got = got * scale_ref[base + j]
            here = rows.at[pl.ds(top, _SUB), pl.ds(lane, c)]
            here[...] = jnp.where(sub == j % _SUB, got, here[...])

        _each_lane(d, chunk)
        return carry

    lax.fori_loop(0, n, row, 0)
    group = lax.broadcasted_iota(jnp.int32, (_SUB, d), 0)

    def store(g, carry):
        top = pl.multiple_of(g * _SUB, _SUB)
        # rows of the last live tile past `live` are zeros
        out_ref[pl.ds(top, _SUB), :] = jnp.where(
            group + top < n, rows[pl.ds(top, _SUB), :], 0.0).astype(
                out_ref.dtype)
        return carry

    lax.fori_loop(0, TILE // _SUB, store, 0)


def gather_rows(src, idx, live, scale=None, *, interpret=None):
    """src (S, D); idx (R,) int32, R a multiple of 128; live: int32 scalar;
    scale (R,) float32 or None -> (R, D) in src's dtype: row `p < live` is
    `src[idx[p]]` (times `scale[p]` in float32, then rounded), the rest of
    its 128-row tile zeros, the tiles past it left unwritten."""
    return _gather_call(src, idx, live, scale, interpret=_interpret(interpret))


# Each call is a jit of its own: a step that makes the same call at every
# layer, forward, recomputed and backward, traces and lowers each kernel
# once, not at every site (set-up time the benchmark counts).
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_call(src, idx, live, scale, *, interpret):
    s, d = src.shape
    r = idx.shape[0]
    assert r % TILE == 0 and s % _SUB == 0, (src.shape, idx.shape)
    prefetch = [jnp.reshape(live, (1,)).astype(jnp.int32), idx]
    if scale is not None:
        prefetch.append(scale.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_gather_kernel, scaled=scale is not None),
        out_shape=jax.ShapeDtypeStruct((r, d), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(_live_tiles(prefetch[0][0]),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TILE, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((TILE, d), jnp.float32),
                            pltpu.VMEM((TILE, _SUB, d), src.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_gather_rows",
    )(*prefetch, src)


# -- token-side rows from the buffer -----------------------------------------

def _sum_kernel(order_ref, lo_ref, hi_ref, *refs, mode: str, k: int,
                n: int):
    if mode == "weighted":
        w_ref, buf_hbm, out_ref, acc, slots, sem = refs
    elif mode == "plain":
        buf_hbm, out_ref, acc, slots, sem = refs
    else:  # "dots"
        buf_hbm, g_ref, out_ref, acc, slots, sem = refs
    d = buf_hbm.shape[-1]
    c = _lanes(d)
    tt = out_ref.shape[0]
    tile = pl.program_id(0)

    def blocks(e):
        """Held expert `e`'s rows `[lo, hi)` for this tile, the first
        aligned block holding them and how many blocks."""
        lo, hi = lo_ref[tile * n + e], hi_ref[tile * n + e]
        first = lo // _SUB
        return lo, hi, first, jnp.where(hi > lo,
                                        (hi - 1) // _SUB - first + 1, 0)

    def each_block(body):
        """`body(slot, j, lo, hi, first)` over the blocks of every held
        expert, in the experts' order: the tile's blocks take consecutive
        slots."""
        def expert(e, base):
            lo, hi, first, count = blocks(e)

            def block(j, carry):
                body(base + j, j, lo, hi, first)
                return carry

            lax.fori_loop(0, count, block, 0)
            return base + count

        lax.fori_loop(0, n, expert, 0)

    def dma(slot, j, first):
        return _block(buf_hbm, (first + j) * _SUB, slots.at[slot], sem)

    each_block(lambda slot, j, lo, hi, first: dma(slot, j, first).start())
    acc[...] = jnp.zeros_like(acc)
    each_block(lambda slot, j, lo, hi, first: dma(slot, j, first).wait())
    sub = lax.broadcasted_iota(jnp.int32, (_SUB, c), 0)
    choice = lax.broadcasted_iota(jnp.int32, (_SUB, k), 1)
    at = lax.broadcasted_iota(jnp.int32, (_SUB, k), 0)

    def pair(p, slot):
        """Buffer row `p`, in `slots[slot]`, into its token's row."""
        m = order_ref[p]
        r = m // k - tile * tt
        top = pl.multiple_of(r // _SUB * _SUB, _SUB)

        def row(lane):
            return _moved(slots[slot, :, pl.ds(lane, c)].astype(jnp.float32),
                          p, r)

        if mode == "dots":
            def product(i, total):
                lane = pl.multiple_of(i * c, c)
                return total + row(lane) * g_ref[pl.ds(top, _SUB),
                                                 pl.ds(lane, c)]

            total = lax.fori_loop(0, d // c, product,
                                  jnp.zeros((_SUB, c), jnp.float32))
            acc[pl.ds(top, _SUB), :] = jnp.where(
                (at == r % _SUB) & (choice == m % k),
                jnp.sum(total, axis=1, keepdims=True),
                acc[pl.ds(top, _SUB), :])
            return

        def add(lane):
            got = row(lane)
            if mode == "weighted":
                got = got * w_ref[p]
            acc[pl.ds(top, _SUB), pl.ds(lane, c)] += jnp.where(
                sub == r % _SUB, got, 0.0)

        _each_lane(d, add)

    def rows_of_block(slot, j, lo, hi, first):
        top = (first + j) * _SUB

        def one(p, carry):
            pair(p, slot)
            return carry

        lax.fori_loop(jnp.maximum(top, lo), jnp.minimum(top + _SUB, hi), one,
                      0)

    each_block(rows_of_block)  # a token's rows in the held experts' order
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "tokens", "k", "dtype",
                                             "interpret"))
def _sum_call(mode, buf, order, lo, hi, extra, *, tokens, k, dtype,
              interpret):
    r, d = buf.shape
    tiles, n = lo.shape
    tt = tokens // tiles
    assert tiles * tt == tokens and tt % _SUB == 0 and r % _SUB == 0, (
        buf.shape, lo.shape)
    prefetch = [order, lo.reshape(-1), hi.reshape(-1)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    args = [buf]
    width = k if mode == "dots" else d
    if mode == "weighted":
        prefetch.append(extra.astype(jnp.float32))
    elif mode == "dots":
        in_specs.append(pl.BlockSpec((tt, d), lambda i, *_: (i, 0)))
        args.append(extra.astype(jnp.float32))
    # a tile's held rows, at most `tt k` (a held expert may hold several
    # pairs of a token), lie in fewer than an eighth of them plus two
    # aligned blocks an expert
    blocks = tt * k // _SUB + 2 * n
    return pl.pallas_call(
        functools.partial(_sum_kernel, mode=mode, k=k, n=n),
        out_shape=jax.ShapeDtypeStruct((tokens, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tiles,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tt, width), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tt, width), jnp.float32),
                            pltpu.VMEM((blocks, _SUB, d), buf.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_sum_rows",
    )(*prefetch, *args)


def sum_rows(buf, order, lo, hi, weights=None, *, tokens: int, k: int,
             dtype=jnp.float32, interpret=None):
    """buf (R, D): the buffer; order (R,) int32: the pair `t k + j` at each
    row; lo, hi (tiles, n) int32: the live rows `[lo, hi)` of each held
    expert's pairs whose tokens lie in each tile of `tokens // tiles`;
    weights (R,) float32 by buffer row, or None for ones -> (tokens, D) in
    `dtype`: token `t` is the float32 sum of `weights[p] buf[p]` over its
    live rows, in the order of the held experts; zeros where it has none."""
    return _sum_call("weighted" if weights is not None else "plain", buf,
                     order, lo, hi, weights, tokens=tokens, k=k,
                     dtype=dtype, interpret=_interpret(interpret))


def dot_rows(buf, order, lo, hi, g, *, k: int, interpret=None):
    """As `sum_rows`, g (T, D) -> (T, k) float32: `<buf[p], g[t]>` in
    float32 at `[t, j]` for each live row `p` of pair `t k + j`, zeros at
    the pairs with no live row."""
    return _sum_call("dots", buf, order, lo, hi, g, tokens=g.shape[0],
                     k=k, dtype=jnp.float32, interpret=_interpret(interpret))


# -- the experts' gate over the live tiles -----------------------------------

def _gate_kernel(live_ref, gu_ref, *refs, backward: bool):
    if backward:
        dh_ref, out_ref = refs
    else:
        (out_ref,) = refs
    f = gu_ref.shape[-1] // 2
    c = _lanes(f)
    rows = lax.broadcasted_iota(jnp.int32, (TILE, c), 0) \
        + pl.program_id(0) * TILE
    live = rows < live_ref[0]

    def chunk(lane):
        gate = gu_ref[:, pl.ds(lane, c)].astype(jnp.float32)
        up = gu_ref[:, pl.ds(f + lane, c)].astype(jnp.float32)
        s = jax.nn.sigmoid(gate)
        if not backward:
            out_ref[:, pl.ds(lane, c)] = jnp.where(
                live, gate * s * up, 0.0).astype(out_ref.dtype)
            return
        dh = dh_ref[:, pl.ds(lane, c)].astype(jnp.float32)
        d_gate = dh * up * (s + gate * s * (1.0 - s))
        out_ref[:, pl.ds(lane, c)] = jnp.where(
            live, d_gate, 0.0).astype(out_ref.dtype)
        out_ref[:, pl.ds(f + lane, c)] = jnp.where(
            live, dh * gate * s, 0.0).astype(out_ref.dtype)

    _each_lane(f, chunk)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gate_call(gu, live, dh=None, *, interpret):
    r, two_f = gu.shape
    f = two_f // 2
    live = jnp.reshape(live, (1,)).astype(jnp.int32)
    tile = lambda width: pl.BlockSpec((TILE, width), lambda i, *_: (i, 0))
    args = [gu] if dh is None else [gu, dh]
    width = f if dh is None else two_f
    return pl.pallas_call(
        functools.partial(_gate_kernel, backward=dh is not None),
        out_shape=jax.ShapeDtypeStruct((r, width), gu.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(_live_tiles(live[0]),),
            in_specs=[tile(two_f)] + ([tile(f)] if dh is not None else []),
            out_specs=tile(width)),
        compiler_params=_params(),
        interpret=interpret,
        name="moe_gate",
    )(live, *args)


def gate_rows(gu, live, *, interpret=None):
    """gu (R, 2F): the gate's and the up product's columns side by side;
    live: int32 scalar -> (R, F) `silu(gate) * up` in gu's dtype, computed
    in float32, over the live tiles: rows past `live` of the last live
    tile zeros, the tiles past it unwritten. The backward visits the same
    tiles."""
    return _gate(gu, live, _interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gate(gu, live, interpret):
    return _gate_call(gu, live, interpret=interpret)


def _gate_fwd(gu, live, interpret):
    return _gate_call(gu, live, interpret=interpret), (gu, live)


def _gate_bwd(interpret, res, dh):
    gu, live = res
    return _gate_call(gu, live, dh.astype(gu.dtype),
                      interpret=interpret), None


_gate.defvjp(_gate_fwd, _gate_bwd)
