"""Greedy NMS as a Pallas TPU kernel: the whole selection loop in VMEM.

The lax implementation in ops/nms.py dispatches a `fori_loop` whose every
iteration does an argmax over HBM-resident scores plus one IoU row — at
YOLO scale (N=10647 candidates, 100 selections) that is 100 sequential
reduce+broadcast rounds the XLA scheduler cannot overlap, and the decode
shows up as a serial tail on the inference profile. This kernel pins the
candidate set (4 coordinate rows + scores, ~250 KB at YOLO scale) in VMEM
for the whole greedy loop: one grid step per image, zero HBM round-trips
per selection.

Same algorithm and arithmetic as ops/nms.py `_nms_single` (argmax ->
suppress-by-IoU with the `broadcast_iou` union/eps convention), so the two
implementations are interchangeable — the parity tests assert exact
agreement on indices and scores. `interpret=True` runs the same kernel on
CPU (the tier-1 path); `ops/nms.py non_maximum_suppression(impl=...)` picks
lax vs pallas (env DVT_NMS_IMPL overrides, TPU defaults to pallas).

Layout: coordinates travel as four (B, 1, N) rows (lane-major over
candidates) rather than (B, N, 4) — a 4-wide lane dim would waste 124 of the
VPU's 128 lanes on every op. The unit middle dim is what lets one image be
one block: the TPU lowering wants a block's last two dims to be (8, 128)
multiples or the array's own, and a (1, N) block of a (B, N) array is
neither once B > 1. N and max_detections are padded to lane multiples in
the wrapper; padded candidates carry score -1 so the `best > 0` selection
gate never picks them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deep_vision_tpu.core import backend as dvt_backend
from deep_vision_tpu.ops.pallas.partition import over_data_axis

_LANES = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _nms_kernel(x1_ref, y1_ref, x2_ref, y2_ref, s_ref,
                out_s_ref, out_i_ref, *, max_detections: int,
                iou_threshold: float):
    x1 = x1_ref[...]  # (1, Np)
    y1 = y1_ref[...]
    x2 = x2_ref[...]
    y2 = y2_ref[...]
    live = s_ref[...]
    np_ = live.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, live.shape, 1)
    # broadcast_iou convention: side lengths clipped at 0, union floored
    # at 1e-9 (ops/boxes.py:34-41)
    area = jnp.maximum(x2 - x1, 0.0) * jnp.maximum(y2 - y1, 0.0)
    out_idx2 = jax.lax.broadcasted_iota(jnp.int32, out_i_ref.shape, 1)

    def body(i, carry):
        live, out_s, out_i = carry
        best = jnp.max(live, axis=None, keepdims=True)  # (1, 1)
        keep = best > 0.0
        # first index attaining the max (lax argmax tie rule)
        bi = jnp.min(jnp.where(live == best, idx, np_), axis=None,
                     keepdims=True)
        sel = idx == bi  # one-hot (1, Np)
        bx1 = jnp.sum(jnp.where(sel, x1, 0.0), axis=None, keepdims=True)
        by1 = jnp.sum(jnp.where(sel, y1, 0.0), axis=None, keepdims=True)
        bx2 = jnp.sum(jnp.where(sel, x2, 0.0), axis=None, keepdims=True)
        by2 = jnp.sum(jnp.where(sel, y2, 0.0), axis=None, keepdims=True)
        barea = jnp.sum(jnp.where(sel, area, 0.0), axis=None, keepdims=True)
        iw = jnp.maximum(jnp.minimum(x2, bx2) - jnp.maximum(x1, bx1), 0.0)
        ih = jnp.maximum(jnp.minimum(y2, by2) - jnp.maximum(y1, by1), 0.0)
        inter = iw * ih
        iou = inter / jnp.maximum(area + barea - inter, 1e-9)
        suppress = (iou >= iou_threshold) | sel
        live = jnp.where(keep & suppress, -1.0, live)
        out_s = jnp.where(out_idx2 == i, jnp.where(keep, best, 0.0), out_s)
        out_i = jnp.where(out_idx2 == i, jnp.where(keep, bi, -1), out_i)
        return live, out_s, out_i

    out_s = jnp.zeros(out_s_ref.shape, out_s_ref.dtype)
    out_i = jnp.full(out_i_ref.shape, -1, jnp.int32)
    _, out_s, out_i = jax.lax.fori_loop(
        0, max_detections, body, (live, out_s, out_i))
    out_s_ref[...] = out_s
    out_i_ref[...] = out_i


def pallas_nms(boxes, scores, max_detections: int, iou_threshold: float,
               score_threshold: float, interpret: bool | None = None):
    """Batched greedy NMS selection. boxes (B, N, 4) xyxy, scores (B, N)
    -> (sel_scores (B, D), sel_idx (B, D) int32, -1 = no selection).

    Matches ops/nms.py `_nms_single` exactly (same thresholding, same
    tie-breaking, same IoU arithmetic); class-awareness is the caller's
    offset trick, gathers of boxes/classes stay outside the kernel.
    """
    if interpret is None:
        interpret = dvt_backend.pallas_interpret()
    n = boxes.shape[1]
    np_ = _round_up(max(n, 1), _LANES)
    dp = _round_up(max(max_detections, 1), _LANES)
    scores = jnp.where(scores >= score_threshold, scores, -1.0)
    scores = scores.astype(jnp.float32)
    boxes = boxes.astype(jnp.float32)
    if np_ != n:
        scores = jnp.pad(scores, ((0, 0), (0, np_ - n)),
                         constant_values=-1.0)
        boxes = jnp.pad(boxes, ((0, 0), (0, np_ - n), (0, 0)))
    x1, y1, x2, y2 = (boxes[..., i][:, None, :] for i in range(4))

    # the kernel sees (1, Np) / (1, Dp): the batch dim is squeezed away
    row = pl.BlockSpec((None, 1, np_), lambda i: (i, 0, 0))
    out_row = pl.BlockSpec((None, 1, dp), lambda i: (i, 0, 0))

    def select(*rows):  # one data-axis shard of the images (partition.py)
        bs = rows[0].shape[0]
        return pl.pallas_call(
            functools.partial(_nms_kernel, max_detections=max_detections,
                              iou_threshold=float(iou_threshold)),
            out_shape=[
                jax.ShapeDtypeStruct((bs, 1, dp), jnp.float32),
                jax.ShapeDtypeStruct((bs, 1, dp), jnp.int32),
            ],
            grid=(bs,),
            in_specs=[row] * 5,
            out_specs=[out_row, out_row],
            interpret=bool(interpret),
            name="nms",
        )(*rows)

    out_s, out_i = over_data_axis(select, (True,) * 5)(
        x1, y1, x2, y2, scores[:, None, :])
    return out_s[:, 0, :max_detections], out_i[:, 0, :max_detections]
