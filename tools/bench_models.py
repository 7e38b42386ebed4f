"""Reproducible per-model benchmark artifacts (YOLOv3 step, flash attention).

README's Performance table cites two numbers beyond the ResNet-50 headline:
the YOLOv3-416 train step (the reference's ONLY published perf figure is a
YOLO epoch time — BASELINE.md) and the Pallas flash-attention kernel vs XLA
dense attention. This harness re-measures both on the local chip and writes
one JSON artifact so the claims stay numbers, not sentences:

    PYTHONPATH=. python tools/bench_models.py [--out artifacts/models_bench.json]

Methodology matches bench.py: median of timed windows, timing closed by a
device->host scalar fetch, one process (wall drift across sessions is +-4%
on this rig, artifacts record the session's interleaved values).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _median_ms(call, steps=100, windows=3):
    """Median wall ms per `call()`, each window closed by ONE
    block_until_ready on the last call's output; steps=100 per window
    amortizes that synchronization."""
    import jax

    for _ in range(3):
        out = call()
    jax.block_until_ready(out)
    dts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = call()
        jax.block_until_ready(out)
        dts.append((time.perf_counter() - t0) / steps)
    return float(np.median(dts)) * 1e3


def bench_yolo(batch: int = 16, size: int = 416, classes: int = 80) -> dict:
    """Full YOLOv3 train step: fwd + 3-scale loss + bwd + SGD update."""
    import jax
    import jax.numpy as jnp

    from deep_vision_tpu.core.train_state import create_train_state
    from deep_vision_tpu.losses.yolo import yolo_train_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train.optimizers import build_optimizer

    model = get_model("yolov3", num_classes=classes, dtype=jnp.bfloat16)
    tx = build_optimizer("sgd", 1e-3, momentum=0.9)
    state = create_train_state(
        model, tx, jnp.ones((2, size, size, 3), jnp.float32)
    )
    rng = np.random.RandomState(0)
    keep = rng.rand(batch, 100, 1) > 0.9  # ~10 real boxes per image
    boxes = np.tile([[0.2, 0.2, 0.6, 0.6]], (batch, 100, 1)) * keep
    batch_d = {
        "image": jnp.asarray(rng.rand(batch, size, size, 3), jnp.bfloat16),
        "boxes": jnp.asarray(boxes, jnp.float32),
        "classes": jnp.asarray(
            rng.randint(0, classes, size=(batch, 100)), jnp.int32
        ),
    }
    grid_sizes = (size // 32, size // 16, size // 8)

    def train_step(state, batch):
        def loss_fn(params):
            out, nms = state.apply_fn(
                {"params": params, "batch_stats": state.batch_stats},
                batch["image"], train=True,
                rngs={"dropout": jax.random.fold_in(state.rng, state.step)},
                mutable=["batch_stats"],
            )
            loss, _ = yolo_train_loss_fn(
                out, batch, grid_sizes=grid_sizes, num_classes=classes
            )
            return loss, nms["batch_stats"]

        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        return state.apply_gradients(grads).replace(batch_stats=bs), loss

    step = jax.jit(train_step, donate_argnums=0)

    box = {"state": state}  # donation: thread the live state through calls

    def call():
        box["state"], loss = step(box["state"], batch_d)
        return loss

    ms = _median_ms(call)
    return {
        "what": f"yolov3-{size} train step (fwd + 3-scale loss + bwd + sgd), "
                f"bf16, batch {batch}, {classes} classes, 100 padded boxes",
        "wall_ms_per_step": round(ms, 1),
        "images_per_sec": round(batch / ms * 1e3, 1),
        "reference_baseline": "~180 img/s on 8x V100 "
                              "(YOLO/tensorflow/README.md:7, BASELINE.md)",
    }


def bench_flash(b=4, t=4096, h=8, d=64) -> dict:
    """Pallas flash attention fwd+bwd vs XLA dense attention, causal bf16."""
    import jax
    import jax.numpy as jnp

    from deep_vision_tpu.ops.pallas.flash_attention import (
        _dense_reference,
        flash_attention,
    )

    rng = np.random.RandomState(0)
    q, k, v = (
        jnp.asarray(rng.randn(b, t, h, d) * 0.2, jnp.bfloat16)
        for _ in range(3)
    )

    def _scalarized(attn):
        # grads still fully computed; reduced to one scalar so _median_ms
        # can close timing with a float() fetch
        @jax.jit
        def fwd_bwd(q, k, v):
            grads = jax.grad(
                lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2),
            )(q, k, v)
            return sum(jnp.sum(g.astype(jnp.float32)) for g in grads)

        return fwd_bwd

    flash_fn = _scalarized(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    )
    dense_fn = _scalarized(
        lambda q, k, v: _dense_reference(q, k, v, True, d ** -0.5)
    )
    flash_ms = _median_ms(lambda: flash_fn(q, k, v))
    dense_ms = _median_ms(lambda: dense_fn(q, k, v))
    return {
        "what": f"attention fwd+bwd, causal bf16, B{b} T{t} H{h} D{d}",
        "pallas_flash_ms": round(flash_ms, 1),
        "xla_dense_ms": round(dense_ms, 1),
        "speedup": round(dense_ms / flash_ms, 2),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default="artifacts/models_bench.json")
    p.add_argument("--journal", default=None,
                   help="bench-journal JSONL (default: <out>.journal.jsonl); "
                        "same schema as train_cli --journal, so BENCH_* "
                        "artifacts machine-diff across PRs via "
                        "tools/obs_report.py")
    p.add_argument("--skip-yolo", action="store_true")
    p.add_argument("--skip-flash", action="store_true")
    args = p.parse_args(argv)

    import jax

    from deep_vision_tpu.obs import RunJournal

    journal_path = args.journal or (
        os.path.splitext(args.out)[0] + ".journal.jsonl"
    )
    result = {"device_kind": jax.devices()[0].device_kind}
    with RunJournal(journal_path, kind="bench") as journal:
        journal.manifest(config={"tool": "bench_models", "out": args.out})
        if not args.skip_yolo:
            result["yolov3"] = bench_yolo()
            print("yolo:", json.dumps(result["yolov3"]))
            journal.bench("yolov3", result["yolov3"])
            # per-chip batch optimum moved for ResNet-50 (batch_scaling_r04);
            # check YOLO's curve one octave up too
            result["yolov3_b32"] = bench_yolo(batch=32)
            print("yolo b32:", json.dumps(result["yolov3_b32"]))
            journal.bench("yolov3_b32", result["yolov3_b32"])
        if not args.skip_flash:
            result["flash_attention"] = bench_flash()
            print("flash:", json.dumps(result["flash_attention"]))
            journal.bench("flash_attention", result["flash_attention"])
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"-> {args.out} (journal: {journal_path})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
