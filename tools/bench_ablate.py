"""Ablation artifact for the README's perf claims (round 4).

Measures, on the real chip in ONE process with interleaved windows
(session drift is +-4%), the three design choices the README credits for
the ResNet-50 number, plus the flash-attention win:

- **s2d stem** (flagship): host lays out (H/2, W/2, 12); stem conv is
  math-identical to 7x7/s2 (tests/test_models_classifiers.py) but
  MXU-friendly — vs the plain conv7 stem on (H, W, 3).
- **fused single-pass BN** (nn/layers.py BatchNorm): activation never
  materialized in f32 — vs flax `nn.BatchNorm` (which promotes the full
  tensor to f32), swapped in by monkeypatching `FusedBatchNorm`.
- **flash vs dense attention**: the Pallas kernel vs the exact dense
  einsum (re-uses tools/bench_models.py bench_flash).

Writes artifacts/ablate_r04.json; every README perf claim should cite a
number from this file or artifacts/models_bench.json. Run solo on the chip.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402

WINDOW = 100  # window-closing fetch costs ~118 ms once per window; 100
              # steps caps the per-step bias at ~1.2 ms (was 50 in r4 —
              # fine for the ResNet ms/step scale, but the short flash
              # attention calls need the longer window; see bench_models)
REPS = 3
BATCH = 128  # flagship batch (artifacts/batch_scaling_r04.json)


def _log(m):
    print(f"ablate: {m}", file=sys.stderr, flush=True)


from contextlib import contextmanager


@contextmanager
def _swap_bn(unfused: bool):
    """Swap EVERY FusedBatchNorm the ResNet path sees for flax nn.BatchNorm.

    `from ... import FusedBatchNorm` binds the name into each model module,
    so patching only nn.layers would leave resnet.py's direct call sites
    (stem BN, bottleneck zero-init BN) fused — the r4 reviewer caught that.
    flax BatchNorm takes the same kwargs ConvBN/resnet pass and promotes
    the activation to f32 (the exact behavior the fused BN avoids).
    """
    import flax.linen as nn

    from deep_vision_tpu.models import resnet as R
    from deep_vision_tpu.nn import layers as L

    if not unfused:
        yield
        return
    saved = (L.FusedBatchNorm, R.FusedBatchNorm)
    L.FusedBatchNorm = nn.BatchNorm
    R.FusedBatchNorm = nn.BatchNorm
    try:
        yield
    finally:
        L.FusedBatchNorm, R.FusedBatchNorm = saved


def make_step(*, stem="s2d", unfused_bn=False):
    """The bench train step with the ablation knobs applied.

    bench.make_train_parts builds the exact flagship program (BATCH images
    PER CHIP, like bench.py); the BN swap stays active through construction
    AND the jit trace. All reported rates are per chip: XLA cost analysis
    is per-device under SPMD and BATCH/time is the per-chip rate."""
    import jax

    with _swap_bn(unfused_bn):
        train_step, state, batch, *_ = bench.make_train_parts(
            BATCH, stem=stem
        )
        step = jax.jit(train_step, donate_argnums=0).lower(
            state, batch
        ).compile()
    return step, state, batch


VARIANTS = [
    ("flagship_s2d_fused_bn", dict(stem="s2d", unfused_bn=False)),
    ("conv7_stem", dict(stem="conv7", unfused_bn=False)),
    ("unfused_flax_bn", dict(stem="s2d", unfused_bn=True)),
]


def main(out_path="artifacts/ablate_r04.json", skip_flash=False,
         journal_path=None):
    from deep_vision_tpu.obs import RunJournal

    journal = RunJournal(
        journal_path or os.path.splitext(out_path)[0] + ".journal.jsonl",
        kind="bench",
    )
    journal.manifest(config={"tool": "bench_ablate", "out": out_path,
                             "batch_per_chip": BATCH, "window": WINDOW,
                             "reps": REPS})
    art = {"what": __doc__.split("\n")[0], "batch_per_chip": BATCH,
           "window": WINDOW, "reps": REPS}
    built = {}
    for name, kw in VARIANTS:
        try:
            t0 = time.perf_counter()
            step, state, batch = make_step(**kw)
            row = {"variant": name,
                   "compile_s": round(time.perf_counter() - t0, 1)}
            try:
                ca = step.cost_analysis()
                row["bytes_gb_per_step"] = round(
                    float(ca["bytes accessed"]) / 1e9, 3
                )
                row["gflops_per_image"] = round(
                    float(ca["flops"]) / 1e9 / BATCH, 2
                )
            except Exception as e:
                _log(f"{name} cost_analysis: {e}")
            for _ in range(3):
                state, loss = step(state, batch)
            float(loss)
            built[name] = [step, state, batch, row, []]
            _log(f"{name}: compiled {row['compile_s']}s, "
                 f"{row.get('bytes_gb_per_step')} GB/step")
        except KeyboardInterrupt:
            raise
        except Exception as e:
            _log(f"{name} FAILED: {type(e).__name__}: {e}")
            built[name] = None
            art.setdefault("errors", []).append(
                f"{name}: {type(e).__name__}: {e}"
            )
    for rep in range(REPS):
        for name, slot in built.items():
            if slot is None or (isinstance(slot, tuple)
                                and slot[0] == "done"):
                continue
            step, state, batch, row, dts = slot
            try:
                t0 = time.perf_counter()
                for _ in range(WINDOW):
                    state, loss = step(state, batch)
                float(loss)
                dts.append((time.perf_counter() - t0) / WINDOW)
                slot[1] = state
                _log(f"rep {rep} {name}: {dts[-1] * 1e3:.2f} ms/step")
            except KeyboardInterrupt:
                raise
            except Exception as e:
                # donated state is gone: stop timing this variant, but KEEP
                # its row (partial reps + the error) in the artifact
                msg = f"rep {rep} {name}: {type(e).__name__}: {e}"
                _log(f"dropped: {msg}")
                row["error"] = msg
                art.setdefault("errors", []).append(msg)
                built[name] = ("done", row, dts)
    rows = []
    flagship = None
    for name, slot in built.items():
        if slot is None:
            continue
        if isinstance(slot, tuple) and slot[0] == "done":
            _, row, dts = slot
            if dts:
                wall = float(np.median(dts)) * 1e3
                row["wall_ms_per_step"] = round(wall, 2)
                row["wall_images_per_sec_per_chip"] = round(BATCH / wall * 1e3, 1)
            rows.append(row)
            continue
        step, state, batch, row, dts = slot
        if dts:
            wall = float(np.median(dts)) * 1e3
            row["wall_ms_per_step"] = round(wall, 2)
            row["wall_images_per_sec_per_chip"] = round(BATCH / wall * 1e3, 1)
        dev = bench._device_step_ms(step, state, batch, 1)
        if dev:
            row["device_ms_per_step"] = round(dev, 2)
            row["device_images_per_sec_per_chip"] = round(BATCH / dev * 1e3, 1)
        if name == "flagship_s2d_fused_bn":
            flagship = row
        rows.append(row)
    for row in rows:
        if flagship and row is not flagship and row.get("device_ms_per_step") \
                and flagship.get("device_ms_per_step"):
            row["slowdown_vs_flagship"] = round(
                row["device_ms_per_step"] / flagship["device_ms_per_step"], 3
            )
    art["resnet50_variants"] = rows
    for row in rows:
        journal.bench(row.get("variant", "?"), row)
    if not skip_flash:
        try:
            from tools.bench_models import bench_flash

            art["flash_attention"] = bench_flash()
            _log(f"flash: {art['flash_attention']}")
            journal.bench("flash_attention", art["flash_attention"])
        except Exception as e:
            art.setdefault("errors", []).append(
                f"flash: {type(e).__name__}: {e}"
            )
            _log(f"flash failed: {e}")
    for err in art.get("errors", []):
        journal.write("note", note=err)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(art, f, indent=2)
    journal.close()
    _log(f"wrote {out_path}")


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--out", default="artifacts/ablate_r04.json")
    p.add_argument("--journal", default=None,
                   help="bench-journal JSONL (default: <out>.journal.jsonl)")
    p.add_argument("--skip-flash", action="store_true")
    a = p.parse_args()
    main(a.out, a.skip_flash, a.journal)
