"""Batch-size scaling curve for the flagship train step (round 4).

Round 3 found batch 512 ~6% slower PER IMAGE on-device than 256
(artifacts/dispatch_r03.json) and left it unexplained. This sweep measures
device time, wall time, XLA cost-analysis bytes, and XLA memory-analysis
peak HBM for batch in {128, 192, 256, 320, 384, 512} in ONE process with
interleaved windows (session drift is +-4%).

The capacity hypothesis: ResNet-50/224 bf16 saves ~46 MB of activations per
image for the backward pass; at batch 512 that alone is ~23 GB against the
v5e's 16 GB HBM, so XLA must rematerialize/spill — visible as bytes/image
and time/image going UP while memory-analysis pins near the HBM limit.

Writes artifacts/batch_scaling_r04.json. Run solo on the chip.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402

BATCHES = [128, 192, 256, 320, 384, 512]
REPS = 3
STEPS_PER_WINDOW_IMAGES = 256 * 20  # equal IMAGE count per window


def _log(m):
    print(f"batch_sweep: {m}", file=sys.stderr, flush=True)


def main(out_path="artifacts/batch_scaling_r04.json"):
    art = {"what": __doc__.split("\n")[0], "batches": BATCHES, "reps": REPS}
    rows = {}
    built = {}
    for b in BATCHES:
        try:
            t0 = time.perf_counter()
            step, state, batch, batch_size, n_chips, devices = (
                bench.build_bench(b, 1)
            )
            row = {"batch_per_chip": b,
                   "compile_s": round(time.perf_counter() - t0, 1)}
            try:
                ca = step.cost_analysis()
                row["bytes_gb_per_step"] = round(
                    float(ca["bytes accessed"]) / 1e9, 3
                )
                row["bytes_mb_per_image"] = round(
                    float(ca["bytes accessed"]) / 1e6 / b, 1
                )
                row["gflops_per_image"] = round(float(ca["flops"]) / 1e9 / b,
                                                2)
            except Exception as e:
                row["bytes_gb_per_step"] = None
                _log(f"b{b} cost_analysis: {e}")
            try:
                ma = step.memory_analysis()
                for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes", "alias_size_in_bytes"):
                    v = getattr(ma, k, None)
                    if v is not None:
                        row[k.replace("_in_bytes", "_gb")] = round(v / 1e9, 2)
            except Exception as e:
                _log(f"b{b} memory_analysis: {e}")
            # warmup
            for _ in range(3):
                state, loss = step(state, batch)
            float(loss)
            built[b] = [step, state, batch, row, []]
            _log(f"b{b}: compiled {row['compile_s']}s, "
                 f"bytes/img {row.get('bytes_mb_per_image')} MB, "
                 f"temp {row.get('temp_size_gb')} GB")
        except KeyboardInterrupt:
            raise
        except Exception as e:
            _log(f"b{b} FAILED: {type(e).__name__}: {e}")
            rows[b] = {"batch_per_chip": b,
                       "error": f"{type(e).__name__}: {e}"}
    for rep in range(REPS):
        for b, (step, state, batch, row, dts) in list(built.items()):
            n_steps = max(1, STEPS_PER_WINDOW_IMAGES // b)
            try:
                t0 = time.perf_counter()
                for _ in range(n_steps):
                    state, loss = step(state, batch)
                float(loss)
                dts.append((time.perf_counter() - t0) / n_steps)
                built[b][1] = state
                _log(f"rep {rep} b{b}: {dts[-1] * 1e3:.2f} ms/step "
                     f"({b / dts[-1]:.0f} img/s)")
            except KeyboardInterrupt:
                raise
            except Exception as e:
                _log(f"rep {rep} b{b} dropped: {type(e).__name__}: {e}")
                row["error"] = f"{type(e).__name__}: {e}"
                del built[b]
    for b, (step, state, batch, row, dts) in built.items():
        if dts:
            wall_ms = float(np.median(dts)) * 1e3
            row["wall_ms_per_step"] = round(wall_ms, 2)
            row["wall_images_per_sec"] = round(b / wall_ms * 1e3, 1)
        dev = bench._device_step_ms(step, state, batch, 1)
        if dev:
            row["device_ms_per_step"] = round(dev, 2)
            row["device_images_per_sec"] = round(b / dev * 1e3, 1)
            row["device_ms_per_256_images"] = round(dev * 256 / b, 2)
        rows[b] = row
        _log(f"b{b}: wall {row.get('wall_ms_per_step')} ms, device "
             f"{row.get('device_ms_per_step')} ms "
             f"({row.get('device_images_per_sec')} img/s device)")
    art["rows"] = [rows[b] for b in BATCHES if b in rows]
    good = [r for r in art["rows"] if r.get("device_images_per_sec")]
    if good:
        best = max(good, key=lambda r: r["device_images_per_sec"])
        art["recommended_batch_per_chip"] = best["batch_per_chip"]
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(art, f, indent=2)
    _log(f"wrote {out_path}")


if __name__ == "__main__":
    # usage: batch_sweep.py [out.json] [b1,b2,...]
    if len(sys.argv) > 2:
        BATCHES = [int(b) for b in sys.argv[2].split(",")]
    main(sys.argv[1] if len(sys.argv) > 1 else
         "artifacts/batch_scaling_r04.json")
