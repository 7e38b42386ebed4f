"""Perf smoke: the CPU-provable contracts behind the step-time attack.

    PYTHONPATH=. JAX_PLATFORMS=cpu python tools/perf_smoke.py \
        [--workdir artifacts/perf_smoke]

The CI teeth behind the perf layer (`make perf-smoke`, a `make verify`
prerequisite) the way obs-smoke gates obs/ and chaos-smoke gates
resilience/. The on-TPU acceptance for this arc is a bench delta
(vs_baseline >= 1.0 wall, mfu_device_pct >= 40); these are the proxies
that must hold on ANY backend before that bench is even worth running:

  1. fused kernels   ops/pallas/nms.py runs under interpret=True and must
                     match its pure-lax reference: exact index/score
                     agreement through the full class-aware
                     non_maximum_suppression.
  2. multistep       a Trainer(multistep=4) superstep over 4 stacked
                     batches must land within float-ulp of 4 single-step
                     dispatches (same params, same per-microstep losses),
                     with step counters advanced identically.
  3. dispatch math   a journal-wired multistep=4 run must show 4x fewer
                     step events than optimizer steps (one dispatch per K
                     microsteps), each stamped multistep=4, and ZERO
                     backend recompiles after the first superstep across
                     the whole window (tail single-steps excluded: they
                     own one compile of their own executable).
  4. device prefetch a DevicePrefetcher at depth 2 feeding a slower
                     consumer must never starve (starvation counter 0);
                     a depth-1 buffer against a slow producer must.
  5. schema          the journal (multistep step fields + a bench event
                     carrying the new wall/device-ms fields) passes
                     `check_journal --strict` — extended fields are
                     forward-compatible, not schema violations.

Exit status 0 = every contract held; 1 = something broke.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class Failures:
    def __init__(self):
        self.rows = []

    def check(self, ok: bool, what: str):
        print(("PASS " if ok else "FAIL ") + what, flush=True)
        if not ok:
            self.rows.append(what)


def phase1_fused_kernels(f: Failures):
    import jax.numpy as jnp
    import numpy as np

    from deep_vision_tpu.ops.nms import non_maximum_suppression

    rng = np.random.RandomState(0)
    xy = rng.rand(2, 300, 2).astype(np.float32) * 0.8
    wh = rng.rand(2, 300, 2).astype(np.float32) * 0.2 + 0.02
    boxes = jnp.asarray(np.concatenate([xy, xy + wh], -1))
    scores = jnp.asarray(rng.rand(2, 300).astype(np.float32))
    classes = jnp.asarray(rng.randint(0, 7, size=(2, 300)).astype(np.int32))
    kw = dict(max_detections=32, iou_threshold=0.5, score_threshold=0.3)
    lax_out = non_maximum_suppression(boxes, scores, classes, impl="lax",
                                      **kw)
    pal_out = non_maximum_suppression(boxes, scores, classes, impl="pallas",
                                      **kw)
    ok = all(np.array_equal(np.asarray(u), np.asarray(v))
             for u, v in zip(lax_out, pal_out))
    f.check(ok, "nms: pallas kernel selections EXACTLY match the lax loop "
                "(boxes/scores/classes/valid)")


def _make_trainer(multistep: int, journal=None, registry=None):
    import jax.numpy as jnp

    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    model = get_model("lenet5", num_classes=4)
    tx = build_optimizer("sgd", 0.05, momentum=0.9)
    return Trainer(model, tx, classification_loss_fn,
                   sample_input=jnp.zeros((8, 32, 32, 1)),
                   multistep=multistep, journal=journal, registry=registry)


def _batches(n, bs=32, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    return [{"image": rng.rand(bs, 32, 32, 1).astype(np.float32),
             "label": rng.randint(0, 4, size=bs)} for _ in range(n)]


def phase2_multistep_equivalence(f: Failures):
    import jax
    import numpy as np

    batches = _batches(4)
    t1 = _make_trainer(1)
    t4 = _make_trainer(4)
    singles = [t1.train_step(b) for b in batches]
    stacked = t4.train_superstep(batches)
    p1 = jax.device_get(t1.state.params)
    p4 = jax.device_get(t4.state.params)
    diffs = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda u, v: float(np.abs(u - v).max()), p1, p4))
    f.check(max(diffs) <= 1e-6,
            f"multistep: params after 1 superstep == 4 single steps "
            f"(max leaf diff {max(diffs):.2e} <= 1e-6)")
    losses_ok = all(
        abs(float(singles[i]["loss"]) - float(stacked[i]["loss"])) <= 1e-5
        for i in range(4))
    f.check(losses_ok, "multistep: per-microstep losses recovered from the "
                       "scan stack match the single-step series")
    f.check(int(t1.state.step) == int(t4.state.step) == 4,
            "multistep: step counter advanced by K in one dispatch")


def phase3_dispatch_and_recompiles(f: Failures, workdir: str):
    import json
    import subprocess

    from deep_vision_tpu.obs.journal import RunJournal
    from deep_vision_tpu.obs.registry import Registry
    from deep_vision_tpu.obs.stepclock import recompile_count

    jpath = os.path.join(workdir, "perf_smoke.jsonl")
    with RunJournal(jpath, kind="train") as journal:
        journal.manifest(config={"tool": "perf_smoke", "multistep": 4})
        t = _make_trainer(4, journal=journal, registry=Registry())
        batches = _batches(16, seed=1)
        # epoch 1 owns the one allowed compile (superstep executable);
        # epoch 2 re-runs the same shapes and must be compile-free
        t.fit(lambda: iter(batches), epochs=1, handle_preemption=False)
        before = recompile_count()
        t.fit(lambda: iter(batches), epochs=2, start_epoch=1,
              handle_preemption=False)
        delta = recompile_count() - before
        f.check(delta == 0,
                f"multistep: ZERO recompiles across the second multistep "
                f"window (saw {delta})")
        f.check(int(t.state.step) == 32,
                "multistep: 32 optimizer steps from 8 dispatches")
        # bench event with the NEW fields (wall/device per-step ms,
        # dispatch arithmetic) — the schema must accept them
        journal.bench("resnet50_train", {
            "metric": "resnet50_train_images_per_sec_per_chip",
            "value": 0.0, "vs_baseline": 0.0, "multistep": 4,
            "wall_ms_per_step": 1.0, "device_ms_per_step": 0.9,
            "dispatches_per_window": 150, "steps_per_dispatch": 4,
        })
    rows = [json.loads(line) for line in open(jpath)]
    steps = [r for r in rows if r["event"] == "step"]
    f.check(len(steps) == 8 and all(r.get("multistep") == 4 for r in steps),
            "journal: one step event per dispatch, each stamped multistep=4 "
            f"(saw {len(steps)} events for 32 steps — 4x fewer dispatches)")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_journal.py"),
         jpath, "--strict"], capture_output=True, text=True)
    f.check(proc.returncode == 0,
            "journal: check_journal --strict accepts the multistep step "
            f"fields and extended bench event ({proc.stdout.strip()!r})")


def phase4_device_prefetch(f: Failures):
    import time

    from deep_vision_tpu.data.device_prefetch import (
        DevicePrefetcher,
        PlacedBatch,
    )
    from deep_vision_tpu.obs.registry import Registry

    reg = Registry()

    def place(b):
        return PlacedBatch(b, 1, 1)

    # fast producer, slow consumer, depth 2: never starves
    pf = DevicePrefetcher(place_one=place, depth=2, name="smoke", registry=reg)
    for _ in pf(iter(range(20))):
        time.sleep(0.002)
    starved = reg.counter("device_prefetch_starved_total",
                          labels={"loader": "smoke"}).value
    f.check(starved == 0,
            f"device prefetch: depth-2 buffer never starves a slower "
            f"consumer (starved={starved})")

    def slow_src():
        for i in range(10):
            time.sleep(0.01)
            yield i

    pf2 = DevicePrefetcher(place_one=place, depth=1, name="smoke2",
                           registry=reg)
    list(pf2(slow_src()))
    starved2 = reg.counter("device_prefetch_starved_total",
                           labels={"loader": "smoke2"}).value
    f.check(starved2 > 0,
            f"device prefetch: a slow producer IS visible as starvation "
            f"(starved={starved2}) — the gauge is live, not decorative")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workdir", default="artifacts/perf_smoke")
    args = p.parse_args(argv)
    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir, exist_ok=True)

    f = Failures()
    print("== phase 1: fused-kernel parity (interpret mode) ==", flush=True)
    phase1_fused_kernels(f)
    print("== phase 2: scan-multistep equivalence ==", flush=True)
    phase2_multistep_equivalence(f)
    print("== phase 3: dispatch amortization + zero recompiles ==",
          flush=True)
    phase3_dispatch_and_recompiles(f, args.workdir)
    print("== phase 4: device-prefetch overlap ==", flush=True)
    phase4_device_prefetch(f)

    if f.rows:
        print(f"\nperf-smoke: {len(f.rows)} contract(s) FAILED:")
        for r in f.rows:
            print("  - " + r)
        return 1
    print("\nperf-smoke: all contracts held")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
