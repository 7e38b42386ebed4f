"""Benchmark: ResNet-50 training throughput (images/sec) on the local chip(s).

Default mode runs the framework's real jitted train step (forward + loss +
backward + SGD update + BN stat update) on the flagship model with synthetic
ImageNet-shaped data in bfloat16 compute (fp32 params), and prints ONE JSON
line:

    {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N}

Baseline: the reference repo publishes no throughput for its classifiers (its
only perf number is YOLOv3 epoch time, BASELINE.md); the driver's north star
is ">= 0.9x A100x8 images/sec" for ResNet-50 (BASELINE.json). We normalize
per chip: an A100 sustains ~2900 images/sec on ResNet-50/224 mixed-precision
training (MLPerf-class recipe), so the per-chip target is 0.9 * 2900 = 2610
and vs_baseline = value_per_chip / 2610.

`value` is the WALL-CLOCK rate (all host-side overhead included) so the
headline is comparable across rounds and to BASELINE.json; the
profiler-derived device-time rate is reported alongside under
`device_images_per_sec_per_chip`. MFU and HBM traffic per step are
reported from XLA's post-fusion cost analysis so the "HBM-bound"
characterization is a number, not a sentence.

Timed windows are TIMED_STEPS=600 steps long and each is closed by ONE
`block_until_ready`, so the host synchronization is paid once per window,
the way a training loop pays it at its logging boundaries.

Failure is loud. The run needs a TPU: on any other platform it prints what
JAX found and exits 2 with no result line (a CPU rate must never appear
under a device metric's name). One budgeted liveness probe
(`resilience.elastic.backend_alive`, shared with tools/preflight.py,
BENCH_INIT_BUDGET_S, default 180 s) runs before any real work. The timing
loop still retries a failed window by rebuilding the jitted step and
replaying it (`resilience.elastic.BackendSupervisor`, the object the Trainer
drives for backend loss; donated buffers die with the failure, so windows
replay on a rebuilt step) — but any line that carries `errors`, has no
value, or is short of WINDOWS windows is a DEGRADED line, and the process
exits 1 after printing it. Exit 0 means a complete, healthy measurement.

`--data host` / `--data fused` instead benchmark the REAL input pipeline
(SURVEY §7 hard part #1): sharded records -> JPEG decode -> augment -> host
batches (`host`), plus space-to-depth + device_put onto the chip (`fused`),
over a self-generated JPEG record fixture. The number is reported per host
CPU core (this VM has one; the 224-vCPU host of a real v5e-8 slice scales
the pipeline linearly with cores via DataLoader(num_procs=...)), with
vs_baseline = per_core / (8 * 2610 / 224) — the per-core rate at which a
full v5e-8 host (224 vCPUs) keeps all 8 chips fed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from deep_vision_tpu.resilience import RetryPolicy
# the one budgeted liveness probe, shared with tools/preflight.py
from deep_vision_tpu.resilience.elastic import (
    BackendSupervisor,
    backend_alive as _backend_alive,
)

A100_IMG_PER_SEC = 2900.0
TARGET_PER_CHIP = 0.9 * A100_IMG_PER_SEC

BATCH_PER_CHIP = 128  # the measured per-chip optimum: 46.3 ms/step device
                      # = 2764 img/s vs 97.9 ms = 2615 at 256 (the whole
                      # curve: artifacts/batch_scaling_r04.json; batch 512
                      # crosses the HBM-capacity line and rematerializes)
IMAGE_SIZE = 224
WARMUP_STEPS = 5
TIMED_STEPS = 600  # steps per timed window: one host sync per window
WINDOWS = 3  # report the MEDIAN window: robust to jitter without
             # inflating the metric the way a best-of-N min would
MAX_RETRIES = 5  # rebuild-and-replay budget for a failed window

# seconds the one liveness probe may take before the backend is declared
# dead (a cold TPU client answers in ~15 s)
INIT_BUDGET_S = float(os.environ.get("BENCH_INIT_BUDGET_S", "180"))

_EMITTED = None  # the contract line this process printed, once it has


def _emit(result: dict) -> bool:
    """Print the one contract JSON line, at most once per process (main's
    `finally` and the CLI's own failure path may both reach here)."""
    global _EMITTED
    if _EMITTED is not None:
        return False
    _EMITTED = result
    # stamp the environment fingerprint the perf ledger keys on
    # (tools/perf_gate.py): a number without its environment is not
    # comparable, and the stamp must ride the SAME line the driver
    # captures — best-effort, a bench must never die to bookkeeping
    try:
        from tools.perf_gate import default_env, env_key

        result.setdefault("env", default_env())
        result.setdefault("env_key", env_key(result["env"]))
    except Exception:
        pass
    print(json.dumps(result), flush=True)
    # journal AFTER the stdout contract line (best-effort, never raises):
    # emitting here — not per run mode — covers train, sweep and data
    _journal_result(result)
    return True


def degraded(result: dict) -> bool:
    """Is this contract line anything less than a complete, healthy
    measurement? The CLI's exit code: 1 when it is."""
    if result.get("errors"):
        return True
    if "windows_completed" in result and \
            result["windows_completed"] < WINDOWS:
        return True
    if "value" in result:
        return not result["value"] > 0
    return not result.get("rows")


# Analytic fallback when XLA cost analysis is unavailable: ResNet-50/224
# forward is ~4.09 GMACs/image (torchvision table); MFU convention counts a
# MAC as 2 flops and training (fwd + bwd wrt activations + bwd wrt weights)
# as 3x forward.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 2 * 4.089e9 * 3


FIXTURE_DIR = "/tmp/deep_vision_tpu_bench_records"
# per-core feed target: 8 chips x 2610 img/s spread over a v5e-8 host's 224
# vCPUs (GCP ct5lp-hightpu-8t machine shape)
DATA_TARGET_PER_CORE = 8 * 2610.0 / 224.0


def _ensure_fixture(n_shards: int = 4, per_shard: int = 256) -> str:
    """Self-generated JPEG record shards (~45KB/img, ImageNet-like sizes)."""
    import cv2

    from deep_vision_tpu.data.example_codec import encode_example
    from deep_vision_tpu.data.records import RecordWriter

    if os.path.isdir(FIXTURE_DIR) and len(os.listdir(FIXTURE_DIR)) == n_shards:
        return FIXTURE_DIR
    os.makedirs(FIXTURE_DIR, exist_ok=True)
    rng = np.random.RandomState(0)
    for s in range(n_shards):
        path = os.path.join(FIXTURE_DIR, f"train-{s:05d}")
        # write-then-rename: a Ctrl-C'd prior run must not leave a truncated
        # shard that the count-based reuse check above would accept
        tmp = path + ".tmp"
        with RecordWriter(tmp) as w:
            for _ in range(per_shard):
                img = (rng.rand(375, 500, 3) * 60 + 90).astype(np.uint8)
                img += np.arange(500, dtype=np.uint8)[None, :, None] // 4
                ok, enc = cv2.imencode(
                    ".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90]
                )
                assert ok
                w.write(encode_example({
                    "image/encoded": [enc.tobytes()],
                    "image/class/label": [int(rng.randint(1, 1001))],
                }))
        os.replace(tmp, path)
    return FIXTURE_DIR


def data_main(mode: str, num_procs: int) -> None:
    """Input-pipeline benchmark: the full ImageNet train chain."""
    from deep_vision_tpu.data import Compose, DataLoader, RecordDataset
    from deep_vision_tpu.data import transforms as T

    _ensure_fixture()
    ds = RecordDataset(FIXTURE_DIR + "/*", "imagenet", shuffle_shards=True)
    chain = Compose([
        T.Rescale(256), T.RandomHorizontalFlip(), T.RandomCrop(IMAGE_SIZE),
        T.ColorJitter(0.4, 0.4, 0.4),
        T.ToFloatNormalize(expand_gray_to_rgb=True),
        T.SpaceToDepth(),  # flagship config's host half of the s2d stem
    ])
    dl = DataLoader(ds, BATCH_PER_CHIP, chain, shuffle=True,
                    shuffle_buffer=1024, num_workers=8, num_procs=num_procs,
                    drop_remainder=True)
    if mode == "fused":
        from deep_vision_tpu.parallel.mesh import create_mesh, data_sharding

        mesh = create_mesh()
        put = lambda b: jax.device_put(
            jnp.asarray(b["image"], jnp.bfloat16),
            data_sharding(mesh, 4),
        )
    n_cores = os.cpu_count() or 1
    n = 0
    t0 = time.perf_counter()
    for batch in dl:
        if mode == "fused":
            jax.block_until_ready(put(batch))
        n += len(batch["image"])
    dt = time.perf_counter() - t0
    per_core = n / dt / n_cores
    print(
        f"bench-data: {mode} {n} imgs in {dt:.1f}s on {n_cores} core(s), "
        f"num_procs={num_procs}",
        file=sys.stderr,
    )
    _emit({
        "metric": f"imagenet_pipeline_{mode}_images_per_sec_per_core",
        "value": round(per_core, 1),
        "unit": "images/sec/core",
        "vs_baseline": round(per_core / DATA_TARGET_PER_CORE, 3),
    })


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# steps per timed scaling window; small by default — four sub-mesh builds
# each pay a compile, and the signal is the RATIO between rows, which
# stabilizes in a handful of steps
MULTICHIP_STEPS = int(os.environ.get("BENCH_MULTICHIP_STEPS", "16"))
MULTICHIP_BATCH = int(os.environ.get("BENCH_MULTICHIP_BATCH", "16"))


def multichip_result_stub() -> dict:
    return {"metric": "multichip_scaling", "value": 0.0,
            "unit": "efficiency_fraction", "rows": []}


def multichip_main(result: dict) -> None:
    """MULTICHIP mode: the scaling-efficiency block that replaces the
    dryrun's bare `loss=OK` as the multi-chip artifact's payload.

    A table-sharded (parallel/shardmap.py) slim-flagship train step is
    timed at data={1,2,4,8} sub-meshes of the available devices
    (deep_vision_tpu/tools/scaling.py); the one contract JSON line
    carries throughput + per-device examples/s per row and the
    efficiency fraction vs the 1-device baseline as the headline value
    — also journaled as a typed `bench` event under --journal, so
    obs_report renders the curve and MULTICHIP_r0N rounds diff as
    numbers."""
    from deep_vision_tpu.tools.scaling import (
        format_rows,
        measure_scaling,
        scaling_result,
    )

    _log(f"multichip scaling: {len(jax.devices())} devices, "
         f"{MULTICHIP_STEPS} steps x batch {MULTICHIP_BATCH}/device")
    try:
        rows = measure_scaling(batch_per_device=MULTICHIP_BATCH,
                               steps=MULTICHIP_STEPS)
        print(format_rows(rows), file=sys.stderr, flush=True)
        result.update(scaling_result(rows))
    except KeyboardInterrupt:
        raise
    except Exception as e:
        result["errors"] = result.get("errors", []) + [
            f"{type(e).__name__}: {e}"]
        _log(f"fatal: {type(e).__name__}: {e}")
    finally:
        _emit(result)


def _cold_start_fields() -> dict:
    """cache-cold vs cache-warm cold start, measured in the SAME run on
    the same probe computation (core/excache.py round trip):

      warmup_compile_ms   the compiler's bill — lower + XLA compile +
                          store into a fresh executable cache
      cold_start_ms       what a restarted process pays over a POPULATED
                          cache — lower + deserialize, zero compiles

    The ratio is the recovery-time-objective win the persistent
    executable cache buys serve warmup / elastic rebuild / host re-exec.
    """
    import shutil
    import tempfile

    from deep_vision_tpu.core.excache import ExecutableCache
    from deep_vision_tpu.obs.registry import Registry

    # a temp dir on purpose: this is the AOT store's cold-vs-warm PROBE and
    # must start empty every run; it is not where JAX's compile cache lives
    d = tempfile.mkdtemp(prefix="bench_excache_")
    try:
        cache = ExecutableCache(d, registry=Registry())
        f = jax.jit(lambda v, x: jnp.tanh(x @ v) @ v)
        v = jnp.ones((256, 256), jnp.float32)
        spec = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        t0 = time.perf_counter()
        compiled, src = cache.get_or_compile(
            f.lower(v, spec), name="bench/coldstart")
        compile_ms = (time.perf_counter() - t0) * 1e3
        t1 = time.perf_counter()
        cached, src2 = cache.get_or_compile(
            f.lower(v, spec), name="bench/coldstart")
        cached_ms = (time.perf_counter() - t1) * 1e3
        if src != "compiled" or src2 != "cache":
            # a backend that can't serialize executables: report the
            # honest compile number and no fake cached one
            return {"warmup_compile_ms": round(compile_ms, 1)}
        return {"warmup_compile_ms": round(compile_ms, 1),
                "cold_start_ms": round(cached_ms, 1)}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def make_train_parts(batch_per_chip: int, stem: str = "s2d"):
    """(train_step_fn, state, batch, batch_size, n_chips, devices): the
    UNJITTED flagship train step + freshly staged inputs.

    Shared by build_bench and the perf probes (tools/bench_ablate.py,
    tools/batch_sweep.py) so every measurement times the same program.
    Everything device-resident is created from host-side seeds so a rebuild
    is bit-equivalent.
    """
    from deep_vision_tpu.core.train_state import create_train_state
    from deep_vision_tpu.losses.classification import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.parallel.mesh import create_mesh, data_sharding, replicated
    from deep_vision_tpu.train.optimizers import build_optimizer

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(devices=devices)
    batch_size = batch_per_chip * n_chips

    # space-to-depth stem (models/resnet.py SpaceToDepthStem): the host
    # pipeline ships (H/2, W/2, 12) images; the stem conv is math-identical
    # to 7x7/s2 but MXU-efficient. Input staged in bf16, as the real
    # pipeline does (uint8 decode -> normalize -> bf16 cast on host).
    model = get_model("resnet50", num_classes=1000, dtype=jnp.bfloat16,
                      stem=stem)
    tx = build_optimizer("sgd", learning_rate=0.1, momentum=0.9,
                         weight_decay=1e-4)
    if stem == "s2d":
        img_shape = (IMAGE_SIZE // 2, IMAGE_SIZE // 2, 12)
    else:
        img_shape = (IMAGE_SIZE, IMAGE_SIZE, 3)
    sample = jnp.ones((8, *img_shape), jnp.float32)
    state = create_train_state(model, tx, sample)
    state = jax.device_put(state, replicated(mesh))

    rng = np.random.RandomState(0)
    batch = {
        "image": rng.rand(batch_size, *img_shape)
        .astype(np.float32).astype(jnp.bfloat16),
        "label": rng.randint(0, 1000, size=(batch_size,)).astype(np.int32),
    }
    batch = {
        k: jax.device_put(v, data_sharding(mesh, v.ndim)) for k, v in batch.items()
    }

    def train_step(state, batch):
        step_rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            variables = {"params": params, "batch_stats": state.batch_stats}
            outputs, new_model_state = state.apply_fn(
                variables,
                batch["image"],
                train=True,
                rngs={"dropout": step_rng},
                mutable=["batch_stats"],
            )
            loss, _ = classification_loss_fn(outputs, batch)
            return loss, new_model_state["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        return state.apply_gradients(grads).replace(batch_stats=new_bs), loss

    return train_step, state, batch, batch_size, n_chips, devices


def build_bench(batch_per_chip: int, multistep: int):
    """(Re)build mesh, model state, synthetic batch and the jitted step.

    Called once at start and again after any transient runtime failure —
    a replay is bit-equivalent to the original attempt (make_train_parts).
    """
    (train_step, state, batch, batch_size, n_chips,
     devices) = make_train_parts(batch_per_chip)

    if multistep > 1:
        # K optimizer steps per dispatch: a lax.scan superstep. Quantifies
        # (and, on hosts where dispatch is the bottleneck, removes) the
        # per-dispatch turnaround cost.
        def superstep(state, batch):
            def body(s, _):
                s, loss = train_step(s, batch)
                return s, loss

            state, losses = jax.lax.scan(body, state, None, length=multistep)
            return state, losses[-1]

        fn = superstep
    else:
        fn = train_step

    # AOT-compile once: the SAME executable serves the timed windows and
    # cost_analysis() afterwards (a plain jit would recompile for the
    # post-run .lower().compile() — a duplicate multi-second compile)
    step = jax.jit(fn, donate_argnums=0).lower(state, batch).compile()

    return step, state, batch, batch_size, n_chips, devices


def _retry_policy() -> RetryPolicy:
    """The bench retry policy, built per call so a monkeypatched
    MAX_RETRIES (tests) is honored. retry_on=Exception: jax wraps runtime
    failures in RuntimeError, and everything this loop runs is a replayable
    pure computation, so any Exception here is worth one more attempt."""
    # max_attempts counts the first try too: MAX_RETRIES retries on top
    return RetryPolicy(name="bench.window", max_attempts=MAX_RETRIES + 1,
                       base_delay_s=2.0, multiplier=2.0, max_delay_s=15.0,
                       jitter=0.25, retry_on=Exception)


def _make_supervisor() -> BackendSupervisor:
    """One BackendSupervisor per _timed_windows session: the rebuild-replay
    bookkeeping — backoff jitter RNG (ONE RNG, advancing per draw), typed
    backend_lost/backend_recovered journal events, flight-recorder
    breadcrumbs, clear_caches pacing — lives in a single object
    (resilience/elastic.py; this replaced the module-global _ACTIVE_POLICY
    shim, whose `or _retry_policy()` fallback could silently re-seed and
    re-draw the same "jittered" delay). retry_unclassified: a bench window
    is a replayable pure computation, so any Exception is worth one more
    attempt — except a version skew, which never heals mid-run."""
    return BackendSupervisor(policy=_retry_policy(), journal=_JOURNAL,
                             name="bench.window", retry_unclassified=True)


def _cost_analysis(step, multistep: int, batch_per_chip: int):
    """(flops_per_step_per_chip, bytes_per_step_per_chip, source).

    XLA's compiled cost analysis reports PER-DEVICE numbers under SPMD
    (verified: an 8-way sharded matmul reports 1/8 of the global flops), so
    everything here is per chip; divide by `batch_per_chip` — NOT the
    global batch — for per-image figures. Analytic fallback for flops,
    None for bytes, if unsupported. `step` is the AOT-compiled executable
    from build_bench."""
    try:
        ca = step.cost_analysis()
        flops = float(ca["flops"]) / multistep
        bytes_acc = ca.get("bytes accessed")
        bytes_acc = float(bytes_acc) / multistep if bytes_acc else None
        if flops > 0:
            return flops, bytes_acc, "xla_cost_analysis"
    except Exception as e:
        _log(f"cost analysis unavailable ({type(e).__name__}: {e}); "
             "using analytic flops")
    return RESNET50_TRAIN_FLOPS_PER_IMAGE * batch_per_chip, None, "analytic"


def _peak_flops(device_kind: str) -> float:
    """bf16 peak from the one table (core/backend.py); an unknown
    device_kind raises."""
    from deep_vision_tpu.core.backend import device_peaks

    return device_peaks(device_kind).bf16_flops


def _timed_windows(batch_per_chip: int, multistep: int):
    """Run warmup + WINDOWS timed windows with transient-failure retry.

    Returns (per-step wall seconds list, step, state, batch, batch_size,
    n_chips, devices, errors). On a failure ALL windows are replayed on
    the rebuilt step: windows timed before the failure may have run on a
    backend already going bad, and mixing them into the median would skew
    the headline. Only if the retry budget exhausts with zero
    healthy-session windows do the pre-failure windows feed the median,
    flagged in `errors` as degraded.
    """
    dispatches = max(1, math.ceil(TIMED_STEPS / multistep))
    steps_per_window = dispatches * multistep
    sup = _make_supervisor()
    errors = []
    window_dts = []
    stale_dts = []  # pre-failure windows: degraded fallback only
    built = None
    last_good = None  # survives rebuild failures: completed windows stay
                      # attributed to a real (step, ..., devices) tuple
    attempt = 0
    recovered_noted = False
    while len(window_dts) < WINDOWS:
        try:
            if built is None:
                step, state, batch, batch_size, n_chips, devices = build_bench(
                    batch_per_chip, multistep
                )
                built = True
                t0 = time.perf_counter()
                warm_dispatches = max(1, math.ceil(WARMUP_STEPS / multistep))
                for _ in range(warm_dispatches):
                    state, loss = step(state, batch)
                jax.block_until_ready(loss)
                _log(f"warmup {time.perf_counter() - t0:.1f}s "
                     f"(batch={batch_size}, multistep={multistep})")
                last_good = [step, state, batch, batch_size, n_chips, devices]
            w = len(window_dts)
            t0 = time.perf_counter()
            for _ in range(dispatches):
                state, loss = step(state, batch)
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            _log(f"window {w}: {dt / steps_per_window * 1e3:.1f} ms/step")
            window_dts.append(dt / steps_per_window)
            if attempt and not recovered_noted:
                # a completed window on the rebuilt step = the outage is
                # over; journaled as a typed backend_recovered event
                sup.on_recovered(attempt)
                recovered_noted = True
            # the step donates its state input: refresh the snapshot so the
            # returned state is the LIVE buffer, not a donated husk
            last_good[1] = state
        except KeyboardInterrupt:
            raise
        except Exception as e:
            attempt += 1
            errors.append(f"{type(e).__name__}: {e}")
            _log(f"transient failure #{attempt} ({errors[-1][:200]})")
            # classification + budget + typed backend_lost event + the
            # shared retry event, all through the supervisor
            retrying = sup.on_failure(attempt, e, context="bench.window")
            recovered_noted = False
            if window_dts:
                stale_dts = window_dts
                window_dts = []  # discard pre-failure windows: one healthy
                                 # session only feeds the median
            if not retrying:
                _log("not retrying: budget exhausted or unretryable "
                     "(version skew never heals mid-run)")
                break
            built = None  # rebuild: donated/invalid buffers are gone
            sup.recover(attempt)  # breadcrumb + backoff + cache clear
    if not window_dts and stale_dts:
        window_dts = stale_dts
        errors.append("degraded: median from pre-failure windows")
    if last_good is None:
        return window_dts, None, None, None, 0, 0, [], errors
    step, state, batch, batch_size, n_chips, devices = last_good
    return (window_dts, step, state, batch, batch_size, n_chips, devices,
            errors)


def train_result_stub(args) -> dict:
    """The degraded-case contract line for the train bench: what the driver
    parses if nothing past argument parsing ever completes."""
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": 0.0,
        "unit": "images/sec/chip",
        "vs_baseline": 0.0,
        "method": "wall_time",
        "batch_per_chip": args.batch,
        "multistep": args.multistep,
    }


#: RunJournal when --journal is set: the bench result then also lands as a
#: typed `bench` event (same schema tools/bench_models.py writes), so
#: BENCH_r0N trajectories are diffable with obs_report/check_journal and the
#: multistep/per-microstep fields are queryable instead of stdout-only
_JOURNAL = None


def _journal_result(result: dict) -> None:
    """Best-effort: the stdout contract line must never die to a journal
    I/O error."""
    if _JOURNAL is None:
        return
    try:
        _JOURNAL.bench(result.get("metric", "bench"), result)
        _JOURNAL.close()
    except Exception as e:
        _log(f"journal write failed ({type(e).__name__}: {e})")


def main(args, result: dict | None = None) -> None:
    if result is None:
        result = train_result_stub(args)
    try:
        try:
            result.update(_cold_start_fields())
            _log("cold-start probe: compile "
                 f"{result.get('warmup_compile_ms')}ms -> cache-warm "
                 f"{result.get('cold_start_ms')}ms")
        except Exception as e:  # the headline must survive a probe bug
            _log(f"cold-start probe failed ({type(e).__name__}: {e})")
        (window_dts, step, state, batch, batch_size, n_chips, devices,
         errors) = _timed_windows(args.batch, args.multistep)
        if errors:
            result["errors"] = errors[-3:]
        result["windows_completed"] = len(window_dts)
        if not window_dts:
            return  # degraded emission from finally
        _log(f"{n_chips}x {devices[0].device_kind} | resnet50 bf16 "
             f"batch={batch_size} image={IMAGE_SIZE}")

        wall_per_chip = batch_size / n_chips / float(np.median(window_dts))
        result["value"] = round(wall_per_chip, 1)
        result["vs_baseline"] = round(wall_per_chip / TARGET_PER_CHIP, 3)
        # per-MICROSTEP wall time + the dispatch arithmetic: without these a
        # multistep>1 round is incomparable to a multistep=1 one (the r0N
        # trajectory would silently mix steps-per-dispatch regimes)
        result["wall_ms_per_step"] = round(
            float(np.median(window_dts)) * 1e3, 3)
        result["dispatches_per_window"] = max(
            1, math.ceil(TIMED_STEPS / args.multistep))
        result["steps_per_dispatch"] = args.multistep

        # MFU / HBM traffic from XLA's post-fusion cost analysis (falls back
        # to analytic ResNet-50 flops). All per-chip: cost analysis is
        # per-device under SPMD and wall_per_chip is the per-chip rate.
        # NB "bytes accessed" is an UPPER BOUND on real HBM traffic: reads
        # served from VMEM-resident buffers still count, so the implied
        # bandwidth can exceed the 819 GB/s pin limit (batch 128 implies
        # ~946 GB/s — proof of the overcount; see
        # artifacts/batch_scaling_r04.json and the round-3 roofline
        # misread it caused).
        batch_per_chip = batch_size // n_chips
        flops_per_step, bytes_per_step, src = _cost_analysis(
            step, args.multistep, batch_per_chip
        )
        peak = _peak_flops(devices[0].device_kind)
        flops_per_image = flops_per_step / batch_per_chip
        result["model_flops_per_image"] = round(flops_per_image / 1e9, 2)
        result["flops_source"] = src
        result["mfu_wall_pct"] = round(
            100 * wall_per_chip * flops_per_image / peak, 1
        )
        if bytes_per_step is not None:
            result["hbm_gbytes_per_step_per_chip"] = round(
                bytes_per_step / 1e9, 2
            )
            result["hbm_gbytes_per_sec_per_chip"] = round(
                bytes_per_step / 1e9 * wall_per_chip / batch_per_chip, 1
            )

        # Device step time from a profiler trace
        dev_ms = _device_step_ms(step, state, batch, args.multistep)
        if dev_ms is not None:
            dev_per_chip = batch_size / n_chips / (dev_ms / 1e3)
            _log(f"device step {dev_ms:.1f} ms")
            result["device_ms_per_step"] = round(dev_ms, 3)  # per microstep
            result["device_images_per_sec_per_chip"] = round(dev_per_chip, 1)
            result["device_vs_baseline"] = round(
                dev_per_chip / TARGET_PER_CHIP, 3
            )
            result["mfu_device_pct"] = round(
                100 * dev_per_chip * flops_per_image / peak, 1
            )
    except KeyboardInterrupt:
        raise
    except Exception as e:
        result["errors"] = result.get("errors", []) + [
            f"{type(e).__name__}: {e}"
        ]
        _log(f"fatal: {type(e).__name__}: {e}")
    finally:
        _emit(result)


def load_xspace(tmpdir: str):
    """Parse the xplane.pb a jax.profiler trace left under `tmpdir`.

    Shared by the module-event timing here and tools/roofline.py's
    DMA-byte walk. TF ships stale generated protos; the pure-python parser
    accepts them (must be set before google.protobuf first loads)."""
    import glob

    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    path = glob.glob(
        os.path.join(tmpdir, "**", "*.xplane.pb"), recursive=True
    )[0]
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def _trace_module_events(step, state, batch, dispatches: int):
    """[(start_ps, duration_ps)] of device "XLA Modules" events from one
    traced window of `dispatches` executions, sorted by start time.

    The trace's "/device:TPU:0" plane holds one event per executed program
    whose duration is the device-side execution time of the whole jitted
    step (matmuls, DMAs and stalls included — everything but host
    dispatch overhead). Raises on trace failure; callers decide the
    fallback.
    """
    import shutil
    import tempfile

    tmpdir = tempfile.mkdtemp(prefix="dv_bench_trace_")
    try:
        jax.profiler.start_trace(tmpdir)
        for _ in range(dispatches):
            state, loss = step(state, batch)
        jax.block_until_ready(loss)
        jax.profiler.stop_trace()
        xs = load_xspace(tmpdir)
        events = []
        for plane in xs.planes:
            if not plane.name.startswith("/device:TPU"):
                continue
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for ev in line.events:
                    start_ps = line.timestamp_ns * 1000 + ev.offset_ps
                    events.append((start_ps, ev.duration_ps))
        events.sort()
        return events
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def _device_step_ms(step, state, batch, multistep: int = 1, n_steps: int = 10):
    """Median on-device ms/step from a jax.profiler trace (None on failure)."""
    dispatches = max(1, math.ceil(n_steps / multistep))
    try:
        events = _trace_module_events(step, state, batch, dispatches)
        durs = [d / 1e9 for _, d in events]  # ps -> ms
        if len(durs) < dispatches // 2:
            return None
        return float(np.median(durs)) / multistep
    except Exception as e:  # no TF proto, trace unsupported on backend, ...
        print(f"bench: no device trace ({type(e).__name__}: {e}); "
              "falling back to wall time", file=sys.stderr)
        return None


def sweep_main(out_path: str) -> None:
    """Dispatch-overhead / batch sweep: interleaved windows across configs.

    Only interleaved same-process windows give trustworthy relative
    numbers. Builds every config up front, then round-robins the timed
    windows. The wall-minus-device gap this reports is the per-host-sync
    latency amortized over the window, not a per-dispatch cost. For the batch scaling curve
    proper, use tools/batch_sweep.py.
    """
    configs = [(256, 1), (256, 8), (512, 1), (512, 8)]
    built = {}
    errors = []
    for bpc, ms in configs:
        try:
            step, state, batch, batch_size, n_chips, devices = build_bench(
                bpc, ms
            )
            t0 = time.perf_counter()
            warm_dispatches = max(1, math.ceil(WARMUP_STEPS / ms))
            for _ in range(warm_dispatches):
                state, loss = step(state, batch)
            jax.block_until_ready(loss)
            _log(f"sweep warmup b{bpc} k{ms}: "
                 f"{time.perf_counter() - t0:.1f}s")
            built[(bpc, ms)] = [step, state, batch, batch_size, n_chips, []]
        except KeyboardInterrupt:
            raise
        except Exception as e:  # config dropped, sweep continues
            errors.append(f"warmup b{bpc} k{ms}: {type(e).__name__}: {e}")
            _log(errors[-1][:200])
    for w in range(WINDOWS):
        for key in list(built):
            step, state, batch, batch_size, n_chips, dts = built[key]
            ms = key[1]
            dispatches = max(1, math.ceil(TIMED_STEPS / ms))
            try:
                t0 = time.perf_counter()
                for _ in range(dispatches):
                    state, loss = step(state, batch)
                jax.block_until_ready(loss)
                dts.append((time.perf_counter() - t0) / (dispatches * ms))
                built[key][1] = state
            except KeyboardInterrupt:
                raise
            except Exception as e:  # donated state is gone: drop the config
                errors.append(
                    f"window b{key[0]} k{ms}: {type(e).__name__}: {e}"
                )
                _log(errors[-1][:200])
                del built[key]
    rows = []
    for (bpc, ms), (step, state, batch, batch_size, n_chips, dts) in (
            built.items()):
        if not dts:
            continue
        wall_ms = float(np.median(dts)) * 1e3
        try:
            dev = _device_step_ms(step, state, batch, ms)
        except Exception:
            dev = None
        rows.append({
            "batch_per_chip": bpc,
            "steps_per_dispatch": ms,
            "wall_ms_per_step": round(wall_ms, 2),
            "device_ms_per_step": round(dev, 2) if dev else None,
            "dispatch_overhead_ms_per_step": (
                round(wall_ms - dev, 2) if dev else None
            ),
            "wall_images_per_sec_per_chip": round(
                batch_size / n_chips / wall_ms * 1e3, 1
            ),
        })
        _log(f"sweep b{bpc} k{ms}: wall {wall_ms:.1f} ms/step, "
             f"device {dev and round(dev, 1)} ms/step")
    artifact = {
        "what": "wall vs device per-step time across batch size and "
                "steps-per-dispatch (lax.scan superstep), interleaved "
                "windows, one process",
        "rows": rows,
    }
    try:
        artifact["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        pass
    if errors:
        artifact["errors"] = errors[-5:]
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
    # the one-line JSON contract holds even for a fully-failed sweep
    _emit({"metric": "dispatch_sweep", "artifact": out_path,
           "rows": rows, **({"errors": errors[-3:]} if errors else {})})


def cli(argv=None) -> int:
    """The command line: 0 = a complete healthy measurement, 1 = a degraded
    line or a failed phase (the line is still printed), 2 = no TPU (no
    line)."""
    global _JOURNAL
    parser = argparse.ArgumentParser()
    parser.add_argument("--data", choices=["host", "fused"], default=None,
                        help="benchmark the input pipeline instead of the "
                             "train step")
    parser.add_argument("--num-procs", type=int, default=0,
                        help="decode worker processes (0 = thread pool)")
    parser.add_argument("--batch", type=int, default=BATCH_PER_CHIP,
                        help="per-chip batch size")
    parser.add_argument("--multistep", type=int, default=1,
                        help="optimizer steps per dispatch (lax.scan "
                             "superstep)")
    parser.add_argument("--sweep", metavar="OUT_JSON", default=None,
                        help="run the dispatch-overhead/batch sweep and "
                             "write the artifact JSON")
    parser.add_argument("--multichip", action="store_true",
                        help="MULTICHIP scaling mode: time a table-sharded "
                             "train step at data={1,2,4,8} sub-meshes and "
                             "emit the scaling-efficiency block (throughput, "
                             "per-device examples/s, efficiency vs the "
                             "1-device baseline) — the perf number that "
                             "replaces the dryrun's loss=OK smoke "
                             "(BENCH_MULTICHIP_STEPS/_BATCH tune the "
                             "windows)")
    parser.add_argument("--flight-dir", default=None, metavar="DIR",
                        help="flight recorder (obs/flight.py): dump a "
                             "postmortem bundle under DIR if the bench "
                             "dies (recovery breadcrumbs included)")
    parser.add_argument("--journal", default=None, metavar="PATH",
                        help="also write the result as a typed `bench` "
                             "journal event (obs/journal.py schema; "
                             "validate with tools/check_journal.py)")
    args = parser.parse_args(argv)
    # JAX's compile cache is placed before anything compiles
    from deep_vision_tpu.core.excache import place_compile_cache

    place_compile_cache()
    if args.journal:
        from deep_vision_tpu.obs.journal import RunJournal

        _JOURNAL = RunJournal(args.journal, kind="bench")
        _JOURNAL.manifest(config={"tool": "bench", "batch": args.batch,
                                  "multistep": args.multistep,
                                  "data": args.data, "sweep": args.sweep})
    if args.flight_dir:
        from deep_vision_tpu.obs import FlightRecorder, set_flight

        set_flight(FlightRecorder(args.flight_dir))
    if args.data:
        stub = {
            "metric": f"imagenet_pipeline_{args.data}_images_per_sec_per_core",
            "value": 0.0, "unit": "images/sec/core", "vs_baseline": 0.0,
        }
        # 'host' mode never touches a device: no liveness gate needed
        run = lambda: data_main(args.data, args.num_procs)
        needs_device = args.data == "fused"
    elif args.multichip:
        stub = multichip_result_stub()
        run = lambda: multichip_main(stub)
        needs_device = True
    elif args.sweep:
        stub = {"metric": "dispatch_sweep", "artifact": args.sweep,
                "rows": []}
        run = lambda: sweep_main(args.sweep)
        needs_device = True
    else:
        stub = train_result_stub(args)
        run = lambda: main(args, stub)
        needs_device = True
    try:
        if needs_device:
            _log(f"backend liveness probe (budget {INIT_BUDGET_S:.0f}s)")
            ok, err = _backend_alive(INIT_BUDGET_S)
            if ok and jax.devices()[0].platform != "tpu":
                # never a CPU rate under a device metric's name
                print(f"bench: needs a TPU; JAX found platform "
                      f"{jax.devices()[0].platform!r}. No result.",
                      file=sys.stderr)
                return 2
            if not ok:
                stub["errors"] = [err]
                _emit(stub)
                return 1
        run()
    except KeyboardInterrupt:
        raise
    except Exception as e:
        # the contract line must exist even for failures outside main()'s
        # own try/finally (e.g. a fixture-dir write error in data_main)
        stub["errors"] = stub.get("errors", []) + [f"{type(e).__name__}: {e}"]
        _log(f"fatal: {type(e).__name__}: {e}")
        try:
            from deep_vision_tpu.obs import flight as _flight

            _flight.emergency_dump("crash")
        except Exception:
            pass
        _emit(stub)
    return 1 if _EMITTED is None or degraded(_EMITTED) else 0


if __name__ == "__main__":
    sys.exit(cli())
