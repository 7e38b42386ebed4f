#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # from the repo root, on a machine with a TPU

One process (a chip belongs to one process at a time) drives the main path
once, through the entry points a user would call, at the full width of
ResNet-50, on every local device:

  train    `train_cli.main(["-m", "resnet50", "--fake-data", ...])`: s2d stem,
           1000 classes, 224 px, batch 128 per chip (the config's 256 is a
           global batch; depth of the RUN is cut to 24 steps, never the
           model), preflight on, journal and checkpoints under the output
           directory. Then a second Trainer, built the way main builds it,
           resumes the checkpoint, shows the compiled step, and times a
           window closed by block_until_ready.
  loader   8 record-reading worker processes started beside the live TPU
           client (they pin themselves to the CPU backend).
  kernels  the Pallas kernels, compiled (interpret=False), against
           their in-repo references at the shapes production uses.
  serve    Transport -> admission -> queue -> Engine with resnet50 over HTTP,
           the Engine warmed from the AOT executable store a first one filled.

Any failed check raises: no phase's exception is caught and carried past.
The numbers printed are first observations of today's code on the named
device, not claims. The script depends on no untracked file: `--fake-data`
needs no dataset and no `native/libdvtpu.so`; what it writes goes under
`chiprun_out/chip_smoke/`, and JAX's compile cache where
`core/excache.place_compile_cache` puts it.

Exit code 0 and a last stdout line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}`
only when every phase passed on a TPU; non-zero and no such line otherwise
(no accelerator, or the rest of the repo missing).
"""
from __future__ import annotations

import functools
import http.client
import json
import os
import shutil
import sys
import time

PER_CHIP_BATCH = 128
FAKE_BATCHES = 4
EPOCHS = 6
OUT_DIR = os.path.join("chiprun_out", "chip_smoke")


def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# -- train -------------------------------------------------------------------

def phase_train(devices) -> dict:
    import jax
    import numpy as np

    from deep_vision_tpu import train_cli
    from deep_vision_tpu.configs import get_config
    from deep_vision_tpu.data.records import best_reader
    from deep_vision_tpu.obs import costmodel
    from deep_vision_tpu.obs.journal import read_journal

    n = len(devices)
    batch = PER_CHIP_BATCH * n
    ckpt_dir = os.path.join(OUT_DIR, "ckpt")
    journal_path = os.path.join(OUT_DIR, "train.journal.jsonl")
    _say(f"record reader: {best_reader().__name__} "
         "(unused here: --fake-data reads no records)")

    t0 = time.perf_counter()
    rc = train_cli.main([
        "-m", "resnet50", "--fake-data", "--fake-batches", str(FAKE_BATCHES),
        "--batch-size", str(batch), "--epochs", str(EPOCHS),
        "--ckpt-dir", ckpt_dir, "--journal", journal_path,
        "--telemetry-sample-every", "4"])
    main_s = time.perf_counter() - t0
    _check(rc == 0, f"train_cli.main returned {rc}")

    steps = [e for e in read_journal(journal_path) if e["event"] == "step"]
    _check(len(steps) == FAKE_BATCHES * EPOCHS,
           f"journal has {len(steps)} step rows, expected "
           f"{FAKE_BATCHES * EPOCHS}")
    losses = [s["metrics"]["loss"] for s in steps]
    _check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    first, last = (float(np.mean(losses[:FAKE_BATCHES])),
                   float(np.mean(losses[-FAKE_BATCHES:])))
    # the fake batches repeat every epoch: the last pass over them must
    # beat the first, and the last step the first
    _check(last < first and losses[-1] < losses[0],
           f"loss did not fall: first epoch {first:.4f} (step 1 "
           f"{losses[0]:.4f}) -> last epoch {last:.4f} (last step "
           f"{losses[-1]:.4f})")
    # warm-up is the first epoch plus one step: the first step of epoch 2
    # carries the compile of the eval step and the first checkpoint save
    warm = FAKE_BATCHES + 1
    late = [s["step"] for s in steps[warm:] if s.get("compile_ms")]
    _check(not late, f"recompiles after warm-up at steps {late}")
    compile_s = sum(s.get("compile_ms", 0.0) for s in steps[:warm]) / 1e3
    fit_ms = float(np.median([s["step_time_ms"] for s in steps[warm:]]))
    # ... but not a second compile of the train step itself (its own time
    # excludes the eval and the save, which run between steps)
    _check(steps[warm - 1]["step_time_ms"] < 10 * fit_ms,
           f"first step of epoch 2 took {steps[warm - 1]['step_time_ms']:.0f}"
           f" ms against a steady {fit_ms:.0f} ms: the step recompiled")

    # a second Trainer, assembled as main assembles it: resume, inspect
    cfg = get_config("resnet50")
    cfg.batch_size, cfg.epochs = batch, EPOCHS
    train_fn, _ = train_cli.build_dataloaders(
        cfg, "./dataset", True, FAKE_BATCHES, 0)
    trainer = train_cli.build_trainer(cfg, train_fn, ckpt_dir)
    _check(dict(trainer.mesh.shape) == {"data": n, "model": 1},
           f"mesh {dict(trainer.mesh.shape)} is not all {n} devices on data")
    next_epoch = trainer.resume()
    _check(int(trainer.state.step) == len(steps) and next_epoch == EPOCHS,
           f"resume restored step {int(trainer.state.step)} / epoch "
           f"{next_epoch}, expected {len(steps)} / {EPOCHS}")

    placed = trainer._place_one(train_fn()[0])  # on the mesh, as fit feeds it
    with trainer._mesh_context():
        compiled = trainer._train_step.lower(trainer.state,
                                             placed.data).compile()
    text = compiled.as_text()
    n_custom = text.count("tpu_custom_call")
    _check(n_custom == 0, f"{n_custom} tpu_custom_call in the compiled "
                          "train step: ResNet's step is XLA's own fusions")

    inventory = costmodel.collective_inventory(text)
    by_kind: dict = {}
    for c in inventory:
        k = by_kind.setdefault(c["kind"], {"count": 0, "bytes": 0})
        k["count"] += 1
        k["bytes"] += c["bytes"]
    grad_bytes = costmodel.tree_bytes(trainer.state.params)
    if n > 1:
        ar = by_kind.get("all-reduce", {"bytes": 0})["bytes"]
        _check(0.95 * grad_bytes <= ar <= 1.10 * grad_bytes,
               f"all-reduce bytes {ar} vs gradient tree {grad_bytes}")
        # BN's statistics reduce per shard: nothing may gather an activation
        big = [c for c in inventory if c["kind"] == "all-gather"
               and c["bytes"] >= 1 << 20]
        _check(not big, f"activation-sized all-gathers: {big[:3]}")

    # steady window on the resumed trainer, closed by block_until_ready
    # (Trainer.fit's own per-step figure above includes its host fetches)
    window = 20
    for _ in range(2):
        metrics = trainer.train_step(placed)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(window):
        metrics = trainer.train_step(placed)
    jax.block_until_ready((trainer.state, metrics))
    step_ms = (time.perf_counter() - t0) / window * 1e3
    _check(np.isfinite(float(metrics["loss"])), "non-finite loss after resume")
    trainer.close()
    shutil.rmtree(ckpt_dir)  # ~300 MB a step: not worth bringing back

    stats = [d.memory_stats() for d in devices]
    _check(all(s["bytes_in_use"] > 64 << 20 for s in stats),
           f"a device holds no state: {[s['bytes_in_use'] for s in stats]}")
    param_dtype = jax.tree_util.tree_leaves(trainer.state.params)[0].dtype
    out = {
        "global_batch": batch, "steps": len(steps),
        "loss_first_epoch": round(first, 4), "loss_last_epoch": round(last, 4),
        "main_wall_s": round(main_s, 1),
        "compile_s_in_warmup": round(compile_s, 1),
        "fit_step_ms_median": round(fit_ms, 2),
        "step_ms_block_until_ready": round(step_ms, 2),
        "img_per_s_per_chip": round(PER_CHIP_BATCH / step_ms * 1e3, 1),
        "compute_dtype": f"{cfg.model_kwargs.get('dtype') or 'float32'} "
                         f"(params {param_dtype})",
        "peak_bytes_in_use": [s["peak_bytes_in_use"] for s in stats],
        "xla_planned_step_bytes": (lambda m: m.argument_size_in_bytes
                                   + m.output_size_in_bytes
                                   - m.alias_size_in_bytes
                                   + m.temp_size_in_bytes)(
                                       compiled.memory_analysis()),
        "memory_stats_device0": {k: v for k, v in stats[0].items()
                                 if isinstance(v, int)},
        "tpu_custom_calls": n_custom,
        "collectives": by_kind, "grad_tree_bytes": grad_bytes,
    }
    _say(f"train: {json.dumps(out)}")
    return out


# -- loader ------------------------------------------------------------------

def _label_schema(features):
    return {"label": features["image/class/label"][0]}


def phase_loader() -> dict:
    """8 spawned record workers beside the process that holds the chip."""
    from deep_vision_tpu.data import DataLoader, RecordDataset
    from deep_vision_tpu.data.example_codec import encode_example
    from deep_vision_tpu.data.records import RecordWriter

    root = os.path.join(OUT_DIR, "records")
    os.makedirs(root, exist_ok=True)
    shards, per_shard = 8, 16
    for s in range(shards):
        with RecordWriter(os.path.join(root, f"train-{s}")) as w:
            for i in range(per_shard):
                w.write(encode_example({
                    "image/encoded": [b""],
                    "image/class/label": [s * per_shard + i]}))
    ds = RecordDataset(os.path.join(root, "train-*"), schema=_label_schema)
    t0 = time.perf_counter()
    seen = []
    for batch in DataLoader(ds, batch_size=16, num_procs=shards,
                            drop_remainder=False):
        seen.extend(int(x) for x in batch["label"])
    _check(sorted(seen) == list(range(shards * per_shard)),
           f"8-worker loader yielded {len(seen)} of {shards * per_shard}")
    out = {"workers": shards, "samples": len(seen),
           "wall_s": round(time.perf_counter() - t0, 1)}
    _say(f"loader: {json.dumps(out)}")
    return out


# -- kernels -----------------------------------------------------------------

def _timed(fn, *args):
    """(result, best-of-3 seconds after one warm-up), block_until_ready."""
    import jax

    result = jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return result, best


@functools.lru_cache(maxsize=None)
def _compare():
    """The jitted (got, want, tol) -> (finite, bad fraction, worst, scale)
    reduction: one function object, so each shape compiles once."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def compare(got, want, tol):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        err = jnp.abs(got - want)
        scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-6)
        return (jnp.all(jnp.isfinite(got)), jnp.mean(err > tol * scale),
                jnp.max(err), scale)

    return compare


def _close(got, want, tol: float, what: str, max_outliers: float = 0.0):
    """|got - want| <= tol * max|want| elementwise, but for a fraction
    `max_outliers` of elements (a ReLU mask may flip where the
    pre-activation is within rounding of zero). Reduced on the device:
    the operands run to hundreds of MB."""
    import jax
    import jax.numpy as jnp

    _check(got.shape == want.shape, f"{what}: shape {got.shape} != "
                                    f"{want.shape}")
    finite, bad, worst, scale = jax.device_get(
        _compare()(jnp.asarray(got), jnp.asarray(want), tol))
    _check(finite, f"{what}: non-finite values")
    _check(bad <= max_outliers,
           f"{what}: {float(bad):.3g} of {got.size} elements off by more "
           f"than {tol:g} x max|ref| (worst {float(worst):.3g}, max|ref| "
           f"{float(scale):.3g})")


def phase_kernels() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deep_vision_tpu.ops import nms as lax_nms
    from deep_vision_tpu.ops.pallas import flash_attention as _  # noqa: F401
    from deep_vision_tpu.ops.pallas.nms import pallas_nms

    fa = sys.modules["deep_vision_tpu.ops.pallas.flash_attention"]
    out: dict = {"flash_ms": {}, "flash_off": {}, "nms_ms": {}}
    key = jax.random.PRNGKey(0)

    # flash attention, fwd+bwd, 12 heads x 64, bf16
    def attn_fwd_bwd(impl):
        def run(q, k, v, g):
            out, vjp = jax.vjp(impl, q, k, v)
            return out, vjp(g)
        return jax.jit(run)

    # `alike`: every token a common vector (3 x a unit normal one) plus its
    # own, in q, k and v, at a tenth of the scale: near-uniform attention
    # over tokens that differ little, a deep block at initialisation. There
    # dq is what the keys' deviations from their mean leave, and a `delta`
    # from a rounded output put it 47% off its norm where unit-normal
    # inputs read 0.2% (PERF.md §6, PR 34). Held by the norm, to 3%: the
    # MXU takes p and ds rounded to bf16, which leaves dq 1.7% off here
    # (the dense bf16 expression: 1.0%) and single elements further.
    rel = jax.jit(lambda a, b: jnp.linalg.norm(
        a.astype(jnp.float32) - b.astype(jnp.float32))
        / jnp.linalg.norm(b.astype(jnp.float32)))
    for t, b in ((1024, 2), (4096, 1)):
        for causal, alike in ((False, False), (True, False), (False, True)):
            ks = jax.random.split(jax.random.fold_in(key, t + causal), 7)
            q, k, v, g = (jax.random.normal(kk, (b, t, 12, 64), jnp.bfloat16)
                          for kk in ks[:4])
            scale = 64 ** -0.5
            if alike:
                scale *= 0.1
                q, k, v = (x + 3 * jax.random.normal(
                    kk, (1, 1, 12, 64), jnp.bfloat16)
                    for x, kk in zip((q, k, v), ks[4:]))
            kernel = attn_fwd_bwd(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=causal, scale=scale, interpret=False))
            reference = attn_fwd_bwd(lambda q, k, v: fa._dense_reference(
                q, k, v, causal, scale))
            (o, grads), secs = _timed(kernel, q, k, v, g)
            ref_o, ref_grads = reference(q, k, v, g)
            tag = (f"T{t}/{'causal' if causal else 'bidirectional'}"
                   + "/alike" * alike)
            _close(o, ref_o, 2e-2, f"flash {tag} out")
            for name, got, want in zip(("dq", "dk", "dv"), grads, ref_grads):
                if not alike:
                    _close(got, want, 3e-2, f"flash {tag} {name}")
                off = float(rel(got, want))
                _check(off < 0.03, f"flash {tag} {name}: {off:.3g} of the "
                                   f"reference's norm away")
                out["flash_off"][f"{tag}/{name}"] = round(off, 5)
            out["flash_ms"][tag] = round(secs * 1e3, 3)

    # the single-block kernel alone at ViT-B/16's shape (batch 128, 196
    # tokens, 12 heads x 64), fwd+bwd through the qkv-in / o-out interface,
    # beside XLA's dense expression on the same operands: a kernel fast
    # alone and slow in the step is PERF.md's finding to repeat or refute.
    # float32 io rides along at batch 16: `train.py`'s default dtype.
    def dense_qkv(qkv):
        b, t, _ = qkv.shape
        q, k, v = (qkv.reshape(b, t, 3, 12, 64)[:, :, i] for i in range(3))
        s = jnp.einsum("bthd,bshd->bhts", q, k) * 64 ** -0.5
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhts,bshd->bthd", p, v).reshape(b, t, 768)

    def qkv_fwd_bwd(impl):
        def run(qkv, bias, g):
            out, vjp = jax.vjp(impl, qkv, bias)
            return out, vjp(g)
        return jax.jit(run)

    out["fused_ms"] = {}
    for dtype, b, tol in ((jnp.bfloat16, 128, 3e-2), (jnp.float32, 16, 2e-2)):
        ks = jax.random.split(jax.random.fold_in(key, 196 + b), 3)
        qkv = jax.random.normal(ks[0], (b, 196, 2304), dtype)
        g = jax.random.normal(ks[1], (b, 196, 768), dtype)
        bias = jax.random.normal(ks[2], (2304,), jnp.float32)
        (o, (dqkv, dbias)), secs = _timed(qkv_fwd_bwd(
            lambda x, bias: fa.fused_attention(x, 12, bias, interpret=False)),
            qkv, bias, g)
        (ref_o, (ref_dqkv, _)), ref_secs = _timed(qkv_fwd_bwd(
            lambda x, bias: dense_qkv(x + bias.astype(x.dtype))), qkv, bias, g)
        tag = f"B{b}/T196/{jnp.dtype(dtype).name}"
        _close(o, ref_o, tol, f"fused {tag} out")
        _close(dqkv, ref_dqkv, tol, f"fused {tag} d(qkv)")
        # the kernel's own sums over each image's tokens, from its float32
        # tiles, beside the rounded d(qkv) summed in float32
        _close(dbias, jnp.sum(ref_dqkv.astype(jnp.float32), axis=(0, 1)), tol,
               f"fused {tag} d(bias)")
        out["fused_ms"][tag] = round(secs * 1e3, 3)
        out["fused_ms"][tag + "/xla_dense"] = round(ref_secs * 1e3, 3)

    # NMS at YOLOv3-416 scale: 10647 candidates, 100 detections. Boxes on
    # a 1/64 grid make every area, intersection and union exact in f32, so
    # an IoU is a fraction with a denominator <= 288 — never within 7e-4 of
    # the 0.501 threshold: index agreement cannot hinge on how the last bit
    # of a division rounds.
    n, det, iou = 10647, 100, 0.501
    reference = jax.jit(jax.vmap(
        lambda bx, sc: lax_nms._nms_single(bx, sc, det, iou, 0.5)))
    kernel = jax.jit(lambda bx, sc: pallas_nms(
        bx, sc, det, iou, 0.5, interpret=False))
    for b in (1, 8):
        ks = jax.random.split(jax.random.fold_in(key, b), 3)
        xy = jax.random.randint(ks[0], (b, n, 2), 0, 52) / 64.0
        wh = jax.random.randint(ks[1], (b, n, 2), 4, 13) / 64.0
        boxes = jnp.concatenate([xy, xy + wh], axis=-1)
        scores = jax.random.uniform(ks[2], (b, n))
        (sel_s, sel_i), secs = _timed(kernel, boxes, scores)
        ref_s, ref_i = reference(boxes, scores)
        _check(np.array_equal(np.asarray(sel_i), np.asarray(ref_i)),
               f"nms batch {b}: selected indices differ from _nms_single")
        _check(int((np.asarray(sel_i) >= 0).sum()) > b * det // 2,
               f"nms batch {b}: too few detections to be a test")
        _close(sel_s, ref_s, 1e-6, f"nms batch {b} scores")
        out["nms_ms"][f"B{b}"] = round(secs * 1e3, 3)
    _say(f"kernels: {json.dumps(out)}")
    return out


# -- serve -------------------------------------------------------------------

def resnet50_engine(journal=None, registry=None, excache=None):
    """An unwarmed `Engine` serving full-width resnet50 (224 px, weights from
    a seed, buckets 1 and 4). Module-level: tools/chip_fleet.py hands it to
    `ProcReplicaPool` as the builder every replica process runs."""
    import jax
    import numpy as np

    from deep_vision_tpu import train_cli
    from deep_vision_tpu.configs import get_config
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.serve.engine import Engine

    cfg = get_config("resnet50")
    shape = train_cli.model_input_shape(cfg)  # 224 px, space-to-depth'd
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      **cfg.model_kwargs)
    variables = jax.jit(lambda k: model.init(
        k, np.zeros((1, *shape), np.float32), train=False))(
            jax.random.PRNGKey(0))

    def predict(variables, images):
        return {"logits": model.apply(variables, images, train=False)}

    engine = Engine(journal=journal, registry=registry, excache=excache)
    engine.register("resnet50", predict, variables, input_shape=shape,
                    buckets=(1, 4))
    return engine


def phase_serve() -> dict:
    import jax
    import numpy as np

    from deep_vision_tpu.core.excache import ExecutableCache
    from deep_vision_tpu.serve.admission import AdmissionController
    from deep_vision_tpu.serve.engine import Engine
    from deep_vision_tpu.serve.router import Server
    from deep_vision_tpu.serve.transport import Transport

    # two engines over one AOT executable store (core/excache.py): the
    # first pays the compiler and stores, the second — the one that serves —
    # must load every executable back and compile nothing
    store = os.path.join(OUT_DIR, "excache")
    first = resnet50_engine(excache=ExecutableCache(store))
    cold = first.warmup()
    model = first.entry("resnet50")
    engine = Engine(excache=ExecutableCache(store))
    engine.register(model.name, model.fn, model.variables,
                    input_shape=model.input_shape, buckets=model.buckets)
    warm = engine.warmup()
    _check(cold["cache_hits"] == 0 and warm["cache_hits"] == warm["pairs"]
           and warm["backend_compiles"] == 0,
           f"executable cache round trip: cold {cold}, warm {warm}")
    reference = jax.jit(model.fn)
    shape, variables = model.input_shape, model.variables
    server = Server(engine, max_wait_ms=2.0).start()
    transport = Transport(server, admission=AdmissionController()).start()
    rng = np.random.RandomState(0)
    latencies = []
    try:
        for i in range(4):
            image = rng.rand(*shape).astype(np.float32)
            conn = http.client.HTTPConnection("127.0.0.1", transport.port,
                                              timeout=120)
            t0 = time.perf_counter()
            conn.request("POST", "/v1/resnet50",
                         body=json.dumps({"image": image.tolist()}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            latencies.append((time.perf_counter() - t0) * 1e3)
            conn.close()
            _check(resp.status == 200, f"request {i}: HTTP {resp.status} "
                                       f"{str(payload)[:200]}")
            logits = np.asarray(payload["outputs"]["logits"], np.float32)
            want = np.asarray(reference(variables, image[None])["logits"][0])
            _close(logits, want, 1e-3, f"request {i} logits")
            _check(logits.shape == (1000,),
                   f"request {i}: logits shape {logits.shape}")
    finally:
        transport.close()
        drained = server.drain("close")
    _check(drained["outcome"] == "flushed", f"server drain: {drained}")
    shutil.rmtree(store)
    out = {"buckets": warm["pairs"],
           "warmup_compile_s": round(cold["compile_ms_total"] / 1e3, 1),
           "warmup_from_excache_s": round(warm["compile_ms_total"] / 1e3, 1),
           "requests_200": len(latencies),
           "http_latency_ms": [round(x, 1) for x in latencies]}
    _say(f"serve: {json.dumps(out)}")
    return out


# -- driver ------------------------------------------------------------------

def main() -> int:
    # the repo first: in a directory that holds only this file the import
    # fails here, before any device is touched
    from deep_vision_tpu.core.excache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devices[0].platform!r} ({len(devices)} x "
              f"{devices[0].device_kind}). No result.", file=sys.stderr)
        return 2
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except (ImportError, AttributeError):
        libtpu_version = "unknown"
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _say(f"device {json.dumps(device)} jax {jax.__version__} jaxlib "
         f"{jaxlib.__version__} libtpu {libtpu_version} compile cache "
         f"{cache_dir}")
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)

    t_start = time.perf_counter()
    report = {"device": device}
    for name, phase, args in (("train", phase_train, (devices,)),
                              ("loader", phase_loader, ()),
                              ("kernels", phase_kernels, ()),
                              ("serve", phase_serve, ())):
        t0 = time.perf_counter()
        report[name] = phase(*args)
        report[name]["phase_wall_s"] = round(time.perf_counter() - t0, 1)
        _say(f"phase {name} passed in {report[name]['phase_wall_s']} s")
    report["compile_cache"] = {"dir": cache_dir, **cache}
    report["wall_s"] = round(time.perf_counter() - t_start, 1)
    _say(f"compile cache: {cache['hits']} hits, {cache['misses']} misses "
         f"in {cache_dir}")
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    _say(f"all phases passed in {report['wall_s']} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
