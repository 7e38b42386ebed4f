"""Cold-path tier-1 suite: the persistent executable cache
(core/excache.py) + int8 serving quantization (serve/quantize.py).

Cache correctness: round-trip bit-identity, version/platform/mesh-key
invalidation (a skewed entry journals `excache_invalid` and falls
through to the compiler — never loads), corrupt-entry quarantine,
concurrent warmers over one dir (locksmith-armed), Engine warmup
integration (zero backend compiles over a warm cache), pool
fresh-engine respawn, and the Trainer's cached step dispatch. Int8:
dequant parity, the accuracy-delta gate firing on a poisoned
calibration, scale sidecar round-trip through the crc32c checkpoint,
and hot-swap of a re-quantized tree through the existing machinery.
The multi-process zero-compile proof is `make cache-smoke`
(tools/cache_smoke.py); everything here is in-process tier-1.
"""
import json
import os
import pickle
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.core.excache import (
    EXCACHE_INVALID_REASONS,
    ExecutableCache,
    env_fingerprint,
)
from deep_vision_tpu.obs import RunJournal, locksmith, read_journal
from deep_vision_tpu.obs.registry import Registry
from deep_vision_tpu.obs.stepclock import recompile_count
from deep_vision_tpu.serve import Engine
from deep_vision_tpu.serve.quantize import (
    QuantizationRejected,
    apply_scales,
    calibrate_and_quantize,
    dequantize_variables,
    quantize_variables,
    quantized_fn,
    scales_host_state,
)

IMG = (4, 4, 1)


def toy_fn(variables, images):
    flat = images.reshape((images.shape[0], -1))
    return {"scores": flat @ variables["w"]}


def toy_variables(seed=0, scale=0.1):
    w = np.random.RandomState(seed).randn(16, 6).astype(np.float32) * scale
    return {"w": w}


def lower_probe(seed=3):
    f = jax.jit(lambda v, x: jnp.tanh(x @ v) + seed)
    v = np.ones((8, 8), np.float32)
    return f, v, f.lower(v, jax.ShapeDtypeStruct((4, 8), "float32"))


def journal_events(path):
    return list(read_journal(path))


# -- cache core ----------------------------------------------------------------


def test_round_trip_bit_identical(tmp_path):
    cache = ExecutableCache(str(tmp_path), registry=Registry())
    f, v, lowered = lower_probe()
    compiled, src = cache.get_or_compile(lowered, name="probe")
    assert src == "compiled"
    # a second cache object over the same dir = a fresh process's view
    cache2 = ExecutableCache(str(tmp_path), registry=Registry())
    _, _, lowered2 = lower_probe()
    loaded, src2 = cache2.get_or_compile(lowered2, name="probe")
    assert src2 == "cache"
    x = np.random.RandomState(0).rand(4, 8).astype(np.float32)
    assert np.array_equal(np.asarray(compiled(v, x)),
                          np.asarray(loaded(v, x)))


def test_key_covers_lowering_and_env(tmp_path):
    cache = ExecutableCache(str(tmp_path), registry=Registry())
    _, _, la = lower_probe(seed=3)
    _, _, lb = lower_probe(seed=4)
    assert cache.key_for(la) == cache.key_for(la.as_text())
    assert cache.key_for(la) != cache.key_for(lb)
    # a different mesh shape changes the key even for the same lowering
    other = ExecutableCache(str(tmp_path), registry=Registry(),
                            mesh_shape=(2, 4))
    assert cache.key_for(la) != other.key_for(la)


def test_load_miss_journals(tmp_path):
    j_path = str(tmp_path / "j.jsonl")
    journal = RunJournal(j_path, kind="serve")
    cache = ExecutableCache(str(tmp_path / "c"), journal=journal,
                            registry=Registry())
    _, _, lowered = lower_probe()
    assert cache.load("deadbeef" * 4, lowered, name="nope") is None
    journal.close()
    ev = [e for e in journal_events(j_path) if e["event"] == "excache_miss"]
    assert len(ev) == 1 and ev[0]["key"] == "deadbeef" * 4


@pytest.mark.parametrize("field,expected_reason", [
    ("jax", "version_skew"),
    ("jaxlib", "version_skew"),
    ("platform_version", "version_skew"),
    ("device_kind", "topology_skew"),
    ("platform", "topology_skew"),
    ("device_count", "topology_skew"),
    ("mesh_shape", "topology_skew"),
])
def test_skewed_entry_refused(tmp_path, field, expected_reason):
    j_path = str(tmp_path / "j.jsonl")
    journal = RunJournal(j_path, kind="serve")
    root = str(tmp_path / "c")
    cache = ExecutableCache(root, journal=journal, registry=Registry())
    _, v, lowered = lower_probe()
    key = cache.key_for(lowered)
    compiled, _ = cache.get_or_compile(lowered, name="probe")
    man = os.path.join(root, key + ".json")
    doc = json.load(open(man))
    doc["fingerprint"][field] = ([9, 9] if field == "mesh_shape"
                                 else 999 if field == "device_count"
                                 else "skewed-by-test")
    with open(man, "w") as fh:
        fh.write(json.dumps(doc))
    # a fresh view must refuse the entry AND fall through to the compiler
    fresh = ExecutableCache(root, journal=journal, registry=Registry())
    assert fresh.load(key, lowered, name="probe") is None
    recompiled, src = fresh.get_or_compile(lowered, name="probe")
    assert src == "compiled"
    x = np.ones((4, 8), np.float32)
    assert np.array_equal(np.asarray(compiled(v, x)),
                          np.asarray(recompiled(v, x)))
    journal.close()
    inv = [e for e in journal_events(j_path)
           if e["event"] == "excache_invalid"]
    assert [e["reason"] for e in inv] == [expected_reason] * 2
    # skewed entries stay in place (valid for the env that wrote them)
    assert not os.path.exists(os.path.join(root, "quarantine"))


def test_corrupt_payload_quarantined(tmp_path):
    j_path = str(tmp_path / "j.jsonl")
    journal = RunJournal(j_path, kind="serve")
    root = str(tmp_path / "c")
    cache = ExecutableCache(root, journal=journal, registry=Registry())
    _, _, lowered = lower_probe()
    key = cache.key_for(lowered)
    cache.get_or_compile(lowered, name="probe")
    with open(os.path.join(root, key + ".exe"), "r+b") as fh:
        fh.seek(10)
        fh.write(b"\xde\xad\xbe\xef")
    loaded, src = cache.get_or_compile(lowered, name="probe")
    assert src == "compiled"  # fell through, and...
    qdir = os.path.join(root, "quarantine")
    assert any("corrupt" in fn for fn in os.listdir(qdir))
    journal.close()
    inv = [e for e in journal_events(j_path)
           if e["event"] == "excache_invalid"]
    assert len(inv) == 1 and inv[0]["reason"] == "corrupt"
    # the fall-through re-stored a good entry: next load hits
    assert cache.load(key, lowered, name="probe") is not None


def test_corrupt_manifest_quarantined(tmp_path):
    root = str(tmp_path / "c")
    cache = ExecutableCache(root, registry=Registry())
    _, _, lowered = lower_probe()
    key = cache.key_for(lowered)
    cache.get_or_compile(lowered)
    with open(os.path.join(root, key + ".json"), "w") as fh:
        fh.write("{not json")
    assert cache.load(key, lowered) is None
    assert os.path.isdir(os.path.join(root, "quarantine"))


def test_undeserializable_payload_quarantined(tmp_path):
    root = str(tmp_path / "c")
    cache = ExecutableCache(root, registry=Registry())
    _, _, lowered = lower_probe()
    key = cache.key_for(lowered)
    cache.get_or_compile(lowered)
    # crc-VALID bytes the runtime refuses: rewrite payload + manifest crc
    import google_crc32c

    blob = pickle.dumps(("not", "an", "executable"))
    with open(os.path.join(root, key + ".exe"), "wb") as fh:
        fh.write(blob)
    man = os.path.join(root, key + ".json")
    doc = json.load(open(man))
    doc["crc32c"] = int(google_crc32c.value(blob))
    with open(man, "w") as fh:
        fh.write(json.dumps(doc))
    assert cache.load(key, lowered) is None
    qdir = os.path.join(root, "quarantine")
    assert any("deserialize_failed" in fn for fn in os.listdir(qdir))


def test_concurrent_warmers_one_dir(tmp_path):
    """N threads racing get_or_compile on one cache dir: every warmer
    gets a working executable, the dir converges to one entry, and the
    locksmith sees no ordering violations."""
    locksmith.arm(registry=Registry())
    try:
        root = str(tmp_path / "c")
        results, errors = [], []
        barrier = threading.Barrier(4)

        def warm(i):
            try:
                cache = ExecutableCache(root, registry=Registry())
                _, v, lowered = lower_probe()
                barrier.wait(timeout=30)
                compiled, src = cache.get_or_compile(lowered,
                                                     name=f"w{i}")
                x = np.ones((4, 8), np.float32)
                results.append((src, np.asarray(compiled(v, x)).sum()))
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        threads = [threading.Thread(target=warm, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors
        assert len(results) == 4
        assert len({r[1] for r in results}) == 1  # identical outputs
        entries = [fn for fn in os.listdir(root) if fn.endswith(".exe")]
        assert len(entries) == 1  # last rename won, nothing torn
        report = locksmith.report()
        assert not report["violations"]
    finally:
        locksmith.disarm()


def test_reasons_in_sync_with_check_journal():
    from tools.check_journal import EXCACHE_INVALID_REASONS as SCHEMA

    assert set(EXCACHE_INVALID_REASONS) == SCHEMA


# -- engine / pool / trainer integration --------------------------------------


def test_engine_warmup_from_cache_zero_compiles(tmp_path):
    registry = Registry()
    cache = ExecutableCache(str(tmp_path), registry=registry)
    eng = Engine(registry=registry, excache=cache)
    eng.register("toy", toy_fn, toy_variables(), input_shape=IMG,
                 buckets=(1, 2))
    stats = eng.warmup()
    assert stats["cache_hits"] == 0 and stats["backend_compiles"] == 2
    # a second engine (the restarted-server model) over the same cache
    eng2 = Engine(registry=registry,
                  excache=ExecutableCache(str(tmp_path), registry=registry))
    eng2.register("toy", toy_fn, toy_variables(), input_shape=IMG,
                  buckets=(1, 2))
    c0 = recompile_count()
    stats2 = eng2.warmup()
    assert stats2["cache_hits"] == 2
    assert stats2["backend_compiles"] == 0
    assert recompile_count() == c0
    img = np.random.RandomState(1).rand(2, *IMG).astype(np.float32)
    assert np.array_equal(np.asarray(eng.run("toy", img)["scores"]),
                          np.asarray(eng2.run("toy", img)["scores"]))


def test_pool_respawn_fresh_warms_from_cache(tmp_path):
    from deep_vision_tpu.resilience import faults
    from deep_vision_tpu.resilience.retry import RetryPolicy
    from deep_vision_tpu.serve import ReplicaPool

    j_path = str(tmp_path / "j.jsonl")
    journal = RunJournal(j_path, kind="serve")
    registry = Registry()
    cache = ExecutableCache(str(tmp_path / "c"), journal=journal,
                            registry=registry)

    def build(rid):
        eng = Engine(registry=registry, journal=journal, excache=cache)
        eng.register("toy", toy_fn, toy_variables(), input_shape=IMG,
                     buckets=(1, 2))
        return eng

    pool = ReplicaPool(
        build, replicas=2, journal=journal, registry=registry,
        respawn_fresh=True, monitor_interval_s=0.05,
        respawn_policy=RetryPolicy(name="serve.replica", max_attempts=3,
                                   base_delay_s=0.01, max_delay_s=0.05))
    pool.start()
    c0 = recompile_count()
    faults.install_spec("serve.replica:io_error@1", seed=1,
                        export_env=False)
    img = np.random.RandomState(2).rand(*IMG).astype(np.float32)
    with pytest.raises(Exception):
        pool.submit("toy", img).result(timeout=60)
    faults.install(None)
    deadline = 50
    import time as _t

    for _ in range(deadline * 20):
        if all(s == "serving" for s in pool.replica_states().values()):
            break
        _t.sleep(0.05)
    assert all(s == "serving" for s in pool.replica_states().values())
    assert pool.submit("toy", img).result(timeout=60) is not None
    assert recompile_count() == c0  # the fresh engine warmed from cache
    pool.drain("close")
    journal.close()
    notes = [e for e in journal_events(j_path)
             if e.get("note") == "replica_respawn_fresh"]
    assert len(notes) == 1
    assert notes[0]["backend_compiles"] == 0
    assert notes[0]["cache_hits"] == notes[0]["pairs"] == 2


def test_trainer_cached_step(tmp_path):
    import flax.linen as nn
    import optax

    from deep_vision_tpu.train.trainer import Trainer

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=True, **kw):
            return nn.Dense(4)(x.reshape((x.shape[0], -1)))

    def loss_fn(outputs, batch):
        loss = optax.softmax_cross_entropy_with_integer_labels(
            outputs, batch["label"]).mean()
        return loss, {"loss": loss}

    j_path = str(tmp_path / "j.jsonl")
    journal = RunJournal(j_path, kind="train")
    cache = ExecutableCache(str(tmp_path / "c"), journal=journal,
                            registry=Registry())

    def make():
        return Trainer(Tiny(), optax.sgd(0.1), loss_fn,
                       jnp.ones((4, *IMG), jnp.float32),
                       executable_cache=cache, journal=journal)

    batch = {"image": np.random.RandomState(0).rand(4, *IMG)
             .astype(np.float32),
             "label": np.zeros((4,), np.int64)}
    t1 = make()
    m1 = t1.train_step(dict(batch))
    # the rebuild path: jitted wrappers + AOT table remade, the next
    # step re-lowers and must HIT the persistent cache
    t1._build_jitted_steps()
    assert t1._aot_steps == {}
    t1.train_step(dict(batch))
    # a second trainer (fresh-process model) over the same cache
    t2 = make()
    m2 = t2.train_step(dict(batch))
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-6
    # REPEATED steps through the cache-LOADED executable: the verify
    # drive caught a segfault here — jax's serialize round trip drops
    # donation bookkeeping, so a deserialized DONATING step aliases the
    # old state's buffers (use-after-free on the second call). The
    # cache path must lower donation-free; the params must stay finite
    # across consecutive loaded-executable steps.
    for _ in range(3):
        t2.train_step(dict(batch))
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(t2.state.params))
    journal.close()
    ev = journal_events(j_path)
    stores = [e for e in ev if e["event"] == "excache_store"]
    hits = [e for e in ev if e["event"] == "excache_hit"]
    assert len(stores) == 1  # one canonical signature, stored once
    assert len(hits) == 2  # the rebuild and the second trainer both hit
    assert all(e["name"] == "trainer/train_step" for e in stores + hits)


# -- int8 quantization --------------------------------------------------------


def test_quantize_parity_and_compression():
    variables = toy_variables(scale=0.3)
    qvars, report = quantize_variables(variables)
    assert report["quantized_leaves"] == 1
    assert report["compression"] > 3.0
    assert qvars["w"]["q8"].dtype == np.int8
    deq = dequantize_variables(qvars)
    # per-channel int8 round trip: worst-case error is scale/2 per entry
    scale = np.asarray(qvars["w"]["scale"])
    assert np.all(np.abs(np.asarray(deq["w"]) - variables["w"])
                  <= scale / 2 + 1e-7)
    x = np.random.RandomState(0).rand(4, *IMG).astype(np.float32)
    f32 = np.asarray(toy_fn(variables, x)["scores"])
    q = np.asarray(quantized_fn(toy_fn)(qvars, x)["scores"])
    assert np.allclose(f32, q, atol=0.05)


def test_quantize_refuses_kernel_free_tree():
    from deep_vision_tpu.serve import ServeError

    with pytest.raises(ServeError, match="no kernel leaves"):
        quantize_variables({"bias": np.zeros((4,), np.float32)})


def test_gate_fires_on_poisoned_calibration(tmp_path):
    """Same weights, same tolerance: a random calibration stream passes,
    the constant-image stream that exposes the cancelling-outlier
    channel REFUSES — and both verdicts are typed journal events."""
    j_path = str(tmp_path / "j.jsonl")
    journal = RunJournal(j_path, kind="serve")
    w = toy_variables(scale=0.02)
    w["w"][0, :], w["w"][1, :] = 500.0, -500.0
    rng = np.random.RandomState(0)
    random_calib = [rng.rand(4, *IMG).astype(np.float32) for _ in range(3)]
    qm = calibrate_and_quantize("toy", toy_fn, w, random_calib,
                                tolerance=0.005, journal=journal)
    assert qm.delta <= 0.005
    poison = [np.full((4, *IMG), v, np.float32) for v in (0.2, 0.6, 0.9)]
    with pytest.raises(QuantizationRejected, match="accuracy gate"):
        calibrate_and_quantize("toy", toy_fn, w, poison,
                               tolerance=0.005, journal=journal)
    journal.close()
    ev = [e for e in journal_events(j_path)
          if e["event"] == "quant_calibrated"]
    assert [e["accepted"] for e in ev] == [True, False]
    assert all(e["model"] == "toy" and isinstance(e["delta"], float)
               for e in ev)


def test_gate_refuses_empty_calibration():
    from deep_vision_tpu.serve import ServeError

    with pytest.raises(ServeError, match="at least one"):
        calibrate_and_quantize("toy", toy_fn, toy_variables(), [])


def test_int8_tree_hot_swaps_through_engine():
    """A re-calibrated int8 tree swaps through set_variables — the
    avals (int8 q8 + f32 scales) match, so the existing machinery
    accepts it without recompiling."""
    registry = Registry()
    qvars1, _ = quantize_variables(toy_variables(seed=0))
    qvars2, _ = quantize_variables(toy_variables(seed=9))
    eng = Engine(registry=registry)
    eng.register("toy", quantized_fn(toy_fn), qvars1, input_shape=IMG,
                 buckets=(2,))
    eng.warmup()
    img = np.random.RandomState(1).rand(2, *IMG).astype(np.float32)
    out1 = np.asarray(eng.run("toy", img)["scores"])
    c0 = recompile_count()
    eng.set_variables("toy", qvars2)
    out2 = np.asarray(eng.run("toy", img)["scores"])
    assert recompile_count() == c0
    assert not np.allclose(out1, out2)


def test_scales_round_trip_checkpoint_sidecar(tmp_path):
    """Scales ride the crc32c sidecar as host state; the int8 arrays
    ride the array checkpoint; apply_scales re-marries them exactly."""
    from deep_vision_tpu.core.checkpoint import CheckpointManager

    qvars, _ = quantize_variables(
        {"layer": {"kernel": np.random.RandomState(0)
                   .randn(8, 5).astype(np.float32)}})
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save_tree(1, qvars,
                  host_state={"quant_scales": scales_host_state(qvars)})
    mgr.wait()
    template = jax.tree_util.tree_map(np.zeros_like, qvars)
    restored, host = mgr.restore_tree(template, step=1)
    rejoined = apply_scales(restored, host["quant_scales"])
    assert np.array_equal(np.asarray(rejoined["layer"]["kernel"]["q8"]),
                          np.asarray(qvars["layer"]["kernel"]["q8"]))
    assert np.array_equal(
        np.asarray(rejoined["layer"]["kernel"]["scale"]),
        np.asarray(qvars["layer"]["kernel"]["scale"]))
    mgr.close()


def test_apply_scales_refuses_mismatch():
    from deep_vision_tpu.serve import ServeError

    qvars, _ = quantize_variables(toy_variables())
    host = scales_host_state(qvars)
    with pytest.raises(ServeError, match="no scales"):
        apply_scales(qvars, {})
    bad = dict(host)
    bad["w"] = bad["w"][:-1]
    with pytest.raises(ServeError, match="channels"):
        apply_scales(qvars, bad)
    extra = dict(host)
    extra["ghost"] = [1.0]
    with pytest.raises(ServeError, match="unknown leaves"):
        apply_scales(qvars, extra)


# -- satellites ---------------------------------------------------------------


def test_flash_min_tokens_env(monkeypatch):
    from deep_vision_tpu.models.vit import FLASH_MIN_TOKENS, flash_min_tokens

    monkeypatch.delenv("DVT_FLASH_MIN_TOKENS", raising=False)
    assert flash_min_tokens() == FLASH_MIN_TOKENS
    monkeypatch.setenv("DVT_FLASH_MIN_TOKENS", "2048")
    assert flash_min_tokens() == 2048
    monkeypatch.setenv("DVT_FLASH_MIN_TOKENS", "lots")
    with pytest.raises(ValueError, match="DVT_FLASH_MIN_TOKENS"):
        flash_min_tokens()


def _write_journal(tmp_path, rows):
    path = str(tmp_path / "j.jsonl")
    base = {"ts": 1.0, "run_id": "r"}
    with open(path, "w") as fh:
        fh.write(json.dumps({"event": "run_manifest", "kind": "serve",
                             "argv": [], **base}) + "\n")
        for row in rows:
            fh.write(json.dumps({**base, **row}) + "\n")
        fh.write(json.dumps({"event": "exit", "status": "clean_exit",
                             **base}) + "\n")
    return path


def test_check_journal_accepts_cold_path_events(tmp_path):
    from tools.check_journal import check_journal

    path = _write_journal(tmp_path, [
        {"event": "excache_hit", "key": "abc", "name": "m/b1"},
        {"event": "excache_miss", "key": "abc"},
        {"event": "excache_store", "key": "abc", "bytes": 10},
        {"event": "excache_invalid", "key": "abc",
         "reason": "version_skew"},
        {"event": "quant_calibrated", "model": "toy", "delta": 0.001,
         "accepted": True},
    ])
    assert check_journal(path, strict=True) == []


def test_check_journal_rejects_bad_cold_path_events(tmp_path):
    from tools.check_journal import check_journal

    path = _write_journal(tmp_path, [
        {"event": "excache_hit", "key": ""},
        {"event": "excache_invalid", "key": "abc", "reason": "dunno"},
        {"event": "quant_calibrated", "model": "toy", "delta": "big",
         "accepted": "yes"},
    ])
    errs = check_journal(path, strict=True)
    assert len(errs) == 4  # empty key, bad reason, bad delta, bad accepted


def test_obs_report_cold_path_section(tmp_path):
    from tools.obs_report import render, summarize_run

    path = _write_journal(tmp_path, [
        {"event": "excache_hit", "key": "abc"},
        {"event": "excache_invalid", "key": "abc",
         "reason": "version_skew"},
        {"event": "quant_calibrated", "model": "toy", "delta": 0.001,
         "accepted": True, "metric": "top1", "tolerance": 0.02},
    ])
    summary = summarize_run(journal_events(path))
    text = render(summary)
    assert "executable cache" in text and "version_skew" in text
    assert "int8 toy" in text and "accepted" in text
    # a journal with no cold-path events renders byte-unchanged
    plain = _write_journal(tmp_path, [])
    summary2 = summarize_run(journal_events(plain))
    assert "cold_path" not in summary2
    assert "executable cache" not in render(summary2)


def test_preflight_check_excache(tmp_path):
    from deep_vision_tpu.tools.preflight import check_excache

    r = check_excache(str(tmp_path / "c"))
    assert r.ok, r.detail
    assert "stale entry refused" in r.detail
    # probe cleaned up after itself
    leftovers = [fn for fn in os.listdir(str(tmp_path / "c"))
                 if fn.endswith((".exe", ".json"))]
    assert leftovers == []


def test_preflight_check_excache_unwritable(tmp_path):
    from deep_vision_tpu.tools.preflight import check_excache

    # a FILE where the cache dir should be: os.makedirs fails the same
    # way a bad mount does (chmod tricks don't bind under root CI)
    not_a_dir = tmp_path / "flat"
    not_a_dir.write_text("occupied")
    r = check_excache(str(not_a_dir))
    assert not r.ok
    assert "flat" in r.detail


def test_env_fingerprint_fields():
    fp = env_fingerprint(mesh_shape=(4, 2))
    assert fp["mesh_shape"] == [4, 2]
    for field in ("jax", "jaxlib", "platform", "device_kind",
                  "device_count"):
        assert field in fp


# -- where JAX's own compile cache lives (place_compile_cache) ---------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def _restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def _tiny_train(tmp_path, *extra):
    from deep_vision_tpu.train_cli import main

    return main(["-m", "lenet5", "--fake-data", "--epochs", "1",
                 "--batch-size", "16", "--fake-batches", "1",
                 "--skip-preflight", "--ckpt-dir", str(tmp_path / "ck"),
                 *extra])


@pytest.mark.parametrize("with_excache", [False, True])
def test_compile_cache_placed_from_outside_is_left_alone(
        tmp_path, monkeypatch, mesh8, _restore_cache_dir, with_excache):
    """JAX_COMPILATION_CACHE_DIR set: no code path sets another directory,
    with or without --executable-cache (which used to repoint it)."""
    outside = str(tmp_path / "placed-by-the-launcher")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    # what JAX itself did with the variable when it was imported
    jax.config.update("jax_compilation_cache_dir", outside)
    extra = (["--executable-cache", str(tmp_path / "aot")]
             if with_excache else [])
    assert _tiny_train(tmp_path, *extra) == 0
    assert jax.config.jax_compilation_cache_dir == outside
    assert not os.path.exists(tmp_path / "aot" / "xla")


def test_compile_cache_defaults_to_the_checkout(
        tmp_path, monkeypatch, mesh8, _restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)  # not derived from the working directory
    assert _tiny_train(tmp_path) == 0
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache")


def test_compile_cache_default_is_the_same_in_every_process(tmp_path):
    """A fixed place, never a temp dir, a pid or a time: the path is part
    of the cache key's environment, so a directory that moves never hits."""
    import subprocess
    import sys

    code = ("import jax; "
            "from deep_vision_tpu.core.excache import place_compile_cache; "
            "print(place_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    outs = [subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=120)
            for cwd in (REPO, str(tmp_path))]
    for res in outs:
        assert res.returncode == 0, res.stderr[-2000:]
        assert res.stdout.split() == [os.path.join(REPO, ".jax_cache")] * 2
    placed = str(tmp_path / "elsewhere")
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env={**env, "JAX_COMPILATION_CACHE_DIR": placed},
                         capture_output=True, text=True, timeout=120)
    assert res.stdout.split() == [placed, placed], res.stderr[-2000:]
