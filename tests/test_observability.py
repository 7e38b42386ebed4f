"""SummaryWriter event-file format + MetricLogger integration + profiler hook
+ the obs/ subsystem (registry, journal, stepclock, trainer wiring)."""
import json
import os
import re

import numpy as np
import pytest

from deep_vision_tpu.core.metrics import MetricLogger
from deep_vision_tpu.core.tensorboard import SummaryWriter
from deep_vision_tpu.obs import (
    Registry,
    RunJournal,
    StepClock,
    read_journal,
    recompile_count,
)

try:
    from tensorboard.backend.event_processing.event_file_loader import (
        EventFileLoader,
    )

    HAS_TB = True
except Exception:
    HAS_TB = False


def test_summary_writer_records_parse(tmp_path):
    w = SummaryWriter(str(tmp_path))
    w.scalar("train/loss", 1.5, 10)
    w.scalar("val/top1", 0.75, 20)
    w.close()
    from deep_vision_tpu.data.records import read_records

    events = list(read_records(w.path))
    assert len(events) == 3  # file_version + 2 scalars
    assert b"brain.Event:2" in events[0]
    assert b"train/loss" in events[1]


@pytest.mark.skipif(not HAS_TB, reason="tensorboard package unavailable")
def test_summary_writer_tensorboard_cross_parity(tmp_path):
    w = SummaryWriter(str(tmp_path))
    w.scalar("loss", 2.25, 7)
    w.close()
    events = [e for e in EventFileLoader(w.path).Load()]
    scalar_events = [e for e in events if e.summary.value]
    assert len(scalar_events) == 1
    (e,) = scalar_events
    assert e.step == 7
    v = e.summary.value[0]
    assert v.tag == "loss"
    # the loader's data_compat pass migrates simple_value -> tensor.float_val
    got = v.simple_value or v.tensor.float_val[0]
    assert got == pytest.approx(2.25)


def test_metric_logger_writes_tb(tmp_path):
    w = SummaryWriter(str(tmp_path))
    lg = MetricLogger(tb_writer=w, name="train", print_every=0)
    lg.start_epoch()
    lg.log_step(1, {"loss": 3.0}, batch_size=4, epoch=0)
    summary = lg.end_epoch(0)
    w.close()
    assert summary["loss"] == pytest.approx(3.0)
    from deep_vision_tpu.data.records import read_records

    payload = b"".join(read_records(w.path))
    assert b"train/batch_loss" in payload
    assert b"train/epoch_loss" in payload


def test_trainer_profiler_hook(tmp_path, mesh8):
    import jax.numpy as jnp

    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    trainer = Trainer(
        get_model("lenet5", num_classes=4),
        build_optimizer("adam", 1e-3),
        classification_loss_fn,
        jnp.ones((2, 32, 32, 1)),
        mesh=mesh8,
        profile_dir=str(tmp_path / "trace"),
        profile_steps=(1, 3),
    )
    rng = np.random.RandomState(0)
    batch = {"image": rng.rand(8, 32, 32, 1).astype(np.float32),
             "label": rng.randint(0, 4, (8,)).astype(np.int32)}
    for _ in range(5):
        trainer.train_step(batch)
    assert not trainer._profiling
    # a trace directory with at least one .pb/.json artifact was produced
    found = []
    for root, _, files in os.walk(tmp_path / "trace"):
        found += files
    assert found, "profiler produced no trace files"


def test_model_summary_counts():
    import jax
    import jax.numpy as jnp

    from deep_vision_tpu.core.summary import count_params, model_summary
    from deep_vision_tpu.models import get_model

    model = get_model("lenet5", num_classes=10)
    text = model_summary(model, jnp.ones((1, 32, 32, 1)))
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
        jnp.ones((1, 32, 32, 1)), train=False,
    )
    n = count_params(variables["params"])
    assert f"trainable params: {n:,}" in text
    # table lists every kernel with its shape
    assert "(5, 5, 1, 6)" in text  # LeNet-5 C1 conv kernel


def test_model_summary_resnet_is_abstract_and_fast():
    import jax.numpy as jnp

    from deep_vision_tpu.core.summary import model_summary
    from deep_vision_tpu.models import get_model

    # eval_shape: no real compute, so a 224x224 ResNet-50 summary is instant
    text = model_summary(
        get_model("resnet50", num_classes=1000), jnp.ones((2, 224, 224, 3)),
        max_rows=5,
    )
    assert "trainable params: 25,5" in text  # ~25.5M
    assert "... " in text  # truncation marker


# -- obs/registry ------------------------------------------------------------

# Prometheus text exposition grammar for the line formats we emit
_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? "
    r"([0-9.eE+-]+|\+Inf|NaN))$"
)


def test_registry_roundtrip_prometheus_and_jsonl(tmp_path):
    reg = Registry()
    c = reg.counter("steps_total", "steps executed")
    c.inc()
    c.inc(4)
    g = reg.gauge("lr", "learning rate")
    g.set(0.1)
    h = reg.histogram("step_ms", "step wall ms")
    for v in (0.5, 5.0, 50.0, 50.0, 5000.0):
        h.observe(v)

    text = reg.to_prometheus()
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"bad prometheus line: {line!r}"
    assert "steps_total 5" in text
    assert "# TYPE steps_total counter" in text
    assert "# TYPE step_ms histogram" in text
    assert 'step_ms_bucket{le="+Inf"} 5' in text
    assert "step_ms_count 5" in text
    # cumulative buckets are monotonically non-decreasing
    cum = [int(m.group(1)) for m in
           re.finditer(r'step_ms_bucket\{le="[^"]+"\} (\d+)', text)]
    assert cum == sorted(cum) and cum[-1] == 5

    # JSONL snapshot appends one parseable line per call
    path = tmp_path / "snap.jsonl"
    assert reg.append_jsonl_snapshot(str(path), tag="a")
    assert reg.append_jsonl_snapshot(str(path), tag="b")
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(rows) == 2 and rows[0]["tag"] == "a"
    assert rows[0]["metrics"]["steps_total"] == 5
    assert rows[0]["metrics"]["step_ms"]["count"] == 5
    assert rows[0]["metrics"]["step_ms"]["p50"] == pytest.approx(100, rel=1.1)

    # whole-file prometheus writer (process-0 path on CPU)
    prom = tmp_path / "m.prom"
    assert reg.write_prometheus(str(prom))
    assert prom.read_text() == text


def test_registry_writers_create_parent_dirs(tmp_path):
    # --metrics-export into a fresh runs/ dir must not crash a finished run
    reg = Registry()
    reg.counter("c").inc()
    assert reg.write_prometheus(str(tmp_path / "new" / "m.prom"))
    assert reg.append_jsonl_snapshot(str(tmp_path / "new2" / "s.jsonl"))
    assert (tmp_path / "new" / "m.prom").exists()


def test_prometheus_families_stay_contiguous():
    # creation order interleaves families (latency{a}, requests, latency{b});
    # the exposition format requires each family's lines in one block
    reg = Registry()
    reg.histogram("lat_ms", buckets=[1.0], labels={"task": "yolo"}).observe(0.5)
    reg.counter("reqs", labels={"task": "yolo"}).inc()
    reg.histogram("lat_ms", buckets=[1.0], labels={"task": "pose"}).observe(2.0)
    names = [l.split("# TYPE ")[1].split()[0] if l.startswith("# TYPE") else
             re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*", l).group(0)
             for l in reg.to_prometheus().strip().splitlines()
             if not l.startswith("# HELP")]
    fam = [re.sub(r"_(bucket|sum|count)$", "", n) for n in names]
    seen, last = set(), None
    for f in fam:
        if f != last:
            assert f not in seen, f"family {f} split across blocks: {fam}"
            seen.add(f)
        last = f


def test_prometheus_export_survives_nonfinite_gauges():
    reg = Registry()
    reg.gauge("maybe_nan").set(float("nan"))
    reg.gauge("neg_inf").set(float("-inf"))
    text = reg.to_prometheus()  # must not raise
    assert "maybe_nan NaN" in text
    assert "neg_inf -Inf" in text
    for line in text.strip().splitlines():
        if not line.startswith("#") and "Inf" not in line:
            assert _PROM_LINE.match(line), line


def test_histogram_snapshot_is_strict_json():
    reg = Registry()
    h = reg.histogram("t_ms", buckets=[1.0])
    h.observe(50.0)  # above the top bucket: quantiles land in +Inf
    snap = h.snapshot()
    assert snap["p50"] is None and snap["p99"] is None
    json.loads(json.dumps(snap, allow_nan=False))  # strict-parser clean


def test_registry_get_or_create_and_kind_conflict():
    reg = Registry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.counter("x", labels={"a": "1"}) is not reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)


def test_histogram_labels_render_with_le():
    reg = Registry()
    h = reg.histogram("lat_ms", buckets=[1.0, 10.0], labels={"task": "yolo"})
    h.observe(3.0)
    text = reg.to_prometheus()
    assert 'lat_ms_bucket{le="1",task="yolo"} 0' in text
    assert 'lat_ms_bucket{le="10",task="yolo"} 1' in text
    assert 'lat_ms_sum{task="yolo"} 3' in text


# -- obs/journal -------------------------------------------------------------

def test_journal_write_readback_clean_exit(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with RunJournal(path, kind="train") as j:
        j.manifest(config={"name": "lenet5"})
        j.step(1, step_time_ms=12.5, data_wait_ms=0.1, examples_per_sec=100.0)
        j.write("checkpoint", step=1, saved=True)
    events = read_journal(path)
    kinds = [e["event"] for e in events]
    assert kinds == ["run_manifest", "step", "checkpoint", "exit"]
    assert events[0]["config"]["name"] == "lenet5"
    assert events[0]["jax_version"]
    assert events[1]["step_time_ms"] == 12.5
    assert events[-1]["status"] == "clean_exit"
    assert all(e["run_id"] == events[0]["run_id"] for e in events)


def test_journal_crash_marker_and_closer(tmp_path):
    path = str(tmp_path / "crash.jsonl")
    j = RunJournal(path, kind="train")
    j.step(1, step_time_ms=1.0)
    closed = []
    j.add_closer(lambda: closed.append(True))
    j._atexit()  # simulate interpreter shutdown without close()
    events = read_journal(path)
    assert events[-1]["event"] == "crash"
    assert closed == [True], "atexit crash path must run registered closers"
    # idempotent: a real atexit firing after this must not double-write
    j._atexit()
    assert len(read_journal(path)) == len(events)


def test_journal_tolerates_torn_final_line(tmp_path):
    path = tmp_path / "torn.jsonl"
    with RunJournal(str(path)) as j:
        j.step(1, step_time_ms=1.0)
    with open(path, "a") as f:
        f.write('{"event": "step", "truncat')  # crash mid-write
    events = read_journal(str(path))
    assert events[-1]["event"] == "_torn_line"
    assert events[0]["event"] == "step"


# -- obs/stepclock -----------------------------------------------------------

def test_stepclock_sampling_cadence(tmp_path):
    import jax.numpy as jnp

    path = str(tmp_path / "clock.jsonl")
    j = RunJournal(path)
    clock = StepClock(registry=Registry(), journal=j, name="t",
                      sample_every=4, track_memory=False)
    for i in range(8):
        with clock.step(batch_size=16) as rec:
            rec.fence_on(jnp.ones(()) * i)
    j.close()
    assert clock.steps_seen == 8
    assert clock.sync_samples == 2  # steps 4 and 8 only
    steps = [e for e in read_journal(path) if e["event"] == "step"]
    assert len(steps) == 8
    sampled = [e["step"] for e in steps if "sync_ms" in e]
    assert sampled == [4, 8]
    for e in steps:
        assert e["step_time_ms"] >= e["data_wait_ms"]
        assert e["examples_per_sec"] > 0


def test_stepclock_iter_data_times_waits():
    import time as _t

    clock = StepClock(registry=Registry(), name="t2", sample_every=100)

    def slow_data():
        for i in range(3):
            _t.sleep(0.02)
            yield i

    waits = []
    for _ in clock.iter_data(slow_data()):
        with clock.step(batch_size=1) as rec:
            pass
        waits.append(rec.data_wait_ms)
    assert len(waits) == 3
    assert all(w >= 15.0 for w in waits), waits


def test_recompile_count_tracks_backend_compiles():
    import jax
    import jax.numpy as jnp

    before = recompile_count()
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones((3,)))
    mid = recompile_count()
    assert mid >= before + 1
    f(jnp.ones((3,)))  # cache hit: no new compile
    assert recompile_count() == mid
    f(jnp.ones((5,)))  # new shape: recompile
    assert recompile_count() >= mid + 1


# -- trainer wiring ----------------------------------------------------------

def _tiny_trainer(mesh8, **kw):
    import jax.numpy as jnp

    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    return Trainer(
        get_model("lenet5", num_classes=4),
        kw.pop("tx", build_optimizer("adam", 1e-3)),
        classification_loss_fn,
        jnp.ones((2, 32, 32, 1)),
        mesh=mesh8,
        **kw,
    )


def _tiny_batches(n=3, bs=8):
    rng = np.random.RandomState(0)
    return [
        {"image": rng.rand(bs, 32, 32, 1).astype(np.float32),
         "label": rng.randint(0, 4, (bs,)).astype(np.int32)}
        for _ in range(n)
    ]


def test_trainer_smoke_journal_and_recompile_gauge(tmp_path, mesh8):
    path = str(tmp_path / "train.jsonl")
    journal = RunJournal(path)
    journal.manifest()
    reg = Registry()
    trainer = _tiny_trainer(mesh8, journal=journal, registry=reg,
                            telemetry_sample_every=2)
    data = _tiny_batches()
    trainer.fit(lambda: data, epochs=1, handle_preemption=False)
    trainer.close()
    journal.close()
    events = read_journal(path)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_manifest" and kinds[-1] == "exit"
    steps = [e for e in events if e["event"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert "step_time_ms" in e and "data_wait_ms" in e
        assert "examples_per_sec" in e
        assert e["metrics"]["lr"] == pytest.approx(1e-3)
    assert any(e["event"] == "epoch" for e in events)
    # the sampled fence recorded the compile(s) of the jitted train step
    assert reg.gauge("jit_recompiles_total").value >= 1
    assert any("recompiles" in e for e in steps)


def test_trainer_close_stops_leaked_trace(tmp_path, mesh8):
    trainer = _tiny_trainer(
        mesh8, profile_dir=str(tmp_path / "trace"),
        profile_steps=(1, 10_000),  # stop gate unreachable in a short run
    )
    for batch in _tiny_batches(2):
        trainer.train_step(batch)
    assert trainer._profiling, "trace should be open mid-run"
    trainer.close()
    assert not trainer._profiling
    trainer.close()  # idempotent
    found = []
    for root, _, files in os.walk(tmp_path / "trace"):
        found += files
    assert found, "closed trace produced no artifacts"


def test_current_lr_falls_back_to_schedule(mesh8):
    import optax

    sched = optax.exponential_decay(0.1, transition_steps=10, decay_rate=0.5,
                                    staircase=True)
    # plain optax optimizer: no inject_hyperparams, so no opt_state.hyperparams
    trainer = _tiny_trainer(mesh8, tx=optax.sgd(sched), lr_schedule=sched)
    assert trainer.current_lr == pytest.approx(0.1)
    for batch in _tiny_batches(1):
        trainer.train_step(batch)
    assert trainer.current_lr == pytest.approx(float(sched(1)))
    # without the schedule hint the old NaN behavior remains
    t2 = _tiny_trainer(mesh8, tx=optax.sgd(0.1))
    assert np.isnan(t2.current_lr)


def test_metric_logger_perf_fields(tmp_path, capsys):
    reg = Registry()
    w = SummaryWriter(str(tmp_path))
    lg = MetricLogger(tb_writer=w, name="train", print_every=1, registry=reg)
    lg.start_epoch()
    lg.log_step(1, {"loss": 2.0}, batch_size=8, epoch=0, lr=0.1,
                data_wait_ms=3.25, examples_per_sec=123.0)
    w.close()
    out = capsys.readouterr().out
    assert "ex/s=123.0" in out
    assert "data_wait_ms=3.2" in out
    assert reg.gauge("train_loss").value == 2.0
    assert reg.gauge("train_learning_rate").value == pytest.approx(0.1)
    from deep_vision_tpu.data.records import read_records

    payload = b"".join(read_records(w.path))
    assert b"train/examples_per_sec" in payload
    assert b"train/data_wait_ms" in payload


def test_metric_logger_metric_slug():
    from deep_vision_tpu.core.metrics import _metric_slug

    assert _metric_slug("mAP@.5") == "mAP__5"
    assert re.match(r"^[a-zA-Z_][a-zA-Z0-9_]*$", "x_" + _metric_slug("mAP@[.5:.95]"))


# -- data pipeline + inference instrumentation -------------------------------

def test_dataloader_prefetch_metrics():
    from deep_vision_tpu.data.pipeline import DataLoader
    from deep_vision_tpu.obs.registry import get_registry

    reg = get_registry()
    labels = {"loader": "obs-test"}
    before = reg.counter("data_batches_total", labels=labels).value
    ds = [{"x": np.ones((2,), np.float32)} for _ in range(12)]
    dl = DataLoader(ds, batch_size=4, num_workers=1, prefetch=2,
                    name="obs-test")
    assert sum(1 for _ in dl) == 3
    assert reg.counter("data_batches_total", labels=labels).value == before + 3


def test_inference_latency_histogram(mesh8):
    from deep_vision_tpu.inference import make_pose_estimator
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.obs.registry import get_registry

    import jax
    import jax.numpy as jnp

    model = get_model("hourglass", num_stack=1, num_heatmap=4)
    images = jnp.ones((1, 64, 64, 3))
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        images, train=False,
    )
    est = make_pose_estimator(model)
    hist = get_registry().histogram("inference_latency_ms",
                                    labels={"task": "pose"})
    before = hist.count
    out = est({"params": variables["params"],
               **({"batch_stats": variables["batch_stats"]}
                  if "batch_stats" in variables else {})}, images)
    assert out.shape == (1, 4, 3)
    assert hist.count == before + 1
    assert hist.sum > 0


# -- obs_report + bench journal schema ---------------------------------------

def test_obs_report_renders_journal(tmp_path):
    from tools.obs_report import main as report_main, summarize_run

    path = str(tmp_path / "r.jsonl")
    with RunJournal(path, kind="train") as j:
        j.manifest(config={"name": "lenet5", "task": "classification"})
        for i in range(1, 5):
            j.step(i, step_time_ms=10.0 + i, data_wait_ms=0.5,
                   examples_per_sec=800.0, recompiles=2)
        j.write("eval", epoch=0, summary={"top1": 0.9})
    events = read_journal(path)
    s = summarize_run(events)
    assert s["steps"] == 4
    assert s["status"] == "clean_exit"
    assert s["step_time_ms"]["mean"] == pytest.approx(12.5)
    assert s["recompiles"] == 2
    assert report_main([path]) == 0


def test_obs_report_flags_crash(tmp_path):
    from tools.obs_report import summarize_run

    path = str(tmp_path / "c.jsonl")
    j = RunJournal(path)
    j.step(1, step_time_ms=1.0)
    j._atexit()
    s = summarize_run(read_journal(path))
    assert s["status"].startswith("CRASHED")
