"""Performance attribution plane (obs/costmodel.py + obs/perfwatch.py +
tools/perf_gate.py + tools/trace_digest.py).

XLA cost extraction on a real compiled step, collective-inventory
parsing checked against the gradient-tree size it predicts (the sharded
ViT all-reduce bill), the crc-manifested perf ledger (append, corrupt-
row quarantine, rotation), the noise-aware MAD gate across its verdict
space, step-time decomposition of a real CPU profiler capture, the
obs_report / telemetry renderings with their byte-unchanged gates, and
the schema drift-guards that pin the emitters to check_journal.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from deep_vision_tpu.obs import costmodel, perfwatch  # noqa: E402
from deep_vision_tpu.obs.journal import RunJournal, read_journal  # noqa: E402
from deep_vision_tpu.obs.registry import Registry  # noqa: E402

from tools.check_journal import (  # noqa: E402
    EVENT_FIELDS,
    PERF_COLLECTIVE_KINDS,
    check_journal,
)
from tools.perf_gate import (  # noqa: E402
    GATE_VERDICTS,
    PerfLedger,
    default_env,
    env_key,
    gate_result,
    mad_gate,
    metric_direction,
)


@pytest.fixture(autouse=True)
def _fresh_perfwatch():
    perfwatch._reset_for_tests()
    yield
    perfwatch._reset_for_tests()


def _compiled_matmul():
    def f(x, w):
        return jnp.tanh(x @ w).sum()

    x = jnp.ones((32, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    return jax.jit(f).lower(x, w).compile()


# ---------------------------------------------------------------- costmodel


def test_cost_summary_real_compiled_step():
    cost = costmodel.cost_summary(_compiled_matmul())
    # 32x64 @ 64x64 is 2*32*64*64 flops before fusion slack
    assert cost["flops"] and cost["flops"] >= 2 * 32 * 64 * 64
    assert cost["bytes_accessed"] and cost["bytes_accessed"] > 0
    assert cost["argument_bytes"] == 32 * 64 * 4 + 64 * 64 * 4


def test_collective_inventory_parses_hlo_forms():
    # one instruction per line, the shape compiled HLO as_text() emits
    hlo = (
        "  %ar = f32[64,128]{1,0} all-reduce(f32[64,128] %p), channel_id=1,"
        " replica_groups=[1,8]<=[8], use_global_device_ids=true\n"
        "  %ag-start = (f32[16]{0}, f32[128]{0}) all-gather-start(f32[16]"
        " %q), replica_groups={{0,1},{2,3}}, dimensions={0}\n"
        "  %ag-done = f32[128]{0} all-gather-done((f32[16], f32[128])"
        " %ag-start)\n"
        "  %rs = bf16[32]{0} reduce-scatter(bf16[256] %r), replica_groups={}\n"
    )
    inv = costmodel.collective_inventory(hlo)
    kinds = sorted(i["kind"] for i in inv)
    # the -done half of an async pair must not double-count
    assert kinds == ["all-gather", "all-reduce", "reduce-scatter"]
    ar = next(i for i in inv if i["kind"] == "all-reduce")
    assert ar["bytes"] == 64 * 128 * 4
    assert ar["group_size"] == 8
    ag = next(i for i in inv if i["kind"] == "all-gather")
    assert ag["group_size"] == 2
    rs = next(i for i in inv if i["kind"] == "reduce-scatter")
    assert rs["bytes"] == 32 * 2  # result shape, bf16
    assert costmodel.predicted_collective_bytes(inv) == sum(
        i["bytes"] for i in inv)
    assert costmodel.predicted_collective_bytes(inv, "all-reduce") \
        == ar["bytes"]


def test_collective_inventory_parses_tpu_tiled_tuple_shapes():
    """TPU layouts carry tiling — parentheses INSIDE the tuple shape. The
    line is from the four-chip resnet50 step (PR 21), where the inventory
    came back empty until the shape pattern allowed one nested level."""
    hlo = (
        "  %all-reduce.370 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)})"
        " all-reduce(%get-tuple-element.46, %get-tuple-element.44),"
        " channel_id=2, replica_groups=[1,4]<=[4],"
        " use_global_device_ids=true, to_apply=%region_1.2.clone\n"
        "  %ar.2 = f32[7,7,512]{2,1,0:T(8,128)} all-reduce(%fusion.9),"
        " channel_id=3, replica_groups=[1,4]<=[4]\n"
    )
    inv = costmodel.collective_inventory(hlo)
    assert [i["kind"] for i in inv] == ["all-reduce", "all-reduce"]
    assert inv[0]["bytes"] == 2 * 64 * 4 and inv[0]["group_size"] == 4
    assert inv[1]["bytes"] == 7 * 7 * 512 * 4


def test_collective_inventory_empty_on_single_device_hlo():
    hlo = costmodel.hlo_text(_compiled_matmul())
    assert hlo  # compiled text must be available on this jax
    assert costmodel.collective_inventory(hlo) == []


def test_sharded_vit_allreduce_matches_grad_tree():
    """The acceptance check: on a pure-DP mesh the grad all-reduce bill
    parsed out of the compiled HLO must equal the gradient tree size
    within 5%."""
    from deep_vision_tpu.core.train_state import create_train_state
    from deep_vision_tpu.losses.classification import classification_loss_fn
    from deep_vision_tpu.models.vit import ViT
    from deep_vision_tpu.parallel.mesh import create_mesh, data_sharding
    from deep_vision_tpu.parallel.shardmap import VIT_RULES
    from deep_vision_tpu.train.optimizers import build_optimizer

    mesh = create_mesh(data=len(jax.devices()), model=1)
    model = ViT(depth=2, dim=16, num_heads=2, patch=8, num_classes=8)
    state = create_train_state(model, build_optimizer("sgd", 0.1),
                               jnp.ones((2, 16, 16, 3), jnp.float32))
    shardings, _ = VIT_RULES.resolve(state, mesh)
    state = jax.device_put(state, shardings)
    batch = {
        "image": jax.device_put(
            np.ones((16, 16, 16, 3), np.float32), data_sharding(mesh, 4)),
        "label": jax.device_put(
            np.zeros((16,), np.int32), data_sharding(mesh, 1)),
    }

    def train_step(state, batch):
        def loss_fn(params):
            logits = state.apply_fn({"params": params}, batch["image"],
                                    train=False)
            loss, _ = classification_loss_fn(logits, batch)
            return loss

        grads = jax.grad(loss_fn)(state.params)
        return state.apply_gradients(grads)

    compiled = jax.jit(train_step).lower(state, batch).compile()
    inv = costmodel.collective_inventory(costmodel.hlo_text(compiled))
    ar = costmodel.predicted_collective_bytes(inv, "all-reduce")
    grad = costmodel.tree_bytes(state.params)
    assert ar > 0
    assert abs(ar - grad) / grad <= 0.05


# ---------------------------------------------------------------- perfwatch


def test_profile_compiled_journals_and_gauges(tmp_path):
    path = str(tmp_path / "j.jsonl")
    reg = Registry()
    with RunJournal(path, kind="test") as j:
        j.manifest()
        prof = perfwatch.profile_compiled("test/matmul", _compiled_matmul(),
                                          journal=j, registry=reg)
    assert prof is not None
    assert prof["cost"]["flops"] > 0
    assert prof["collective_bytes"] == 0  # single-device program
    events = [e for e in read_journal(path) if e["event"] == "perf_profile"]
    assert len(events) == 1
    assert events[0]["name"] == "test/matmul"
    assert check_journal(path, strict=True) == []
    snap = reg.snapshot()  # flat {name+labels: value}
    assert snap["perfwatch_profiles_total"] == 1
    assert any(k.startswith("perfwatch_flops") for k in snap)


def test_profile_compiled_never_raises_on_garbage():
    assert perfwatch.profile_compiled("x", object()) is not None


def test_telemetry_status_surfaces_last_profile_gate_digest():
    perfwatch.profile_compiled("t/step", _compiled_matmul())
    perfwatch.note_gate({"verdict": "pass", "metric": "m"})
    perfwatch.note_digest({"compute_ms": 1.0})
    perfwatch.set_quantile_source(
        lambda: {"step_time_ms_p50": 3.0, "step_time_ms_p95": 9.0})
    st = perfwatch.telemetry_status()
    assert st["step_time_ms_p50"] == 3.0
    assert st["gate"]["verdict"] == "pass"
    assert st["digest"]["compute_ms"] == 1.0
    assert st["last_profile"]["name"] == "t/step"
    assert isinstance(st.get("recompiles"), int)
    json.dumps(st)  # the /statusz scraper must be able to serialize it


# ------------------------------------------------------------------ ledger


def test_ledger_append_read_roundtrip(tmp_path):
    led = PerfLedger(str(tmp_path / "led.jsonl"))
    led.append({"metric": "m", "value": 1.0, "verdict": "pass"})
    led.append({"metric": "m", "value": 2.0, "verdict": "pass"})
    rows = led.read()
    assert [r["value"] for r in rows] == [1.0, 2.0]
    assert all("crc" in r and "ts" in r for r in rows)


def test_ledger_quarantines_corrupt_rows(tmp_path):
    led = PerfLedger(str(tmp_path / "led.jsonl"))
    for v in (1.0, 2.0, 3.0):
        led.append({"metric": "m", "value": v})
    with open(led.path, "a") as f:
        f.write('{"metric": "tampered", "value": 9, "crc": 1}\n')
        f.write("not json\n")
    rows = led.read()
    assert [r["value"] for r in rows] == [1.0, 2.0, 3.0]
    assert os.path.exists(led.quarantine_path)
    quarantined = open(led.quarantine_path).read()
    assert "tampered" in quarantined and "not json" in quarantined
    # the main file was rewritten clean: a second read quarantines nothing
    assert [r["value"] for r in led.read()] == [1.0, 2.0, 3.0]


def test_ledger_rotation_spills_oldest(tmp_path):
    led = PerfLedger(str(tmp_path / "led.jsonl"), max_rows=6, keep_rows=3)
    for v in range(8):
        led.append({"metric": "m", "value": float(v)})
    live = [r["value"] for r in led.read()]
    assert len(live) <= 6
    assert live[-1] == 7.0
    assert os.path.exists(led.rotated_path)
    spilled = [json.loads(line)["value"]
               for line in open(led.rotated_path) if line.strip()]
    assert spilled[0] == 0.0
    assert sorted(spilled + live) == [float(v) for v in range(8)]


# ---------------------------------------------------------------- MAD gate


def test_mad_gate_verdicts():
    hist = [10.0, 10.2, 9.8, 10.1, 9.9]
    out = mad_gate(hist, 10.05, direction="lower")
    assert out["verdict"] == "pass"
    out = mad_gate(hist, 30.0, direction="lower")
    assert out["verdict"] == "fail"
    assert out["baseline"] == pytest.approx(10.0)
    assert out["threshold"] > 0
    # a big IMPROVEMENT must not fail a lower-is-better gate
    assert mad_gate(hist, 1.0, direction="lower")["verdict"] == "pass"
    # higher-is-better flips the failing side
    assert mad_gate(hist, 1.0, direction="higher")["verdict"] == "fail"
    assert mad_gate(hist, 30.0, direction="higher")["verdict"] == "pass"
    assert mad_gate([10.0], 30.0, direction="lower")["verdict"] \
        == "insufficient_history"
    # identical history (MAD=0): the relative floor absorbs jitter
    flat = [10.0] * 5
    assert mad_gate(flat, 10.2, direction="lower")["verdict"] == "pass"
    assert mad_gate(flat, 11.0, direction="lower")["verdict"] == "fail"


def test_metric_direction_heuristic():
    assert metric_direction("step_time_ms", None) == "lower"
    assert metric_direction("x", "ms_per_step") == "lower"
    assert metric_direction("resnet50_images_per_sec", None) == "higher"
    assert metric_direction("multichip_scaling", "efficiency_fraction") \
        == "higher"


def test_gate_result_excludes_failed_rows_and_blesses(tmp_path):
    led = PerfLedger(str(tmp_path / "led.jsonl"))
    env = default_env()
    kw = dict(unit="ms", env=env, min_history=2, journal=None)
    for v in (10.0, 10.1, 9.9):
        gate_result(led, "m", v, **kw)
    out = gate_result(led, "m", 50.0, **kw)
    assert out["verdict"] == "fail"
    # the failed row must not drag the baseline: a clean run still passes
    assert gate_result(led, "m", 10.0, **kw)["verdict"] == "pass"
    # bless re-anchors at the new level; the next run gates against it
    assert gate_result(led, "m", 50.0, bless=True, **kw)["verdict"] \
        == "blessed"
    assert gate_result(led, "m", 50.5, **kw)["verdict"] == "pass"
    assert gate_result(led, "m", 90.0, **kw)["verdict"] == "fail"


def test_gate_result_journals_regression(tmp_path):
    led = PerfLedger(str(tmp_path / "led.jsonl"))
    path = str(tmp_path / "j.jsonl")
    kw = dict(unit="ms", env=default_env(), min_history=2)
    with RunJournal(path, kind="perf_gate") as j:
        j.manifest()
        for v in (1.0, 1.01, 1.02):
            gate_result(led, "m", v, journal=j, **kw)
        out = gate_result(led, "m", 99.0, journal=j, **kw)
    assert out["verdict"] == "fail"
    events = [e for e in read_journal(path)
              if e["event"] == "perf_regression"]
    assert len(events) == 1
    assert events[0]["observed"] == 99.0
    assert events[0]["metric"] == "m"
    assert check_journal(path, strict=True) == []
    # the verdict also lands on the /statusz perf section
    assert perfwatch.telemetry_status()["gate"]["verdict"] == "fail"


def test_env_key_separates_mesh_shapes():
    a = default_env(mesh_shape={"data": 8, "model": 1})
    b = default_env(mesh_shape={"data": 4, "model": 2})
    assert env_key(a) != env_key(b)
    assert env_key(a) == env_key(dict(a))


# ------------------------------------------------------------ trace digest


def test_trace_digest_on_real_cpu_capture(tmp_path):
    from tools.trace_digest import digest, find_xplanes, render_digest

    @jax.jit
    def f(x, w):
        return jnp.tanh(x @ w).sum()

    x = jnp.ones((16, 32))
    w = jnp.ones((32, 32))
    f(x, w).block_until_ready()
    cap = str(tmp_path / "cap")
    with jax.profiler.trace(cap):
        for _ in range(3):
            f(x, w).block_until_ready()
    assert find_xplanes(cap), "profiler wrote no xplane capture"
    d = digest(cap)
    assert "error" not in d
    assert d["totals"]["compute_ms"] > 0
    assert d["totals"]["collective_ms"] == 0  # single-device program
    # (XLA:CPU calls the matmul `dot_general.N`; older ones `dot.N`)
    dot, = [r for r in d["ops"] if r["op"].startswith("dot")]
    assert dot["category"] == "compute"
    assert dot["count"] == 3
    assert any(r["category"] == "host" for r in d["ops"])
    assert d["spans"] == []  # no span of the program's in this capture
    text = render_digest(d)
    assert "step-time decomposition" in text and dot["op"] in text
    # the in-process run surfaces on /statusz
    assert perfwatch.telemetry_status()["digest"]["compute_ms"] > 0


def test_trace_digest_lists_the_programs_spans(tmp_path):
    """A capture taken as the program takes them holds its spans in the
    host plane; nested events count once in the totals."""
    from deep_vision_tpu.obs.trace import span, start_profiler
    from tools.trace_digest import digest, render_digest

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    cap = str(tmp_path / "cap")
    start_profiler(cap)
    try:
        for i in range(3):
            with span("train/step", step=i):
                with span("train/dispatch", step=i):
                    y = f(x)
                with span("train/fetch", step=i, n=1):
                    float(y)
    finally:
        jax.profiler.stop_trace()
    d = digest(cap)
    assert "error" not in d
    spans = {r["span"]: r for r in d["spans"]}
    assert set(spans) == {"train/step", "train/dispatch", "train/fetch"}
    assert all(r["count"] == 3 for r in spans.values())
    assert spans["train/step"]["total_ms"] >= (
        spans["train/dispatch"]["total_ms"] + spans["train/fetch"]["total_ms"])
    # host events nest (PjitFunction > Execute > ...): their union, not
    # their sum, is the host's total
    host_sum = sum(r["total_ms"] for r in digest(cap, top_k=10_000)["ops"]
                   if r["category"] == "host")
    assert 0 < d["totals"]["host_ms"] < host_sum
    assert "train/fetch" in render_digest(d)


def test_trace_digest_missing_capture_degrades(tmp_path):
    from tools.trace_digest import digest, render_digest

    d = digest(str(tmp_path))
    assert d["error"]
    assert "no .xplane.pb" in render_digest(d)


# ------------------------------------------------------------- renderings


def test_obs_report_perf_section_renders(tmp_path):
    from tools.obs_report import render, summarize_run

    path = str(tmp_path / "j.jsonl")
    with RunJournal(path, kind="test") as j:
        j.manifest()
        j.write("perf_profile", name="trainer/train", flops=1e9,
                bytes_accessed=2e6, argument_bytes=1, output_bytes=1,
                temp_bytes=0, collective_count=2, collective_bytes=33024)
        j.write("perf_collective", name="trainer/train", kind="all-reduce",
                dtype="f32", ops=2, bytes=33024, group_size=8)
        j.write("perf_regression", metric="step_ms", baseline=1.0,
                observed=9.0, threshold=0.5, direction="lower")
    text = render(summarize_run(read_journal(path)))
    assert "perf trainer/train" in text
    assert "all-reduce f32 x2" in text
    assert "PERF REGRESSION" in text and "step_ms" in text


def test_obs_report_unchanged_without_perf_events(tmp_path):
    from tools.obs_report import render, summarize_run

    path = str(tmp_path / "j.jsonl")
    with RunJournal(path, kind="test") as j:
        j.manifest()
        j.write("note", note="nothing perf-shaped here")
    events = read_journal(path)
    text = render(summarize_run(events))
    assert "perf" not in text.lower() or "perf" not in text
    from tools.obs_report import summarize_perf

    assert summarize_perf(events) is None


def test_obs_report_ledger_trajectory(tmp_path):
    from tools.obs_report import render_ledger

    led = PerfLedger(str(tmp_path / "led.jsonl"))
    kw = dict(unit="ms", env=default_env(), min_history=2)
    for v in (10.0, 10.5, 9.5, 10.2):
        gate_result(led, "step_ms", v, **kw)
    text = render_ledger(led.path)
    assert "step_ms" in text
    assert "[pass]" in text
    assert "(n=4)" in text
    # empty ledger renders a stub, not a crash
    assert "empty" in render_ledger(str(tmp_path / "missing.jsonl"))


# ------------------------------------------------------------ drift guards


def test_collective_kind_enums_stay_in_sync():
    assert set(costmodel.COLLECTIVE_KINDS) == PERF_COLLECTIVE_KINDS


# (the old perf-event registration walk lives in lint now: DV204 fails
# any journal.write whose event type has no check_journal schema, and
# tests/test_distlint.py parametrizes that walk over every emitter)


def test_gate_verdicts_cover_gate_outputs():
    assert set(GATE_VERDICTS) == {"pass", "fail", "insufficient_history",
                                  "blessed"}


def test_emitters_satisfy_required_schema(tmp_path):
    """Every field check_journal requires must actually be emitted —
    the strict gate and the emitters drift together or not at all."""
    path = str(tmp_path / "j.jsonl")
    led = PerfLedger(str(tmp_path / "led.jsonl"))
    with RunJournal(path, kind="test") as j:
        j.manifest()
        perfwatch.profile_compiled("t", _compiled_matmul(), journal=j)
        kw = dict(unit="ms", env=default_env(), min_history=2, journal=j)
        for v in (1.0, 1.0, 1.0):
            gate_result(led, "m", v, **kw)
        gate_result(led, "m", 99.0, **kw)
    by_event = {}
    for e in read_journal(path):
        by_event.setdefault(e["event"], []).append(e)
    assert "perf_profile" in by_event
    assert "perf_regression" in by_event
    for ev, rows in by_event.items():
        for row in rows:
            for field in EVENT_FIELDS.get(ev, ()):
                assert field in row, (ev, field)
    assert check_journal(path, strict=True) == []
