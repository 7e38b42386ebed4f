"""Flight recorder, anomaly-triggered profiling, and multi-host journal
aggregation (obs/flight.py, obs/autoprof.py, obs/merge.py + the tools/
CLIs and the trainer wiring)."""
import json
import os
import zlib

import numpy as np
import pytest

from deep_vision_tpu.obs import (
    AutoProfiler,
    FlightRecorder,
    Registry,
    RunJournal,
    read_journal,
    set_flight,
)
from deep_vision_tpu.obs import flight as flight_mod
from deep_vision_tpu.obs.flight import find_bundles, validate_bundle


@pytest.fixture(autouse=True)
def _clean_global_obs_state():
    """Flight recorder and profiler latch are process-global; a test that
    leaks either would poison its neighbors."""
    yield
    set_flight(None)
    from deep_vision_tpu.obs import autoprof as ap_mod

    ap_mod._release_capture()


@pytest.fixture()
def fake_profiler(monkeypatch):
    """Replace jax.profiler start/stop with call recorders: most autoprof
    tests assert the DECISIONS, not the (slow) real trace I/O."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    return calls


# -- flight recorder: buffers and bundles ------------------------------------

def _step_row(i, ms=10.0):
    return {"event": "step", "ts": 1000.0 + i, "run_id": "r", "step": i,
            "step_time_ms": ms, "data_wait_ms": 1.0}


def test_flight_observe_routes_and_bounds(tmp_path):
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r",
                        max_steps=8, max_tail=16, max_health=4)
    for i in range(100):
        fr.observe(_step_row(i))
    fr.observe({"event": "health", "ts": 2000.0, "run_id": "r",
                "kind": "loss_spike"})
    assert len(fr._steps) == 8          # bounded
    assert len(fr._tail) == 16
    assert fr._steps[-1]["step"] == 99  # ...keeping the most recent
    assert len(fr._health) == 1
    fr.close()


def test_flight_dump_bundle_valid_and_latched(tmp_path):
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    for i in range(5):
        fr.observe(_step_row(i))
    fr.note("data_worker_restart", worker=2)
    p1 = fr.dump("manual")
    assert p1 and os.path.basename(p1) == "r-manual"
    assert validate_bundle(p1) == []
    man = json.load(open(os.path.join(p1, "MANIFEST.json")))
    assert man["run_id"] == "r" and man["reason"] == "manual"
    steps = [json.loads(ln) for ln in open(os.path.join(p1, "steps.jsonl"))]
    assert [s["step"] for s in steps] == list(range(5))
    notes = [json.loads(ln) for ln in open(os.path.join(p1, "notes.jsonl"))]
    assert notes[0]["category"] == "data_worker_restart"
    # latch: same reason returns the same bundle; a new reason gets its own
    assert fr.dump("manual") == p1
    p2 = fr.dump("hang")
    assert p2 != p1 and validate_bundle(p2) == []
    assert set(fr.dumped) == {"manual", "hang"}
    # atomic: no torn tmp dirs remain
    assert not [d for d in os.listdir(tmp_path / "flight") if ".tmp-" in d]
    fr.close()


def test_flight_dump_never_clobbers_prior_run(tmp_path):
    d = tmp_path / "flight"
    fr1 = FlightRecorder(str(d), run_id="r")
    p1 = fr1.dump("crash")
    fr1.close()
    fr2 = FlightRecorder(str(d), run_id="r")  # same run_id (restart)
    p2 = fr2.dump("crash")
    assert p2 != p1 and p2.endswith("-2")
    assert validate_bundle(p1) == [] and validate_bundle(p2) == []
    fr2.close()


def test_validate_bundle_detects_rot_and_truncation(tmp_path):
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    fr.observe(_step_row(1))
    p = fr.dump("manual")
    fr.close()
    target = os.path.join(p, "steps.jsonl")
    data = bytearray(open(target, "rb").read())
    data[0] ^= 0xFF
    open(target, "wb").write(bytes(data))
    errs = validate_bundle(p)
    assert errs and "crc32" in errs[0]
    open(target, "wb").write(bytes(data[:-2]))
    errs = validate_bundle(p)
    assert any("size" in e for e in errs)
    os.remove(target)
    errs = validate_bundle(p)
    assert any("unreadable" in e for e in errs)


def test_flight_tap_and_flight_dump_event(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = RunJournal(path, run_id="r")
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    fr.attach(j)
    j.manifest()
    for i in range(3):
        j.step(i, step_time_ms=5.0)
    p = fr.dump("manual")
    j.close()
    fr.close()
    events = read_journal(path)
    dumps = [e for e in events if e["event"] == "flight_dump"]
    assert len(dumps) == 1
    assert dumps[0]["reason"] == "manual"
    assert dumps[0]["outcome"] == "written"
    assert dumps[0]["dir"] == p
    # the tap fed the buffers: the bundle's tail is the journal's history
    tail = [json.loads(ln)
            for ln in open(os.path.join(p, "journal_tail.jsonl"))]
    assert [e["event"] for e in tail] == ["run_manifest"] + ["step"] * 3
    from tools.check_journal import check_journal

    assert check_journal(path, strict=True) == []


def test_flight_dumps_on_hang_and_health_abort(tmp_path):
    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    fr.attach(j)
    j.write("health", kind="hang", stalled_s=12.0, timeout_s=5.0,
            stacks={"MainThread": ["frame"]})
    j.write("health", kind="non_finite", action="abort", step=7,
            fields=["loss"])
    assert set(fr.dumped) == {"hang", "health_abort"}
    for p in fr.dumped.values():
        assert validate_bundle(p) == []
    j.close()
    fr.close()


def test_journal_less_health_events_reach_flight(tmp_path):
    """A run with --flight-dir but no --journal must still dump on a
    hang: HealthMonitor feeds the recorder directly when no journal tap
    can route for it."""
    from deep_vision_tpu.obs import HealthMonitor

    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    set_flight(fr)
    h = HealthMonitor(policy="warn", registry=Registry())
    h._emit("hang", stalled_s=9.0, timeout_s=1.0, stacks={"t": ["f"]})
    assert "hang" in fr.dumped
    assert validate_bundle(fr.dumped["hang"]) == []
    health = [json.loads(ln) for ln in
              open(os.path.join(fr.dumped["hang"], "health.jsonl"))]
    assert health and health[0]["kind"] == "hang"
    fr.close()


def test_flight_atexit_dumps_only_while_armed(tmp_path):
    fr = FlightRecorder(str(tmp_path / "armed"), run_id="r")
    fr.observe(_step_row(1))
    fr._atexit()  # simulated interpreter exit without close()
    assert len(find_bundles(str(tmp_path / "armed"))) == 1
    fr.close()

    fr2 = FlightRecorder(str(tmp_path / "disarmed"), run_id="r")
    fr2.close()  # clean exit disarms
    fr2._atexit()
    assert find_bundles(str(tmp_path / "disarmed")) == []


def test_module_level_note_and_emergency_dump(tmp_path):
    # no recorder installed: both are no-ops
    flight_mod.note("probe", x=1)
    assert flight_mod.emergency_dump("manual") is None
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    set_flight(fr)
    flight_mod.note("probe", x=1)
    p = flight_mod.emergency_dump("manual")
    assert p is not None and validate_bundle(p) == []
    notes = [json.loads(ln) for ln in open(os.path.join(p, "notes.jsonl"))]
    assert notes and notes[0]["category"] == "probe" and notes[0]["x"] == 1
    fr.close()
    assert flight_mod.get_flight() is None  # close deregisters itself


def test_flight_bundle_snapshots_span_tail(tmp_path):
    from deep_vision_tpu.obs import Tracer, set_tracer, span

    tracer = Tracer(str(tmp_path / "t.json"), run_id="r")
    set_tracer(tracer)
    try:
        with span("unit/probe", k=1):
            pass
        fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
        p = fr.dump("manual")
        fr.close()
    finally:
        tracer.close()
        set_tracer(None)
    doc = json.load(open(os.path.join(p, "spans.json")))
    names = [e["name"] for e in doc["traceEvents"]]
    assert "unit/probe" in names


def test_journal_tap_exception_swallowed(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = RunJournal(path, run_id="r")

    def bad_tap(row):
        raise RuntimeError("observer must never kill the run")

    j.add_tap(bad_tap)
    j.write("note", note="still written")
    j.close()
    events = read_journal(path)
    assert [e["event"] for e in events] == ["note", "exit"]


# -- per-process file suffix --------------------------------------------------

def test_per_process_paths_for_followers(tmp_path, monkeypatch):
    import jax

    from deep_vision_tpu.obs.registry import process_suffix

    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(jax, "process_index", lambda: 3)
    assert process_suffix() == ".p3"
    path = str(tmp_path / "j.jsonl")
    j = RunJournal(path, run_id="r")
    # the FOLLOWER writes its own suffixed file (it would be a silent
    # non-writer under the old process-0-only contract)
    assert j.path == path + ".p3"
    j.write("note", note="from host 3")
    j.close()
    assert not os.path.exists(path)
    events = read_journal(path + ".p3")
    assert events[0]["note"] == "from host 3"

    from deep_vision_tpu.obs import Tracer

    t = Tracer(str(tmp_path / "t.json"), run_id="r")
    assert t.path.endswith(".p3")
    with t.span("probe"):
        pass
    assert t.num_events > 0  # follower collects AND writes
    t.close()
    assert os.path.exists(str(tmp_path / "t.json") + ".p3")


def test_flight_bundle_per_host_suffix(tmp_path, monkeypatch):
    """Hosts of a pod can share run_id (pid + launch second): on a shared
    flight dir their simultaneous preemption dumps must land at distinct
    per-host paths instead of racing one rename."""
    import jax

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    p = fr.dump("preempt")
    fr.close()
    assert os.path.basename(p) == "r-preempt.p1"
    assert validate_bundle(p) == []
    assert json.load(open(os.path.join(p, "MANIFEST.json")))[
        "process_index"] == 1


def test_tracer_tail(tmp_path):
    from deep_vision_tpu.obs import Tracer

    t = Tracer(str(tmp_path / "t.json"), run_id="r")
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    tail = t.tail(3)
    assert len(tail) == 3
    assert tail[-1]["name"] == "s9"
    t.close()


# -- stepclock peak HBM -------------------------------------------------------

def test_hbm_stats_reads_peak():
    from deep_vision_tpu.obs.stepclock import hbm_stats

    class FakeDev:
        def memory_stats(self):
            return {"bytes_in_use": 100, "peak_bytes_in_use": 250}

    assert hbm_stats(FakeDev()) == (100, 250)

    class ReservedIsLarger:  # the step program's own memory (ROADMAP C11)
        def memory_stats(self):
            return {"bytes_in_use": 100, "peak_bytes_in_use": 250,
                    "peak_bytes_reserved": 900}

    assert hbm_stats(ReservedIsLarger()) == (100, 900)

    class OnlyReserved:
        def memory_stats(self):
            return {"bytes_in_use": 100, "peak_bytes_reserved": 300}

    assert hbm_stats(OnlyReserved()) == (100, 300)

    class NoPeak:
        def memory_stats(self):
            return {"bytes_in_use": 7}

    assert hbm_stats(NoPeak()) == (7, None)

    class NoStats:
        def memory_stats(self):
            return None

    assert hbm_stats(NoStats()) == (None, None)


def test_stepclock_journals_peak_bytes(tmp_path, monkeypatch):
    from deep_vision_tpu.obs import StepClock
    from deep_vision_tpu.obs import stepclock as sc_mod

    monkeypatch.setattr(sc_mod, "hbm_stats", lambda dev=None: (100, 250))
    path = str(tmp_path / "j.jsonl")
    j = RunJournal(path, run_id="r")
    reg = Registry()
    clock = StepClock(registry=reg, journal=j, sample_every=1)
    with clock.step(batch_size=4):
        pass
    j.close()
    step = [e for e in read_journal(path) if e["event"] == "step"][0]
    assert step["hbm_bytes"] == 100
    assert step["hbm_peak_bytes"] == 250
    assert reg.gauge("hbm_peak_bytes_in_use").value == 250


# -- autoprof: windows, triggers, guards -------------------------------------

def _drive(ap, n, ms=10.0, start=1):
    for s in range(start, start + n):
        ap.on_step_start(s)
        ap.observe_step(s, {"step_time_ms": ms})
    return start + n


def test_static_window_configurable(tmp_path, fake_profiler):
    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    ap = AutoProfiler(str(tmp_path / "p"), journal=j, registry=Registry(),
                      window=(3, 5))
    for s in range(1, 8):
        ap.on_step_start(s)
    ap.close()
    j.close()
    assert [c[0] for c in fake_profiler] == ["start", "stop"]
    evs = [e for e in read_journal(str(tmp_path / "j.jsonl"))
           if e["event"] == "profile_capture"]
    assert [(e["reason"], e["outcome"], e["step"]) for e in evs] == [
        ("static_window", "started", 3), ("static_window", "captured", 5)]


def test_static_window_tolerates_resume_past_start(tmp_path, fake_profiler):
    ap = AutoProfiler(str(tmp_path / "p"), registry=Registry(),
                      window=(10, 20))
    ap.on_step_start(14)  # resumed mid-window: capture starts here
    assert ap.capturing
    ap.on_step_start(20)
    assert not ap.capturing
    ap.close()
    assert [c[0] for c in fake_profiler] == ["start", "stop"]


def test_static_window_retries_while_latch_held(tmp_path, fake_profiler):
    """A static window blocked at START by another in-flight capture must
    retry at the next step inside the window, not silently drop the
    user's explicit capture request."""
    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    blocker = AutoProfiler(str(tmp_path / "b"), registry=Registry(),
                           window=(1, 3))
    ap = AutoProfiler(str(tmp_path / "p"), journal=j, registry=Registry(),
                      window=(2, 10))
    blocker.on_step_start(1)   # holds the process-wide latch
    ap.on_step_start(2)        # skipped_inflight — stays pending
    assert not ap.capturing and ap.needs_step_index
    blocker.on_step_start(3)   # blocker's window ends, latch released
    ap.on_step_start(4)        # retry inside [2, 10) succeeds
    assert ap.capturing and not ap.needs_step_index
    ap.close()
    blocker.close()
    j.close()
    outcomes = [e["outcome"] for e in
                read_journal(str(tmp_path / "j.jsonl"))
                if e["event"] == "profile_capture"]
    assert outcomes == ["skipped_inflight", "started", "closed_early"]


def test_needs_step_index_expires_with_window(tmp_path, fake_profiler):
    """needs_step_index (the trainer's pay-the-device-sync gate) is True
    only while the static window is still pending — auto-only profilers
    and consumed/expired windows never cost the per-step fetch."""
    auto_only = AutoProfiler(str(tmp_path / "a"), registry=Registry(),
                             auto=True)
    assert not auto_only.needs_step_index
    auto_only.close()
    ap = AutoProfiler(str(tmp_path / "p"), registry=Registry(),
                      window=(5, 8))
    assert ap.needs_step_index
    ap.on_step_start(100)  # resumed far past the window: expire it
    assert not ap.needs_step_index and not ap.capturing
    ap.close()


def test_counterless_on_step_start_advances(tmp_path, fake_profiler):
    """Bare train_step callers (no observe_step) drive the capture
    lifecycle through the internal counter alone."""
    ap = AutoProfiler(str(tmp_path / "p"), registry=Registry(),
                      window=(2, 4))
    ap.on_step_start(2)        # real index anchors the window
    assert ap.capturing
    ap.on_step_start(None)     # counter: 3
    assert ap.capturing
    ap.on_step_start(None)     # counter: 4 -> stop boundary
    assert not ap.capturing
    ap.close()
    assert [c[0] for c in fake_profiler] == ["start", "stop"]


def test_static_window_rejects_bad_bounds(tmp_path):
    with pytest.raises(ValueError):
        AutoProfiler(str(tmp_path / "p"), registry=Registry(),
                     window=(20, 10))


def test_reentry_guard_skipped_inflight(tmp_path, fake_profiler):
    """A second trigger while a trace is in flight must not double-start
    the (process-global) profiler."""
    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    ap1 = AutoProfiler(str(tmp_path / "p1"), journal=j,
                       registry=Registry(), window=(1, 100))
    ap2 = AutoProfiler(str(tmp_path / "p2"), journal=j,
                       registry=Registry(), window=(1, 100))
    ap1.on_step_start(1)
    assert ap1.capturing
    ap2.on_step_start(1)  # would have been the double-start
    assert not ap2.capturing
    ap1.close()
    ap2.close()
    j.close()
    assert [c[0] for c in fake_profiler] == ["start", "stop"]
    evs = [e for e in read_journal(str(tmp_path / "j.jsonl"))
           if e["event"] == "profile_capture"]
    assert [e["outcome"] for e in evs] == ["started", "skipped_inflight",
                                           "closed_early"]


def test_close_stops_inflight_and_releases_latch(tmp_path, fake_profiler):
    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    ap = AutoProfiler(str(tmp_path / "p"), journal=j, registry=Registry(),
                      window=(1, 10_000))
    ap.on_step_start(1)
    assert ap.capturing
    ap.close()
    assert not ap.capturing
    ap.close()  # idempotent
    j.close()
    assert [c[0] for c in fake_profiler] == ["start", "stop"]
    evs = [e for e in read_journal(str(tmp_path / "j.jsonl"))
           if e["event"] == "profile_capture"]
    assert evs[-1]["outcome"] == "closed_early"
    # the latch is free again: a fresh profiler can capture
    ap2 = AutoProfiler(str(tmp_path / "p2"), registry=Registry(),
                       window=(1, 2))
    ap2.on_step_start(1)
    assert ap2.capturing
    ap2.close()


def test_step_time_z_trigger_and_cooldown(tmp_path, fake_profiler):
    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    ap = AutoProfiler(str(tmp_path / "p"), journal=j, registry=Registry(),
                      auto=True, window_steps=2, cooldown_steps=30,
                      max_captures=1, z_threshold=4.0, min_history=8)
    s = _drive(ap, 12)                      # baseline
    ap.on_step_start(s)
    ap.observe_step(s, {"step_time_ms": 500.0})  # regression -> arm
    s += 1
    s = _drive(ap, 4, start=s)              # capture runs + stops
    ap.close()
    j.close()
    evs = [e for e in read_journal(str(tmp_path / "j.jsonl"))
           if e["event"] == "profile_capture"]
    assert [e["outcome"] for e in evs] == ["started", "captured"]
    assert evs[0]["reason"] == "step_time_z"
    assert evs[0]["z"] > 4.0


def test_spikes_stay_out_of_baseline(tmp_path, fake_profiler):
    """Consecutive regressions must keep registering: a spike admitted to
    the rolling window would inflate the std until triggers went blind."""
    # budget 0: every spike is evaluated (none spent inside a capture
    # window), so the trigger counter isolates the baseline-exclusion rule
    ap = AutoProfiler(str(tmp_path / "p"), registry=Registry(), auto=True,
                      cooldown_steps=0, max_captures=0,
                      z_threshold=4.0, min_history=8, window_steps=1)
    s = _drive(ap, 12)
    triggers_before = ap._c_triggers.value
    for _ in range(5):
        ap.on_step_start(s)
        ap.observe_step(s, {"step_time_ms": 500.0})
        s += 1
    ap.close()
    assert ap._c_triggers.value - triggers_before == 5


def test_static_window_does_not_consume_cooldown(tmp_path, fake_profiler):
    """Like the budget, the cooldown is spent only by TRIGGERED captures:
    a static window ending at step N must not blind the anomaly policy
    until N + cooldown."""
    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    ap = AutoProfiler(str(tmp_path / "p"), journal=j, registry=Registry(),
                      window=(1, 3), auto=True, window_steps=2,
                      cooldown_steps=1000, max_captures=1,
                      z_threshold=4.0, min_history=8)
    s = _drive(ap, 14)  # consumes the static window, builds the baseline
    ap.on_step_start(s)
    ap.observe_step(s, {"step_time_ms": 500.0})  # regression right after
    s += 1
    s = _drive(ap, 4, start=s)
    ap.close()
    j.close()
    evs = [(e["reason"], e["outcome"]) for e in
           read_journal(str(tmp_path / "j.jsonl"))
           if e["event"] == "profile_capture"]
    assert ("step_time_z", "captured") in evs
    assert not any(o == "skipped_cooldown" for _r, o in evs)


def test_divergence_abort_dumps_health_abort_bundle(tmp_path):
    """The documented health_abort trigger must fire for divergence
    escalation under the abort policy, not only for non_finite aborts."""
    from deep_vision_tpu.obs import HealthMonitor, TrainingHealthError

    j = RunJournal(str(tmp_path / "j.jsonl"), run_id="r")
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    fr.attach(j)
    h = HealthMonitor(policy="abort", journal=j, registry=Registry(),
                      min_history=5, patience=2, z_threshold=3.0)
    with pytest.raises(TrainingHealthError):
        for step in range(40):
            h.check_step(step, loss=1.0 + 0.001 * (step % 3))
        for step in range(40, 50):
            h.check_step(step, loss=100.0)
    assert "health_abort" in fr.dumped
    assert validate_bundle(fr.dumped["health_abort"]) == []
    j.close()
    fr.close()


def test_flight_note_keeps_structured_values(tmp_path):
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r")
    fr.note("probe", mesh_shape={"data": 2, "model": 1}, dims=[1, 2])
    p = fr.dump("manual")
    fr.close()
    notes = [json.loads(ln) for ln in open(os.path.join(p, "notes.jsonl"))]
    assert notes[0]["mesh_shape"] == {"data": 2, "model": 1}
    assert notes[0]["dims"] == [1, 2]


def test_recompile_burst_trigger(tmp_path, fake_profiler):
    ap = AutoProfiler(str(tmp_path / "p"), registry=Registry(), auto=True,
                      recompile_burst=2, min_history=1000)  # z-path off
    ap.observe_step(1, {"step_time_ms": 10.0, "recompiles": 3})
    assert ap._armed is None  # first observation only sets the baseline
    ap.observe_step(2, {"step_time_ms": 10.0, "recompiles": 3})
    assert ap._armed is None  # no new compiles
    ap.observe_step(3, {"step_time_ms": 10.0, "recompiles": 6})
    assert ap._armed is not None and ap._armed[0] == "recompile_burst"
    ap.close()


def test_hbm_jump_trigger(tmp_path, fake_profiler):
    ap = AutoProfiler(str(tmp_path / "p"), registry=Registry(), auto=True,
                      hbm_jump_frac=0.25, min_history=1000)
    ap.observe_step(1, {"step_time_ms": 10.0, "hbm_peak_bytes": 1000})
    assert ap._armed is None  # high-water baseline
    ap.observe_step(2, {"step_time_ms": 10.0, "hbm_peak_bytes": 1100})
    assert ap._armed is None  # +10% < 25% jump
    ap.observe_step(3, {"step_time_ms": 10.0, "hbm_peak_bytes": 1400})
    assert ap._armed is not None and ap._armed[0] == "hbm_jump"
    ap.close()


# -- trainer integration ------------------------------------------------------

def _tiny_trainer(mesh8, **kw):
    import jax.numpy as jnp

    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    return Trainer(
        get_model("lenet5", num_classes=4),
        build_optimizer("adam", 1e-3),
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


def test_trainer_close_stops_inflight_autocapture(tmp_path, mesh8):
    """Satellite regression test: Trainer.close() must stop an in-flight
    (auto-)capture without leaking — journaled as closed_early, and the
    process-wide latch released for the next run."""
    path = str(tmp_path / "j.jsonl")
    journal = RunJournal(path, run_id="r")
    trainer = _tiny_trainer(
        mesh8, journal=journal,
        profile_dir=str(tmp_path / "trace"),
        profile_steps=(1, 10_000),  # stop gate unreachable in a short run
    )
    for batch in _tiny_batches(2):
        trainer.train_step(batch)
    assert trainer._profiling, "capture should be open mid-run"
    trainer.close()
    assert not trainer._profiling
    trainer.close()  # idempotent
    journal.close()
    evs = [e for e in read_journal(path) if e["event"] == "profile_capture"]
    assert [e["outcome"] for e in evs] == ["started", "closed_early"]
    from deep_vision_tpu.obs import autoprof as ap_mod

    assert not ap_mod._capture_active, "profiler latch leaked"
    found = []
    for _root, _dirs, files in os.walk(tmp_path / "trace"):
        found += files
    assert found, "closed capture produced no artifacts"


def test_trainer_static_window_journals_profile_capture(tmp_path, mesh8):
    path = str(tmp_path / "j.jsonl")
    journal = RunJournal(path, run_id="r")
    trainer = _tiny_trainer(
        mesh8, journal=journal,
        profile_dir=str(tmp_path / "trace"), profile_steps=(1, 3),
    )
    for batch in _tiny_batches(5):
        trainer.train_step(batch)
    assert not trainer._profiling
    trainer.close()
    journal.close()
    evs = [e for e in read_journal(path) if e["event"] == "profile_capture"]
    assert [(e["reason"], e["outcome"]) for e in evs] == [
        ("static_window", "started"), ("static_window", "captured")]
    from tools.check_journal import check_journal

    assert check_journal(path, strict=True) == []


# -- merge + straggler detection ----------------------------------------------

def _host_journal(tmp_path, host, slow=(), n=20, base_ms=50.0,
                  slow_ms=300.0):
    path = str(tmp_path / f"j.jsonl.p{host}")
    rows = [{"event": "run_manifest", "ts": 100.0, "kind": "train",
             "argv": [], "run_id": f"h{host}", "process_index": host,
             "process_count": 2}]
    for s in range(1, n + 1):
        rows.append({"event": "step", "ts": 100.0 + s, "run_id": f"h{host}",
                     "step": s,
                     "step_time_ms": slow_ms if s in slow else base_ms})
    rows.append({"event": "exit", "ts": 100.0 + n + 1,
                 "status": "clean_exit", "run_id": f"h{host}"})
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return path


def test_merge_detects_straggler_and_annotates_hosts(tmp_path):
    from deep_vision_tpu.obs.merge import merge_journal_files

    p0 = _host_journal(tmp_path, 0)
    p1 = _host_journal(tmp_path, 1, slow={5, 6})
    out = str(tmp_path / "merged.jsonl")
    summary = merge_journal_files([p0, p1], out)
    assert summary["hosts"] == [0, 1]
    assert len(summary["stragglers"]) == 2
    events = read_journal(out)
    assert events[0]["event"] == "note" and events[0]["note"] == "obs_merge"
    stragglers = [e for e in events if e["event"] == "straggler"]
    assert {e["step"] for e in stragglers} == {5, 6}
    assert all(e["host"] == 1 for e in stragglers)
    # 2 hosts: median of (50, 300) = 175, gap = 125
    assert stragglers[0]["gap_ms"] == pytest.approx(125.0)
    # every source event is host-annotated, and the timeline is sorted
    hosts = {e.get("host") for e in events if e["event"] == "step"}
    assert hosts == {0, 1}
    ts = [e["ts"] for e in events if e.get("ts") is not None]
    assert ts == sorted(ts)
    from tools.check_journal import check_journal

    assert check_journal(out, strict=True) == []


def test_straggler_thresholds_absolute_and_relative(tmp_path):
    from deep_vision_tpu.obs.merge import detect_stragglers

    def steps(times):
        return {h: {1: {"step": 1, "ts": 0.0, "step_time_ms": t}}
                for h, t in enumerate(times)}

    # 10ms gap: below the 25ms absolute floor even though relative is huge
    assert detect_stragglers(steps([1.0, 11.0])) == []
    # 30ms gap on a 5s step: above absolute, below relative -> noise
    assert detect_stragglers(steps([5000.0, 5030.0])) == []
    # 200ms gap on a 100ms median: both floors cleared
    out = detect_stragglers(steps([100.0, 100.0, 300.0]))
    assert len(out) == 1 and out[0]["host"] == 2
    # a step only one host reported can never flag
    assert detect_stragglers({0: {1: {"step": 1, "ts": 0.0,
                                      "step_time_ms": 900.0}}}) == []


def test_host_index_fallbacks(tmp_path):
    from deep_vision_tpu.obs.merge import host_index

    assert host_index("x.jsonl", [{"event": "run_manifest",
                                   "process_index": 7}], 0) == 7
    assert host_index("x.jsonl.p3", [], 0) == 3
    assert host_index("x.jsonl", [], 5) == 5


def test_obs_merge_cli_auto_glob(tmp_path, capsys):
    from tools.obs_merge import main as merge_main

    _host_journal(tmp_path, 0)
    _host_journal(tmp_path, 1, slow={9})
    base = str(tmp_path / "j.jsonl")
    rc = merge_main(["--auto", base])
    assert rc == 0
    out = capsys.readouterr().out
    assert "hosts [0, 1]" in out and "stragglers: 1" in out
    assert os.path.exists(base + ".merged")


def test_obs_report_merged_rendering(tmp_path, capsys):
    from deep_vision_tpu.obs.merge import merge_journal_files
    from tools.obs_report import main as report_main

    p0 = _host_journal(tmp_path, 0)
    p1 = _host_journal(tmp_path, 1, slow={5})
    out = str(tmp_path / "merged.jsonl")
    merge_journal_files([p0, p1], out)
    rc = report_main([out, "--merged"])
    assert rc == 0
    text = capsys.readouterr().out
    assert "host 0: 20 steps" in text
    assert "host 1: 20 steps" in text
    assert "stragglers (1)" in text
    assert "gap 125.0 ms" in text


def test_span_summary_has_percentiles(tmp_path, capsys):
    from tools.obs_report import render_trace, summarize_trace

    events = [{"name": "s", "ph": "X", "ts": i, "dur": (i + 1) * 1000.0,
               "pid": 1, "tid": 1} for i in range(10)]
    path = str(tmp_path / "t.json")
    json.dump({"traceEvents": events}, open(path, "w"))
    spans = summarize_trace(path)
    assert spans[0]["count"] == 10
    assert spans[0]["p50_ms"] == pytest.approx(5.0, abs=1.1)
    assert spans[0]["p95_ms"] == pytest.approx(10.0, abs=1.1)
    text = render_trace(spans, path)
    assert "p50 ms" in text and "p95 ms" in text


# -- check_journal: new event schemas ----------------------------------------

def _write_journal(tmp_path, rows, name="j.jsonl"):
    path = str(tmp_path / name)
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return path


BASE = [{"event": "run_manifest", "ts": 1.0, "run_id": "r",
         "kind": "train", "argv": []}]
EXIT = [{"event": "exit", "ts": 9.0, "run_id": "r",
         "status": "clean_exit"}]


def test_check_journal_accepts_new_event_types(tmp_path):
    from tools.check_journal import check_journal

    path = _write_journal(tmp_path, BASE + [
        {"event": "profile_capture", "ts": 2.0, "run_id": "r",
         "reason": "step_time_z", "outcome": "captured", "step": 40},
        {"event": "flight_dump", "ts": 3.0, "run_id": "r",
         "reason": "hang", "outcome": "written", "dir": "/tmp/x"},
        {"event": "straggler", "ts": 4.0, "run_id": "r", "step": 7,
         "gap_ms": 120.5, "host": 3},
    ] + EXIT)
    assert check_journal(path, strict=True) == []


def test_check_journal_rejects_bad_new_events(tmp_path):
    from tools.check_journal import check_journal

    path = _write_journal(tmp_path, BASE + [
        {"event": "profile_capture", "ts": 2.0, "run_id": "r",
         "reason": "vibes", "outcome": "captured"},
        {"event": "profile_capture", "ts": 2.1, "run_id": "r",
         "reason": "step_time_z", "outcome": "maybe"},
        {"event": "flight_dump", "ts": 3.0, "run_id": "r",
         "reason": "bored", "outcome": "written", "dir": "/tmp/x"},
        {"event": "flight_dump", "ts": 3.1, "run_id": "r",
         "reason": "crash", "outcome": "written"},  # missing dir
        {"event": "straggler", "ts": 4.0, "run_id": "r", "step": 7,
         "gap_ms": "huge", "host": "h3"},
    ] + EXIT)
    errs = check_journal(path, strict=True)
    assert any("profile_capture reason" in e for e in errs)
    assert any("profile_capture outcome" in e for e in errs)
    assert any("flight_dump reason" in e for e in errs)
    assert any("missing field 'dir'" in e for e in errs)
    assert any("straggler host" in e for e in errs)
    assert any("straggler gap_ms" in e for e in errs)


def test_check_journal_cli_exit_codes_new_events(tmp_path):
    from tools.check_journal import EXIT_INVALID, EXIT_OK, main

    good = _write_journal(tmp_path, BASE + [
        {"event": "profile_capture", "ts": 2.0, "run_id": "r",
         "reason": "manual", "outcome": "started"},
    ] + EXIT, name="good.jsonl")
    assert main([good, "--strict"]) == EXIT_OK
    bad = _write_journal(tmp_path, BASE + [
        {"event": "flight_dump", "ts": 2.0, "run_id": "r",
         "reason": "crash", "outcome": "lost", "dir": "/x"},
    ] + EXIT, name="bad.jsonl")
    assert main([bad, "--strict"]) == EXIT_INVALID
