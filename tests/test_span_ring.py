"""The span ring (ISSUE 38): every span of `obs.trace.span` is written, with
the stamps it took at entry and exit, into a process-wide ring that needs no
profiler session; what reads it (the stall event, `spans.json` beside a
capture, the flight bundle's span tail) and the clock it is on."""
import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
import types

import jax
import numpy as np
import pytest

from deep_vision_tpu.obs import RunJournal, read_journal
from deep_vision_tpu.obs import stepclock as stepclock_mod
from deep_vision_tpu.obs import trace as trace_mod
from deep_vision_tpu.obs.registry import Registry
from deep_vision_tpu.obs.trace import Span, SpanRing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trainer(mesh8, **kw):
    import jax.numpy as jnp

    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    return Trainer(get_model("lenet5", num_classes=4),
                   build_optimizer("adam", 1e-3), classification_loss_fn,
                   jnp.ones((2, 32, 32, 1)), mesh=mesh8, **kw)


def _batches(n, bs=8):
    rng = np.random.RandomState(0)
    return [{"image": rng.rand(bs, 32, 32, 1).astype(np.float32),
             "label": rng.randint(0, 4, (bs,)).astype(np.int32)}
            for _ in range(n)]


# -- the ring ------------------------------------------------------------------

def test_ring_overwrites_the_oldest_and_counts_it():
    ring = SpanRing(8)
    assert ring.spans() == [] and ring.overwritten() == 0
    for i in range(5):
        ring.put("a", 10 * i, 10 * i + 5, i, None)
    assert [s.step for s in ring.spans()] == [0, 1, 2, 3, 4]
    assert ring.overwritten() == 0
    for i in range(5, 11):
        ring.put("a", 10 * i, 10 * i + 5, i, {"k": i})
    held = ring.spans()
    assert [s.step for s in held] == list(range(3, 11))  # oldest first
    assert ring.overwritten() == 3
    assert held[-1] == Span("a", 100, 105, 10, threading.get_ident(),
                            {"k": 10})
    assert held[0].args is None and held[2].args == {"k": 5}
    with pytest.raises(ValueError, match="power of two"):
        SpanRing(12)


def test_process_ring_has_the_issues_capacity_and_no_holes():
    """The module's ring: 65,536 slots; more spans than that leave exactly
    the newest 65,536, in order."""
    assert trace_mod.RING_CAPACITY == 65_536
    before = trace_mod.overwritten()
    n = trace_mod.RING_CAPACITY + 10
    for i in range(n):
        trace_mod._ring.put("unit/fill", i, i + 1, i, None)
    held = trace_mod.spans()
    assert len(held) == trace_mod.RING_CAPACITY
    # (a collection meanwhile writes a `gc/collect` of its own)
    mine = [s.step for s in held if s.name == "unit/fill"]
    assert len(held) - len(mine) < 100
    assert mine == list(range(n - len(mine), n))
    assert trace_mod.overwritten() - before >= 10


def test_spans_filters_by_end_stamp_and_thread():
    ring = SpanRing(16)
    other = {}

    def writer():
        other["ident"] = threading.get_ident()
        ring.put("b", 50, 60, None, None)

    t = threading.Thread(target=writer)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    ring.put("a", 0, 10, 1, None)
    ring.put("a", 100, 110, 2, None)
    assert [s.end_ns for s in ring.spans(since_ns=60)] == [60, 110]
    assert [s.name for s in ring.spans(thread=other["ident"])] == ["b"]
    mine = ring.spans(since_ns=11, thread=threading.get_ident())
    assert [s.step for s in mine] == [2]


def test_two_threads_lose_no_span():
    """More writers than cores, a short switch interval: every span of
    every thread is in the ring once (a shared slot would lose one)."""
    n_threads, n_each = 16, 400
    tag = f"unit/race-{time.time_ns()}"
    start = threading.Event()

    def writer(k):
        start.wait(timeout=30)
        for i in range(n_each):
            with trace_mod.span(tag, step=k * n_each + i):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        start.set()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    mine = [s for s in trace_mod.spans() if s.name == tag]
    assert sorted(s.step for s in mine) == list(range(n_threads * n_each))
    assert len({s.thread for s in mine}) == n_threads


def test_a_span_that_raises_is_still_recorded():
    t0 = time.time_ns()
    with pytest.raises(KeyError):
        with trace_mod.span("unit/raises", step=3, k="v"):
            raise KeyError("x")
    last, = [s for s in trace_mod.spans(since_ns=t0)
             if s.name == "unit/raises"]
    assert last.step == 3 and last.args == {"k": "v", "error": "KeyError"}
    assert t0 <= last.start_ns <= last.end_ns <= time.time_ns()
    assert last.thread == threading.get_ident()


def test_a_span_without_arguments_stores_none():
    t0 = time.time_ns()
    with trace_mod.span("unit/bare"):
        pass
    with trace_mod.span("unit/step-only", step=9) as sp:
        sp.set(step=11)
    bare, stepped = [s for s in trace_mod.spans(since_ns=t0)
                     if s.name.startswith("unit/")][-2:]
    assert bare.args is None and bare.step is None
    assert stepped.args is None and stepped.step == 11


def test_a_clock_stepped_back_under_a_span_keeps_it_at_zero_length():
    ring = SpanRing(4)
    ring.put("a", 1_000, 900, None, None)
    ring.put("a", 1_000, 1_001, None, None)
    assert [(s.start_ns, s.end_ns) for s in ring.spans()] == [
        (1_000, 1_000), (1_000, 1_001)]


def test_tracer_ring_and_annotation_get_one_pair_of_stamps(tmp_path):
    capture = str(tmp_path / "capture")
    tracer = trace_mod.Tracer(str(tmp_path / "t.json"))
    trace_mod.set_tracer(tracer)
    trace_mod.start_profiler(capture)  # host plane on: the annotation shows
    try:
        with trace_mod.span("unit/stamps", step=7, n=2) as sp:
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
        trace_mod.set_tracer(None)
        tracer.close()
    ring, = [s for s in trace_mod.spans() if s.name == "unit/stamps"
             and s.start_ns == sp.start_ns]
    assert (ring.start_ns, ring.end_ns) == (sp.start_ns, sp.end_ns)
    assert ring.step == 7 and ring.args == {"n": 2}
    chrome, = [e for e in json.load(open(tracer.path))["traceEvents"]
               if e["ph"] == "X"]
    assert chrome["ts"] == round(sp.start_ns / 1e3, 1)
    assert chrome["dur"] == round(sp.end_ns / 1e3 - sp.start_ns / 1e3, 1)
    assert chrome["args"] == {"step": 7, "n": 2}
    # the profiler's own stamps of the same object, on the capture's clock:
    # its events count from `profile_start_time`, as the span's stamps do
    # once that is taken off
    from tools import trace_digest

    xplane, = trace_digest.find_xplanes(capture)
    data = jax.profiler.ProfileData.from_file(xplane)
    epoch, stop = trace_digest.capture_epoch_ns(list(data.planes))
    assert epoch <= sp.start_ns <= sp.end_ns <= stop
    event, = [e for plane in data.planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name == "unit/stamps"]
    # (another clock would stand seconds or years off; a descheduled
    # thread between the two reads, milliseconds)
    assert abs(event.start_ns - (sp.start_ns - epoch)) < 50_000_000
    assert abs(event.duration_ns - (sp.end_ns - sp.start_ns)) < 50_000_000


def test_now_us_and_trace_event_are_on_the_ring_clock():
    t0 = trace_mod.now_us()
    assert abs(t0 * 1e3 - time.time_ns()) < 50e6
    trace_mod.trace_event("unit/explicit", t0, t0 + 250.0, loader="x", step=4)
    last = [s for s in trace_mod.spans() if s.name == "unit/explicit"][-1]
    assert last.step == 4 and last.args == {"loader": "x"}
    assert last.end_ns - last.start_ns == pytest.approx(250_000, abs=2_000)
    assert not hasattr(trace_mod, "_now_us")
    assert not hasattr(trace_mod, "_ANCHOR_PERF")


# -- gc/collect ------------------------------------------------------------------

def test_gc_collect_spans_for_full_and_for_long_collections(monkeypatch):
    import gc

    trace_mod.watch_gc()
    trace_mod.watch_gc()  # idempotent
    assert gc.callbacks.count(trace_mod._on_gc) == 1
    assert trace_mod.GC_MIN_NS == 1_000_000  # a millisecond, as shipped
    monkeypatch.setattr(trace_mod, "GC_MIN_NS", 10 ** 12)
    t0 = time.time_ns()
    gc.collect(0)  # young and under the limit: dropped
    assert not [s for s in trace_mod.spans(since_ns=t0)
                if s.name == "gc/collect"]
    gc.collect()  # generation 2: kept however short
    full, = [s for s in trace_mod.spans(since_ns=t0)
             if s.name == "gc/collect"]
    assert full.args == {"generation": 2}
    assert full.thread == threading.get_ident()
    assert t0 <= full.start_ns <= full.end_ns
    # a young collection over the limit (1 ms as shipped) is kept too
    assert trace_mod.GC_MIN_NS == 10 ** 12
    monkeypatch.setattr(trace_mod, "GC_MIN_NS", -1)
    t1 = time.time_ns()
    gc.collect(0)
    young, = [s for s in trace_mod.spans(since_ns=t1)
              if s.name == "gc/collect"]
    assert young.args == {"generation": 0}


# -- the stall rule and its split ------------------------------------------------

def test_stall_rule_needs_sixteen_steps_and_three_medians():
    rule = stepclock_mod.StallRule()
    # nothing is a stall before 16 are seen, however long
    assert [rule.observe(ms) for ms in [10.0] * 14 + [5000.0]] == [False] * 15
    assert not rule.observe(10.0)  # the 16th sets the limit
    assert rule.median_ms == 10.0 and rule.limit_ms == 30.0
    assert not rule.observe(30.0)  # three medians exactly: not over
    assert rule.observe(30.001)
    assert rule.observe(5000.0)
    # the limit follows the last 64 committed
    for _ in range(64):
        rule.observe(100.0)
    assert rule.median_ms == 100.0
    assert not rule.observe(299.0) and rule.observe(301.0)


_MS = 1_000_000


def _rec(lo_ms, hi_ms, name="train"):
    rec = types.SimpleNamespace(wall_ns=(lo_ms * _MS, hi_ms * _MS),
                                _clock=types.SimpleNamespace(name=name))
    return rec


def _put(name, lo_ms, hi_ms, **args):
    trace_mod._ring.put(name, lo_ms * _MS, hi_ms * _MS, args.pop("step", None),
                        args or None)


@pytest.mark.parametrize("cause", ["fetch", "gc", "other", "data_wait"])
def test_stall_split_names_its_cause(cause):
    """A 5 s step on a fake clock (stamps in 1970, where no other test
    looks; on this thread): the loop's usual spans, and one long stretch."""
    base = 10 ** 6 * (1 + ["fetch", "gc", "other", "data_wait"].index(cause))
    long_ms = 5000
    t = base + 2  # the previous commit was at base, inside its train/log
    _put("train/log", base - 1, t, step=1)
    wait = long_ms if cause == "data_wait" else 1
    _put("train/data_wait", t, t + wait, step=3)
    t += wait
    step_start = t
    _put("train/place", t, t + 2, step=3, bytes=8)
    _put("train/dispatch", t + 2, t + 5, step=3)
    t += 5
    if cause == "other":
        t += long_ms  # nothing covers it (the thread was descheduled)
    fetch = long_ms if cause == "fetch" else 20
    if cause == "gc":  # a collection inside the fetch span's python part
        _put("gc/collect", t + 1, t + 1 + long_ms, generation=2)
        fetch += long_ms
    _put("train/fetch", t + 3, t + 4, step=2, n=0)  # the fence, nested
    _put("train/fetch", t, t + fetch, step=2, n=1)
    t += fetch
    # the commit comes 1 ms into a `train/log` that is still open
    open_log = types.SimpleNamespace(name="train/log", start_ns=t * _MS,
                                     step=2)
    split = stepclock_mod.stall_split(_rec(base, t + 1),
                                      open_spans=(open_log,))
    assert max(split, key=split.get) == cause
    assert split[cause] >= long_ms - 1  # the fence's 1 ms lies inside
    assert sum(split.values()) == pytest.approx(t + 1 - base)
    assert split["place"] == 2 and split["dispatch"] == 3
    assert split["log"] == 2 + 1  # the last one's tail, this one's head
    if cause != "data_wait":
        assert split["data_wait"] == 1
    if cause != "fetch":  # under a collection the fence's 1 ms still shows
        assert split["fetch"] == (21 if cause == "gc" else 20)
    if cause != "other":
        assert split["other"] == 0
    assert "train/step" not in split and step_start  # names nothing


def test_split_wall_takes_the_innermost_and_clips():
    spans = [Span("outer", 0, 100, None, 1, None),
             Span("inner", 40, 60, None, 1, None),
             Span("unnamed", 0, 1000, None, 1, None),
             Span("inner", 90, 150, None, 1, None)]
    out = trace_mod.split_wall(spans, 10, 120,
                               {"outer": "o", "inner": "i"})
    assert out == {"o": 30 + 30, "i": 20 + 30, "other": 0}
    out = trace_mod.split_wall(spans, 200, 300, {"outer": "o", "inner": "i"})
    assert out == {"o": 0, "i": 0, "other": 100}


def test_trainer_journals_a_stall_with_its_cause(tmp_path, mesh8):
    """A feed that sleeps once, long: one `stall` event, in `data_wait`,
    the counter raised, a line on stderr; the short steps raise none."""
    path = str(tmp_path / "j.jsonl")
    journal = RunJournal(path, run_id="r")
    reg = Registry()
    trainer = _trainer(mesh8, journal=journal, registry=reg)
    data = _batches(4)
    trainer.fit(lambda: data[:2], epochs=1, handle_preemption=False)  # compile

    def feed():
        for i in range(40):
            if i == 30:
                time.sleep(1.0)
            yield data[i % 4]

    trainer.fit(feed, epochs=1, handle_preemption=False)
    trainer.close()
    journal.close()
    stalls = [e for e in read_journal(path) if e["event"] == "stall"]
    rows = {e["step"]: e for e in read_journal(path) if e["event"] == "step"}
    planted = [e for e in stalls if e["cause"] == "data_wait"
               and e["split_ms"]["data_wait"] >= 1000.0]
    assert len(planted) == 1, stalls
    event = planted[0]
    assert event["step_time_ms"] == rows[event["step"]]["step_time_ms"]
    assert event["step_time_ms"] > 3 * event["median_ms"] > 0
    assert set(event["split_ms"]) == {"data_wait", "place", "dispatch",
                                      "fetch", "log", "gc", "other"}
    assert sum(event["split_ms"].values()) == pytest.approx(
        event["step_time_ms"], abs=0.01)
    # the loop reads a report one dispatch late, so the wall between two
    # commits holds the wait for the *next* dispatch's batch: that row's
    # `data_wait_ms`, the same region timed on the monotonic clock
    assert rows[event["step"] + 1]["data_wait_ms"] == pytest.approx(
        event["split_ms"]["data_wait"], rel=2e-3)
    assert reg.counter("train_stalls_total").value == len(stalls)
    assert all(e["dispatch"] > 2 + 16 for e in stalls)  # never under 16 seen
    from tools.check_journal import check_journal

    assert check_journal(path, strict=True) == []


def test_journal_rows_and_ring_time_the_same_regions(tmp_path, mesh8):
    """`data_wait_ms` brackets the `train/data_wait` span and `dispatch_ms`
    the loop's place and enqueue, each on the monotonic clock: a row and
    the ring agree to the clock reads between them."""
    path = str(tmp_path / "j.jsonl")
    journal = RunJournal(path, run_id="r")
    trainer = _trainer(mesh8, journal=journal)
    t0 = time.time_ns()
    trainer.fit(lambda: _batches(3), epochs=1, handle_preemption=False)
    trainer.close()
    journal.close()
    rows = [e for e in read_journal(path) if e["event"] == "step"]
    held = [s for s in trace_mod.spans(since_ns=t0,
                                       thread=threading.get_ident())]
    waits = [s for s in held if s.name == "train/data_wait"]
    steps = [s for s in held if s.name == "train/step"]
    places = [s for s in held if s.name == "train/place"]
    dispatches = [s for s in held if s.name == "train/dispatch"]
    assert len(rows) == len(steps) == 3 and len(waits) == 4
    for row, wait, step, place, dispatch in zip(rows, waits, steps, places,
                                                dispatches):
        assert row["data_wait_ms"] == pytest.approx(
            (wait.end_ns - wait.start_ns) * 1e-6, abs=0.5)
        # from before `train/place` to past the enqueue, inside `train/step`
        enqueued = (dispatch.end_ns - place.start_ns) * 1e-6
        assert row["dispatch_ms"] == pytest.approx(enqueued, abs=0.5)
        assert row["dispatch_ms"] <= (step.end_ns - step.start_ns) * 1e-6 + 0.5


class _Clocks:
    """`time`, with a wall clock and a monotonic one the test sets."""

    def __init__(self):
        self.mono_s, self.wall_ns = 100.0, 10 ** 18

    def perf_counter(self):
        return self.mono_s

    def time_ns(self):
        return self.wall_ns

    def __getattr__(self, name):
        return getattr(time, name)


def test_a_stepped_wall_clock_moves_no_duration_and_raises_no_stall(
        monkeypatch):
    """What drives decisions (`step_time_ms`, `data_wait_ms`, `dispatch_ms`,
    the stall rule) is timed on the monotonic clock: the wall clock set an
    hour ahead and two back under a run changes none of it, and `wall_ns`
    stays `step_time_ms` long."""
    clocks = _Clocks()
    monkeypatch.setattr(stepclock_mod, "time", clocks)
    clock = stepclock_mod.StepClock(name="unit", registry=Registry(),
                                    track_memory=False)
    rule = stepclock_mod.StallRule()
    seen = []

    def feed():
        for i in range(40):
            clocks.mono_s += 0.002  # the feed's work
            if i == 20:
                clocks.wall_ns += 3600 * 10 ** 9
            if i == 30:
                clocks.wall_ns -= 7200 * 10 ** 9
            yield i

    for _ in clock.iter_data(feed()):
        with clock.step(batch_size=8) as rec:
            clocks.mono_s += 0.008  # the dispatch
            clocks.wall_ns += 10 * _MS
        seen.append((rec, rule.observe(rec.step_time_ms)))
    assert len(seen) == 40 and not any(stalled for _, stalled in seen)
    for rec, _ in seen:
        assert rec.step_time_ms == pytest.approx(10.0)
        assert rec.data_wait_ms == pytest.approx(2.0)
        assert rec.dispatch_ms == pytest.approx(8.0)
        assert rec.wall_ns[1] - rec.wall_ns[0] == pytest.approx(10 * _MS)
    assert rule.median_ms == pytest.approx(10.0)


# -- set-up ----------------------------------------------------------------------

def test_build_trainer_journals_where_its_wall_went(tmp_path, mesh8, capsys):
    """`setup/build_trainer` and the spans inside it, and what reads them:
    one `setup` event and one `[setup]` line with the span's wall split
    over `imports / init_state / other`."""
    from deep_vision_tpu import train_cli
    from deep_vision_tpu.configs import ExperimentConfig
    from tools.check_journal import check_journal

    cfg = ExperimentConfig(
        name="lenet5", task="classification", model="lenet5",
        model_kwargs={}, batch_size=8, input_shape=(32, 32, 1),
        num_classes=4,
        optimizer={"name": "sgd", "learning_rate": 0.1, "momentum": 0.9})
    path = str(tmp_path / "j.jsonl")
    journal = RunJournal(path, run_id="r")
    t0 = time.time_ns()
    trainer = train_cli.build_trainer(cfg, None, ckpt_dir=None,
                                      steps_per_epoch=4, journal=journal)
    trainer.close()
    journal.close()
    setup = [s for s in trace_mod.spans(since_ns=t0)
             if s.name.startswith("setup/")]
    assert [s.name for s in setup] == [
        "setup/imports", "setup/init_state",
        "setup/build_trainer"]  # in the order they end
    imports, init, outer = setup
    assert outer.start_ns <= imports.start_ns <= imports.end_ns
    assert imports.end_ns <= init.start_ns <= init.end_ns <= outer.end_ns
    assert {s.thread for s in setup} == {threading.get_ident()}
    event, = [e for e in read_journal(path) if e["event"] == "setup"]
    assert event["build_trainer_s"] == round(
        (outer.end_ns - outer.start_ns) * 1e-9, 3)
    assert set(event["split_s"]) == {"imports", "init_state", "other"}
    assert event["split_s"]["init_state"] == round(
        (init.end_ns - init.start_ns) * 1e-9, 3)
    assert sum(event["split_s"].values()) == pytest.approx(
        event["build_trainer_s"], abs=0.002)
    assert "[setup] build_trainer" in capsys.readouterr().err
    assert check_journal(path, strict=True) == []
    assert train_cli.build_trainer.__wrapped__.__name__ == "build_trainer"


# -- a capture's clock, with both host tracers off ---------------------------------

def _capture_with_tracers_off(tmp_path, body):
    cap = str(tmp_path / "cap")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    before = time.time_ns()
    jax.profiler.start_trace(cap, profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return cap, before, time.time_ns()


def _device_planes(spans, lag_ns=2_000, idle_ns=1_000):
    """A TPU's two lines made from a capture's spans: each dispatch's module
    begins `lag_ns` after the dispatch does and runs until `idle_ns` before
    the next one's begins (the last: until `lag_ns` before the fetch of its
    report ends); one op fills each module."""
    loop = sorted((s for s in spans if s["name"].startswith("train/")),
                  key=lambda s: s["start_ns"])
    fetch_end = {s["step"]: s["end_ns"] for s in loop
                 if s["name"] == "train/fetch" and s["args"]["n"] == 1}
    starts = [(s["start_ns"] + lag_ns, s["step"]) for s in loop
              if s["name"] == "train/dispatch"]
    ends = [nxt - idle_ns for nxt, _ in starts[1:]]
    ends.append(fetch_end[starts[-1][1]] - lag_ns)
    event = lambda name, s, e: types.SimpleNamespace(
        name=name, start_ns=s, duration_ns=e - s)
    line = lambda name, events: types.SimpleNamespace(name=name,
                                                      events=events)
    return [types.SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Modules", [event("jit_train_step", s, e)
                             for (s, _), e in zip(starts, ends)]),
        line("XLA Ops", [event("%fusion.1 = f32[] fusion()", s, e)
                         for (s, _), e in zip(starts, ends)])])]


def test_capture_epoch_and_spans_json_with_both_tracers_off(tmp_path, mesh8):
    """A capture taken with both host tracers off still states its epoch;
    `write_capture_spans` leaves the ring's spans in its directory with
    their own stamps, `tools/trace_digest` counts them from
    `profile_start_time`, lists them and lays them over a device's gaps; a
    planted offset raises `ClockMismatch`."""
    from benchmark import hostspans
    from tools import trace_digest

    trainer = _trainer(mesh8)
    data = _batches(4)
    trainer.fit(lambda: data[:2], epochs=1, handle_preemption=False)
    holder = {}

    def body():
        holder["t0"] = time.time_ns()
        trainer.fit(lambda: [data[i % 4] for i in range(6)], epochs=1,
                    handle_preemption=False)

    cap, before, after = _capture_with_tracers_off(tmp_path, body)
    trainer.close()
    xplane, = trace_digest.find_xplanes(cap)
    data_planes = list(jax.profiler.ProfileData.from_file(xplane).planes)
    start, stop = trace_digest.capture_epoch_ns(data_planes)
    assert before <= start <= holder["t0"] <= stop <= after
    assert trace_digest.capture_spans(xplane, data_planes) is None
    path = trace_mod.write_capture_spans(cap, before, after)
    assert path == os.path.join(cap, "spans.json")
    doc = json.load(open(path))
    assert (doc["since_ns"], doc["until_ns"]) == (before, after)
    # as the ring holds them: the file's writer opens no capture
    assert all(before <= s["start_ns"] <= s["end_ns"] <= after
               for s in doc["spans"])
    spans = trace_digest.capture_spans(xplane, data_planes)
    names = [s["name"] for s in spans]
    assert names.count("train/dispatch") == 6
    assert names.count("train/data_wait") == 7
    # counted from the capture's start, like the capture's own events
    assert all(0 <= s["start_ns"] <= s["end_ns"] <= stop - start
               for s in spans)
    first = next(s for s in spans if s["name"] == "train/dispatch")
    ring = [s for s in trace_mod.spans(since_ns=start)
            if s.name == "train/dispatch"][0]
    assert first["start_ns"] + start == ring.start_ns
    assert first["thread"] == threading.get_ident()
    place = next(s for s in spans if s["name"] == "train/place")
    assert place["args"]["bytes"] > 0
    # the digest needs no host plane: the capture has none
    d = trace_digest.digest(cap)
    assert "error" not in d and "gaps" not in d  # a CPU's capture: no device
    rows = {r["span"]: r for r in d["spans"]}
    assert rows["train/dispatch"]["count"] == 6
    assert "train/fetch" in trace_digest.render_digest(d)
    # over a device's planes: the seven names add up to the idle time
    red = trace_digest.host_gaps(data_planes + _device_planes(spans), spans)
    assert red["steps_checked"] == 6 and red["periods"] == 4
    table = red["gap_s_per_step"]
    assert set(table) == set(hostspans.NAMES)
    # the device idles 1 us a period, 2 us into each `train/dispatch`
    assert table["train/dispatch"] == pytest.approx(1e-6)
    assert sum(table.values()) == pytest.approx(1e-6)
    # the clocks disagree by a second: refused, not attributed
    late = [dict(s, start_ns=s["start_ns"] + 10 ** 9,
                 end_ns=s["end_ns"] + 10 ** 9) for s in spans]
    with pytest.raises(hostspans.ClockMismatch, match="after its module"):
        trace_digest.host_gaps(data_planes + _device_planes(spans), late)
    early = [dict(s, start_ns=s["start_ns"] - 10 ** 9,
                  end_ns=s["end_ns"] - 10 ** 9) for s in spans]
    with pytest.raises(hostspans.ClockMismatch, match="before its module"):
        trace_digest.host_gaps(data_planes + _device_planes(spans), early)
    # no capture there: an error, not a table
    assert "error" in trace_digest.digest(str(tmp_path / "nothing"))


def test_capture_epoch_raises_without_the_task_environment_plane(
        tmp_path, monkeypatch):
    f = jax.jit(lambda x: x + 1)
    f(1.0).block_until_ready()
    cap, _, _ = _capture_with_tracers_off(
        tmp_path, lambda: f(2.0).block_until_ready())
    from tools import trace_digest

    xplane, = trace_digest.find_xplanes(cap)
    planes = list(jax.profiler.ProfileData.from_file(xplane).planes)
    assert trace_digest.capture_epoch_ns(planes)[0] > 0
    monkeypatch.setattr(trace_digest, "_EPOCH_PLANE", "No Such Plane")
    with pytest.raises(ValueError, match="No Such Plane"):
        trace_digest.capture_epoch_ns(planes)
    # spans beside a capture that states no epoch: refused, not guessed
    trace_mod.write_capture_spans(cap, 0, time.time_ns())
    assert "No Such Plane" in trace_digest.digest(cap)["error"]


def test_profile_dir_capture_gets_its_spans_json(tmp_path, mesh8):
    """The program's one `stop_trace` (autoprof's; `--profile-dir` ends
    there too) writes the spans into the capture's directory."""
    trainer = _trainer(mesh8, profile_dir=str(tmp_path / "trace"),
                       profile_steps=(2, 5))
    data = _batches(4)
    trainer.fit(lambda: [data[i % 4] for i in range(8)], epochs=1,
                handle_preemption=False)
    trainer.close()
    found = glob.glob(os.path.join(str(tmp_path / "trace"), "cap-*",
                                   "spans.json"))
    assert len(found) == 1
    doc = json.load(open(found[0]))
    assert glob.glob(os.path.join(os.path.dirname(found[0]), "plugins",
                                  "profile", "*", "*.xplane.pb"))
    assert doc["since_ns"] < doc["until_ns"]
    names = [s["name"] for s in doc["spans"]]
    # the window's dispatches, whole: it starts and stops in `_dispatch_step`
    assert names.count("train/dispatch") == 3
    assert {"train/place", "train/fetch", "train/log",
            "train/data_wait"} <= set(names)
    from tools import trace_digest

    d = trace_digest.digest(os.path.dirname(found[0]))
    assert "error" not in d
    assert any(r["span"] == "train/dispatch" for r in d["spans"])


# -- the flight bundle's span tail -----------------------------------------------

def test_flight_bundle_holds_the_rings_tail_without_a_tracer(tmp_path):
    from deep_vision_tpu.obs import FlightRecorder

    assert trace_mod.get_tracer() is None
    for i in range(5):
        with trace_mod.span("train/probe", step=i, k=1):
            pass
    fr = FlightRecorder(str(tmp_path / "flight"), run_id="r", span_tail=3)
    p = fr.dump("manual")
    fr.close()
    events = json.load(open(os.path.join(p, "spans.json")))["traceEvents"]
    assert [e["name"] for e in events] == ["train/probe"] * 3
    assert [e["args"] for e in events] == [{"step": i, "k": 1}
                                           for i in (2, 3, 4)]
    last = events[-1]
    assert last["ph"] == "X" and last["pid"] == os.getpid()
    assert last["tid"] == threading.get_ident()
    assert abs(last["ts"] * 1e3 - time.time_ns()) < 60e9


def test_killed_cli_run_leaves_its_last_spans_in_the_flight_bundle(tmp_path):
    """`train.py --fake-data --flight-dir <dir>` with no `--trace`, killed
    (SIGKILL injected inside the first checkpoint save): the bundle's
    `spans.json` holds the loop's last spans; before the ring it was
    empty."""
    from deep_vision_tpu.obs.flight import find_bundles, validate_bundle

    flight = str(tmp_path / "flight")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("DVT_FAULT_SPEC", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "train.py"), "-m", "lenet5",
         "--fake-data", "--fake-batches", "4", "--epochs", "2",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--flight-dir", flight,
         "--fault-spec", "ckpt.sidecar:crash_after_write@1"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=560)
    assert done.returncode == -signal.SIGKILL, done.stdout + done.stderr
    bundle, = find_bundles(flight)
    assert validate_bundle(bundle) == []
    events = json.load(open(os.path.join(bundle, "spans.json")))["traceEvents"]
    names = [e["name"] for e in events]
    assert names.count("train/dispatch") == 4
    assert names.count("train/fetch") >= 4 and "train/log" in names
    assert "setup/build_trainer" in names  # set-up, from the same ring
    steps = [e["args"]["step"] for e in events
             if e["name"] == "train/dispatch"]
    assert steps == [1, 2, 3, 4]
