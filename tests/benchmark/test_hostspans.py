"""The host-span reduction (benchmark/hostspans.py) on hand-made timelines,
and the readers of the metrics it feeds. CPU only: no device number."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import hostspans, trace  # noqa: E402

# An execution of the step module that the session started inside (cut
# short, its dispatch before the trace), then two whole ones, 2000 ns apart,
# 1000 ns long; the first of those has a 100 ns bubble inside. The slice is
# [1000, 3000): idle [1400, 1500) and [2000, 3000).
MODULES = [("jit_step", 100, 300), ("jit_step", 1000, 1000),
           ("jit_other", 2300, 10), ("jit_step", 3000, 1000)]
OPS = [("%fusion.1 = f32[8] fusion()", 100, 300),
       ("%fusion.1 = f32[8] fusion()", 1000, 400),
       ("%copy-done.2 = f32[8] copy-done()", 1500, 500),
       ("%fusion.1 = f32[8] fusion()", 3000, 1000)]
# (name, start, end, its `step` argument) on the loop's thread: steps 7 and
# 8 of a loop that reads each step's report before it dispatches the next
SPANS = [
    ("train/epoch", 400, 4400, None),
    ("train/data_wait", 500, 600, 7),
    ("train/step", 600, 2400, 7),
    ("train/place", 610, 700, 7),
    ("train/dispatch", 700, 800, 7),
    ("train/fetch", 820, 2100, 7),     # ends 100 after its module
    ("train/log", 2150, 2400, 7),
    ("train/data_wait", 2450, 2550, 8),
    ("train/step", 2600, 4300, 8),
    ("train/place", 2600, 2750, 8),
    ("train/dispatch", 2760, 2800, 8),
    ("train/fetch", 2850, 4100, 8),    # 150 before its module begins
    ("train/log", 4150, 4300, 8),
]
EXPECTED = {  # ns of the slice's 1100 idle, by hand
    "train/fetch": 100 + 100,       # the bubble, and the tail after the end
    "train/log": 250,
    "train/data_wait": 100,
    "train/place": 150,
    "train/dispatch": 40,
    "launch": 150,
    "other": 50 + 50 + 50 + 10 + 50,
}


def host_lines(shift=0, spans=SPANS):
    return {"python": [(n, s + shift, e - s, step) for n, s, e, step in spans]
            + [("PjitFunction(step)", 705 + shift, 80)],
            "prefetch": [("data/fetch", 100, 50)]}


def planes(shift=0, devices=1):
    out = {hostspans.HOST_PLANE: host_lines(shift)}
    for d in range(devices):
        out[f"{trace.DEVICE_PLANE_PREFIX}{d}"] = {
            trace.MODULES_LINE: MODULES, trace.OPS_LINE: OPS}
    return out


def test_every_name_gets_its_share_and_the_shares_are_the_gap():
    red = hostspans.reduce_planes(planes())
    assert red["thread"] == "python"
    assert red["periods"] == 1 and red["steps_checked"] == 2
    table = red["gap_s_per_step"]
    assert set(table) == set(hostspans.NAMES) == set(EXPECTED)
    for name, ns in EXPECTED.items():
        assert table[name] == pytest.approx(ns * 1e-9), name
    # the same slice, the same idle time as the device reduction's
    device = trace.reduce_device(MODULES, OPS)
    idle = device["window_s"] - device["busy_s"]
    assert sum(table.values()) == pytest.approx(idle)
    assert sum(device["gap_s_per_step"].values()) == pytest.approx(idle)


def test_table_is_the_mean_over_the_devices():
    # the second device ends its first execution's ops 100 ns sooner
    early = [OPS[0], (OPS[1][0], OPS[1][1], OPS[1][2] - 100), OPS[2]]
    two = planes(devices=2)
    two[f"{trace.DEVICE_PLANE_PREFIX}1"] = {
        trace.MODULES_LINE: MODULES, trace.OPS_LINE: early}
    red = hostspans.reduce_planes(two)
    a, b = (d["gap_s_per_step"] for d in red["devices"])
    assert b["train/fetch"] == pytest.approx(a["train/fetch"] + 100e-9)
    for name in hostspans.NAMES:
        assert red["gap_s_per_step"][name] == pytest.approx(
            (a[name] + b[name]) / 2)


@pytest.mark.parametrize("spans, gap, expected", [
    # the innermost open span names the instant
    ([("train/log", 0, 100), ("train/fetch", 20, 40)], (0, 100),
     {"train/log": 80, "train/fetch": 20}),
    # `train/step` and `train/epoch` name nothing
    ([("train/epoch", 0, 100), ("train/step", 10, 90)], (0, 100),
     {"other": 100}),
    # an edge gap whose span the session missed is `other`
    ([("train/place", 60, 100)], (0, 100),
     {"other": 60, "train/place": 40}),
    # a fetch that no module begins inside is all fetch
    ([("train/fetch", 0, 100)], (0, 100), {"train/fetch": 100}),
])
def test_attribution_rule(spans, gap, expected):
    table = hostspans.attribute([gap], spans, runs=[(500, 600)])
    assert {n: v for n, v in table.items() if v} == expected


def test_launch_is_idle_before_a_module_that_begins_inside_the_fetch():
    table = hostspans.attribute(
        [(0, 50)], [("train/fetch", 10, 200)], runs=[(50, 150)])
    assert {n: v for n, v in table.items() if v} == {"other": 10,
                                                     "launch": 40}


@pytest.mark.parametrize("shift, says", [
    (400, "after its module begins"),   # dispatch at 1100, module at 1000
    (-200, "before its module"),        # fetch ends 1900, module 2000
    (2000, "after its module begins"),  # a whole step off
])
def test_a_host_plane_on_another_clock_trips_the_check(shift, says):
    with pytest.raises(hostspans.ClockMismatch, match=says):
        hostspans.reduce_planes(planes(shift=shift))


def test_other_steps_than_the_devices_trip_the_check():
    fewer = [s for s in SPANS if s[1] < 2600]  # one dispatch, two executions
    with pytest.raises(hostspans.ClockMismatch, match="not the same steps"):
        hostspans.reduce_planes({**planes(), hostspans.HOST_PLANE:
                                 host_lines(spans=fewer)})


def capture(steps, in_flight, first=4):
    """Planes of a hand-made capture of `steps` dispatches, 1000 ns a step.
    `in_flight`: the loop of PR 27, which dispatches step k and then reads
    step k-1 (the device runs back to back, the session starts inside step
    `first - 1` and stops before the last report is read); else the loop
    before it, which reads step k before it feeds step k+1 (the device
    idles 400 ns a step, and every execution has its dispatch)."""
    modules, ops, spans = [], [], []
    if in_flight:
        t = 1000 * (first - 1)
        modules.append(("jit_step", t + 700, 290))  # cut to the session's
        ops.append(("%fusion.1 = f32[8] fusion()", t + 700, 290))
    for k in range(first, first + steps):
        t = 1000 * k
        length = 990 if in_flight else 600
        modules.append(("jit_step", t, length))
        ops.append(("%fusion.1 = f32[8] fusion()", t, length))
        read = k - 1 if in_flight else k
        spans += [("train/data_wait", t - 350, 20, k),
                  ("train/place", t - 320, 50, k),
                  ("train/dispatch", t - 260, 60, k)]
        if in_flight:  # ends 15 after module k-1, inside module k
            spans += [("train/fetch", t - 190, 195, read),
                      ("train/log", t + 10, 30, read)]
        else:          # ends 40 after module k
            spans += [("train/fetch", t - 190, 190 + 640, read),
                      ("train/fetch", t + 100, 400, read),  # the fence
                      ("train/log", t + 645, 5, read)]
    return {hostspans.HOST_PLANE: {"python": spans},
            f"{trace.DEVICE_PLANE_PREFIX}0": {trace.MODULES_LINE: modules,
                                              trace.OPS_LINE: ops}}


@pytest.mark.parametrize("in_flight", [True, False])
def test_the_check_pairs_a_dispatch_with_the_fetch_of_its_own_step(in_flight):
    red = hostspans.reduce_planes(capture(5, in_flight))
    # whole periods only: the trace's first execution bounds none
    assert red["steps_checked"] == 5
    assert red["periods"] == (4 if in_flight else 3)
    idle = sum(red["gap_s_per_step"].values())
    assert idle == pytest.approx(10e-9 if in_flight else 400e-9)
    fewer = hostspans.reduce_planes(capture(4, in_flight))
    assert fewer["periods"] == red["periods"] - 1
    assert fewer["gap_s_per_step"] == pytest.approx(red["gap_s_per_step"])


@pytest.mark.parametrize("in_flight, shift, says", [
    (True, -20, "before its module ends"),   # fetch of k ends inside module k
    (True, 300, "after its module begins"),
    (False, -50, "before its module ends"),
])
def test_a_shifted_host_plane_trips_the_check_in_either_loop(in_flight, shift,
                                                             says):
    planes_ = capture(5, in_flight)
    planes_[hostspans.HOST_PLANE] = {"python": [
        (n, s + shift, d, step)
        for n, s, d, step in planes_[hostspans.HOST_PLANE]["python"]]}
    with pytest.raises(hostspans.ClockMismatch, match=says):
        hostspans.reduce_planes(planes_)


def test_a_report_read_for_another_step_trips_the_check():
    planes_ = capture(5, in_flight=True)
    planes_[hostspans.HOST_PLANE] = {"python": [
        (n, s, d, step + 1 if n == "train/fetch" else step)
        for n, s, d, step in planes_[hostspans.HOST_PLANE]["python"]]}
    # the fetch that says step k now ends when module k-1 does
    with pytest.raises(hostspans.ClockMismatch, match="before its module"):
        hostspans.reduce_planes(planes_)


def test_a_trace_without_the_loops_spans_is_refused():
    bare = planes()
    bare[hostspans.HOST_PLANE] = {"python": [("PjitFunction(step)", 705, 80)]}
    with pytest.raises(RuntimeError, match="host plane is off"):
        hostspans.reduce_planes(bare)
    del bare[hostspans.HOST_PLANE]
    with pytest.raises(RuntimeError, match="no /host:CPU plane"):
        hostspans.reduce_planes(bare)


def test_reads_a_real_capture_up_to_its_missing_device_planes(tmp_path):
    """On the CPU the capture has the spans and no `/device:TPU:` plane:
    the file is read and the reduction says what is missing."""
    from deep_vision_tpu.obs.trace import span, start_profiler

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    start_profiler(str(tmp_path))
    try:
        with span("train/dispatch", step=1):
            y = f(x)
        with span("train/fetch", step=1, n=1):
            float(y)
    finally:
        jax.profiler.stop_trace()
    with pytest.raises(RuntimeError, match="no device plane"):
        hostspans.reduce_host_spans(str(tmp_path))


def load_metric(name):
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


GAP_METRICS = {"gap_fetch_ms": "train/fetch", "gap_log_ms": "train/log",
               "gap_data_wait_ms": "train/data_wait",
               "gap_place_ms": "train/place",
               "gap_dispatch_ms": "train/dispatch",
               "gap_launch_ms": "launch", "gap_other_ms": "other"}


@pytest.mark.parametrize("metric", sorted(GAP_METRICS))
def test_gap_metric_reads_its_name_or_nothing(metric):
    reader = load_metric(metric)
    assert reader.read({"trace": None}) is None  # untraced run
    assert reader.read({"trace": {}, "host_spans": None}) is None
    red = hostspans.reduce_planes(planes())
    got = reader.read({"host_spans": red})
    assert got == pytest.approx(EXPECTED[GAP_METRICS[metric]] * 1e-6)


def test_gap_metrics_add_up_to_host_gap_ms():
    device = trace.reduce_device(MODULES, OPS)
    record = {"trace": device, "host_spans": hostspans.reduce_planes(planes())}
    total = sum(load_metric(m).read(record) for m in GAP_METRICS)
    assert total == pytest.approx(load_metric("host_gap_ms").read(record))


def test_host_fetches_per_step_reads_the_process_registry(monkeypatch):
    from deep_vision_tpu.obs import registry as registry_mod

    reader = load_metric("host_fetches_per_step")
    fresh = registry_mod.Registry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    assert reader.read({}) is None  # a program without the counter
    assert not fresh.metrics()      # and the reader created none
    fresh.counter("train_steps_total").inc(4)
    assert reader.read({}) is None
    fresh.counter("train_host_fetches_total").inc(24)
    assert reader.read({}) == 6.0
