"""The host-loop metrics that read the program's span ring (ISSUE 38):
`host_busy_ms`, `data_wait_ms`, `place_ms`, `dispatch_ms`,
`build_trainer_s`, and the helper they share."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import loopspans, run  # noqa: E402
from deep_vision_tpu.obs import trace as trace_mod  # noqa: E402
from deep_vision_tpu.obs.trace import Span  # noqa: E402

NEW = ("host_busy_ms", "data_wait_ms", "place_ms", "dispatch_ms",
       "build_trainer_s")
ENTRIES = os.path.join(ROOT, "benchmark", "host_loop.json")
US = 1_000
LOOP, OTHER = 11, 22  # thread idents


def hand_made_ring(n_dispatches=6, first=1):
    """A loop as `Trainer` drives it, one step in flight, in microseconds:
    dispatch i waits (10 + i) for its batch, places 100, dispatches 200,
    then reads dispatch i-1 (a fetch of 5000 with its fence inside, a log
    of 50); 30 of `train/step` are covered by no child. Before it the
    set-up's span; a data thread's spans beside it; after it the wait in
    which the feed ends and the flush of the last dispatch."""
    out = [Span("setup/build_trainer", 0, 21_000_000 * US, None, LOOP, None)]
    t = 30_000_000 * US
    for i in range(first, first + n_dispatches):
        out.append(Span("data/fetch", t, t + 7 * US, None, OTHER, None))
        out.append(Span("train/data_wait", t, t + (10 + i) * US, i, LOOP,
                        None))
        t += (10 + i) * US
        step_start = t
        t += 10 * US
        out.append(Span("train/place", t, t + 100 * US, i, LOOP,
                        {"bytes": 77}))
        t += 100 * US
        out.append(Span("train/dispatch", t, t + 200 * US, i, LOOP, None))
        t += 200 * US + 10 * US
        if i > first:  # the report of the dispatch before
            out.append(Span("train/fetch", t + US, t + 4000 * US, i - 1,
                            LOOP, {"n": 0}))
            out.append(Span("train/fetch", t, t + 5000 * US, i - 1, LOOP,
                            {"n": 1}))
            t += 5000 * US
            out.append(Span("train/log", t, t + 50 * US, i - 1, LOOP,
                            {"opt_step": i - 1}))
            t += 50 * US
        t += 10 * US
        out.append(Span("train/step", step_start, t, i, LOOP, {"epoch": 0}))
    last = first + n_dispatches - 1
    out.append(Span("train/data_wait", t, t + 3 * US, last + 1, LOOP, None))
    out.append(Span("train/fetch", t + 3 * US, t + 9000 * US, last, LOOP,
                    {"n": 1}))
    out.append(Span("train/log", t + 9000 * US, t + 9050 * US, last, LOOP,
                    None))
    return out


def test_the_windows_dispatches_are_the_last_steps_of_the_run():
    ring = hand_made_ring(n_dispatches=6, first=1)
    window = loopspans.window_dispatches({"steps": 3}, ring)
    assert sorted(window) == [4, 5, 6]  # the warm-up's three fall outside
    # the feed's closing wait carries a step no dispatch has
    assert 7 not in window
    names = sorted(s.name for s in window[5])
    assert names == ["train/data_wait", "train/dispatch", "train/fetch",
                     "train/fetch", "train/log", "train/place", "train/step"]
    assert all(s.thread == LOOP for held in window.values() for s in held)
    # fewer dispatches in the ring than steps asked for: what is there
    assert sorted(loopspans.window_dispatches({"steps": 50}, ring)) == [
        1, 2, 3, 4, 5, 6]
    assert loopspans.window_dispatches({"steps": 3}, None) is None
    assert loopspans.window_dispatches({"steps": 3}, []) == {}


def test_each_reader_on_a_hand_made_ring():
    ring = hand_made_ring(n_dispatches=6, first=1)
    run_ = {"steps": 3}
    assert loopspans.median_ms(run_, loopspans.DATA_WAIT, ring) == \
        pytest.approx(0.015)  # 14, 15, 16 us: the median
    assert loopspans.median_ms(run_, loopspans.PLACE, ring) == \
        pytest.approx(0.100)
    assert loopspans.median_ms(run_, loopspans.DISPATCH, ring) == \
        pytest.approx(0.200)
    # wait + the whole step less the outer fetch (the fence is inside it)
    assert loopspans.host_busy_ms(run_, ring) == pytest.approx(
        0.015 + (0.010 + 0.100 + 0.200 + 0.010 + 0.050 + 0.010))
    assert loopspans.build_trainer_s(ring) == pytest.approx(21.0)
    busy = loopspans.host_busy_ms(run_, ring)
    assert busy >= sum(loopspans.median_ms(run_, n, ring) for n in (
        loopspans.DATA_WAIT, loopspans.PLACE, loopspans.DISPATCH))
    # the first dispatch of a feed reads nothing: all of its step is busy
    # (341, then 392 ... 396 us: the median of the six)
    assert loopspans.host_busy_ms({"steps": 6}, ring) == pytest.approx(
        0.3935)
    # no ring, no reading
    for reader in (loopspans.host_busy_ms,):
        assert reader(run_, []) is None
    assert loopspans.median_ms(run_, loopspans.PLACE, []) is None
    assert loopspans.build_trainer_s([]) is None


def manifest_with_the_five():
    """`BENCHMARK.json` as it reads once the five entries that wait in
    `benchmark/host_loop.json` are appended to its `per_layer`."""
    m = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    return {**m, "per_layer": m["per_layer"]
            + run.load_manifest(ENTRIES)["per_layer"]}


@pytest.mark.parametrize("name", NEW)
def test_metric_files_read_the_ring_and_none_without_one(name, monkeypatch):
    m = manifest_with_the_five()
    entry, = [x for x in m["per_layer"] if x["name"] == name]
    assert entry["source"] == "program_span" and "workloads" not in entry
    assert entry["better"] == "lower"
    assert (entry["layer"], entry["moves"], entry["unit"]) == (
        ("entry and set-up", "setup_s", "s") if name == "build_trainer_s"
        else ("host loop", "img_per_s_chip", "ms"))
    ring = hand_made_ring()
    monkeypatch.setattr(trace_mod, "spans", lambda *a, **kw: ring)
    record = {"steps": 3, "cell": {"name": "resnet50_train_b128"}}
    only = {**m, "per_layer": [entry]}
    assert run.read_metrics(only, "per_layer", record) == {name: {
        "value": pytest.approx({
            "host_busy_ms": 0.395, "data_wait_ms": 0.015, "place_ms": 0.1,
            "dispatch_ms": 0.2, "build_trainer_s": 21.0}[name]),
        "unit": entry["unit"]}}
    # a program without the ring (the parent commit): the metric is absent
    monkeypatch.delattr(trace_mod, "spans")
    assert run.read_metrics(only, "per_layer", record) == {}


def test_the_five_entries_wait_as_files_until_a_benchmark_pr_lists_them():
    """`test_benchmark.py` holds `waiting.json`'s `per_layer` equal to
    `BENCHMARK.json`'s, and neither is a program PR's to edit: the entries
    wait in `benchmark/host_loop.json`, in the order they enter, with a
    reader each, and clash with no name that is listed."""
    waiting = run.load_manifest(ENTRIES)
    assert list(waiting) == ["per_layer"]
    assert tuple(x["name"] for x in waiting["per_layer"]) == NEW
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {x["name"] for x in real["per_layer"] + real["end_to_end"]}
    layers = {x["layer"] for x in real["per_layer"]}
    e2e = {x["name"] for x in real["end_to_end"]}
    for entry in waiting["per_layer"]:
        assert entry["name"] not in listed
        assert entry["layer"] in layers and entry["moves"] in e2e
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves"}
        assert callable(run.load_py(run.find_file(
            real, "metrics", entry["name"] + ".py")).read)


def test_a_rehearsed_run_leaves_its_window_in_the_ring(monkeypatch):
    """Through the harness on the CPU, tiny: the ring holds the window's
    dispatches and every reader finds its spans there. (A benchmark process
    builds one trainer; this one's ring may hold other tests' dispatches
    under the same `step` values, so the run gets a ring of its own.)"""
    monkeypatch.setattr(trace_mod, "_ring", trace_mod.SpanRing())
    from deep_vision_tpu.models import register_model
    from deep_vision_tpu.models.resnet import ResNet

    register_model("bench_tiny_resnet")(
        lambda num_classes=10, dtype=None, stem="s2d", **_: ResNet(
            stage_sizes=(1, 1), width=8, num_classes=num_classes, stem=stem,
            dtype=dtype))
    manifest = run.load_manifest(os.path.join(
        ROOT, "tests", "benchmark", "rehearsal.json"))
    result = run.run_cell(manifest, "tiny_resnet_train", 2 ** 31 + 38, 0.3,
                          0, require_chip=False)
    assert result["correct"], (result["compared"], result["faults"])
    record = {"steps": result["attempted"]}
    window = loopspans.window_dispatches(record, loopspans.ring_spans())
    assert len(window) == result["attempted"] >= 1
    assert min(window) == max(window) - len(window) + 1  # consecutive
    place = loopspans.median_ms(record, loopspans.PLACE)
    dispatch = loopspans.median_ms(record, loopspans.DISPATCH)
    wait = loopspans.median_ms(record, loopspans.DATA_WAIT)
    busy = loopspans.host_busy_ms(record)
    assert place > 0 and dispatch > 0 and wait >= 0
    assert busy >= place + dispatch + wait
    assert busy < result["window"]["interval_max_ms"]
    assert 0 < loopspans.build_trainer_s() < result["metrics"]["setup_s"][
        "value"]
