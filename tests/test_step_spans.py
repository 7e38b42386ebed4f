"""The host loop's spans in the profiler's trace (ISSUE 26): every span of
`obs.trace.span` is a `jax.profiler.TraceAnnotation` too, so a profiler
session holds them in its host plane, on the device planes' clock."""
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from deep_vision_tpu.obs import trace as trace_mod
from deep_vision_tpu.obs.registry import Registry

CHILDREN = ("train/place", "train/dispatch", "train/fetch", "train/log")
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


def _host_events(capture_dir):
    """{thread: [(name, start_ns, end_ns, stats)]} of the `train/*` events
    and the step annotation in a capture's host plane."""
    path, = glob.glob(os.path.join(capture_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name == "train" or e.name.startswith("train/")]
            if events:
                out[line.name] = sorted(events, key=lambda e: (e[1], -e[2]))
    return out


def _profiled_fit(trainer, feed, tmp_path):
    """Warm up outside the session, then one `fit` of `feed` inside it."""
    trainer.fit(lambda: feed[:2], epochs=1, handle_preemption=False)
    capture = str(tmp_path / "capture")
    trace_mod.start_profiler(capture)
    try:
        trainer.fit(lambda: feed, epochs=1, handle_preemption=False)
    finally:
        jax.profiler.stop_trace()
    threads = _host_events(capture)
    loop = [t for t, evs in threads.items()
            if any(e[0] == "train/dispatch" for e in evs)]
    assert len(loop) == 1, f"the loop's spans on threads {loop}"
    return threads[loop[0]]


def _by_step(events):
    steps = {}
    for e in events:
        if e[0].startswith("train/") and e[0] != "train/epoch":
            steps.setdefault(e[3]["step"], []).append(e)
    return steps


@pytest.mark.parametrize("multistep", [1, 2])
def test_profiler_session_holds_the_loops_spans(tmp_path, mesh8, multistep):
    k = multistep
    trainer = _trainer(mesh8, multistep=k)
    events = _profiled_fit(trainer, _batches(3 * k), tmp_path)
    trainer.close()
    steps = _by_step(events)
    assert len(steps) == 4  # three dispatches, and the wait that ends the feed
    first = min(steps)
    for i in range(first, first + 3):
        names = [e[0] for e in steps[i]]
        assert names == ["train/data_wait", "train/step", *CHILDREN], names
        wait, step, place, dispatch, fetch, log = steps[i]
        assert wait[2] <= step[1]  # the wait ends before its step begins
        for child in (place, dispatch):
            assert step[1] <= child[1] and child[2] <= step[2]
        assert place[2] <= dispatch[1] and fetch[2] <= log[1]  # in sequence
        # the report is read one dispatch late, inside the next `train/step`
        # — or, the last one, once the feed has ended
        if i < first + 2:
            after, inside = steps[i + 1][3], steps[i + 1][1]
            assert after[2] <= fetch[1] and log[2] <= inside[2]
        else:
            assert steps[i + 1][0][2] <= fetch[1]
        stats = {e[0]: e[3] for e in steps[i]}
        assert stats["train/place"]["bytes"] == k * (8 * 32 * 32 * 4 + 8 * 4
                                                     + 8 * 4)
        assert stats["train/fetch"]["n"] == 1  # one `device_get` a dispatch
        assert stats["train/log"]["opt_step"] == (i - first + 1) * k + 2
    assert [e[0] for e in steps[first + 3]] == ["train/data_wait"]
    # the whole step is XProf's step annotation too
    marks = [e for e in events if e[0] == "train"]
    assert [m[3]["step_num"] for m in marks] == list(range(first, first + 3))
    for mark, i in zip(marks, range(first, first + 3)):
        step = steps[i][1]
        assert mark[1] <= step[1] and step[2] <= mark[2]


def test_placed_batch_step_has_no_place_span(tmp_path, mesh8):
    trainer = _trainer(mesh8, device_prefetch=2)
    events = _profiled_fit(trainer, _batches(3), tmp_path)
    trainer.close()
    steps = _by_step(events)
    first = min(steps)
    for i in range(first, first + 3):
        names = [e[0] for e in steps[i]]
        assert names == ["train/data_wait", "train/step", "train/dispatch",
                         "train/fetch", "train/log"], names


@pytest.mark.parametrize("multistep", [1, 2])
def test_host_fetch_counter_counts_each_blocking_fetch(mesh8, multistep):
    reg = Registry()
    trainer = _trainer(mesh8, registry=reg, multistep=multistep)
    fetches = reg.counter("train_host_fetches_total")
    steps = reg.counter("train_steps_total")
    assert fetches.value == 0
    trainer.fit(lambda: _batches(3 * multistep), epochs=1,
                handle_preemption=False)
    trainer.close()
    assert steps.value == 3
    # the whole report of a dispatch — step counter, learning rate, every
    # metric, a superstep's microsteps — in one `device_get`
    assert fetches.value == 3


def test_chrome_tracer_gets_the_old_spans_and_the_new(tmp_path, mesh8):
    path = str(tmp_path / "run.trace.json")
    tracer = trace_mod.Tracer(path)
    trace_mod.set_tracer(tracer)
    try:
        trainer = _trainer(mesh8)
        data = _batches(2)
        trainer.fit(lambda: data, lambda: data, epochs=1,
                    handle_preemption=False)
        trainer.close()
    finally:
        trace_mod.set_tracer(None)
        tracer.close()
    spans = [e for e in json.load(open(path))["traceEvents"]
             if e["ph"] == "X"]
    names = [e["name"] for e in spans]
    assert {"train/epoch", "train/step", "eval"} <= set(names)
    for name in ("train/step", *CHILDREN):
        assert names.count(name) == 2, name
    assert names.count("train/data_wait") == 3  # the third ends the feed
    for step in (e for e in spans if e["name"] == "train/step"):
        inside = [e["name"] for e in spans if e["name"] in CHILDREN
                  and step["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= step["ts"] + step["dur"] + 1]
        # its own placement and dispatch, then the step before it is read
        late = CHILDREN[2:] if step["args"]["step"] > 1 else ()
        assert inside == [*CHILDREN[:2], *late]
    for log in (e for e in spans if e["name"] == "train/log"):
        assert log["args"]["opt_step"] == log["args"]["step"]


def test_span_is_an_annotation_and_a_tracer_span_at_once(tmp_path):
    """With a tracer installed and a session live, both sinks get it, late
    args included."""
    capture = str(tmp_path / "capture")
    tracer = trace_mod.Tracer(str(tmp_path / "t.json"))
    trace_mod.set_tracer(tracer)
    trace_mod.start_profiler(capture)
    try:
        with trace_mod.span("train/fetch", step=7, n=2) as sp:
            sp.set(late="x")
    finally:
        jax.profiler.stop_trace()
        trace_mod.set_tracer(None)
        tracer.close()
    (event,), = _host_events(capture).values()
    assert event[0] == "train/fetch"
    assert event[3] == {"step": 7, "n": 2, "late": "x"}
    chrome, = [e for e in json.load(open(tracer.path))["traceEvents"]
               if e["ph"] == "X"]
    assert chrome["args"] == {"step": 7, "n": 2, "late": "x"}


def test_trace_module_imports_and_runs_without_jax():
    code = (
        "import sys\n"
        "from deep_vision_tpu.obs import trace, stepclock\n"
        "assert 'jax' not in sys.modules, 'obs.trace imported jax'\n"
        "assert trace.span('data/fetch', loader='x') is trace._NULL_SPAN\n"
        "with trace.span('data/fetch') as sp:\n"
        "    sp.set(step=1)\n"
        "sys.modules['jax'] = type(sys)('jax')  # loaded, profiler not yet\n"
        "assert trace.span('data/fetch') is trace._NULL_SPAN\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_span_without_session_or_tracer_is_the_bare_annotation():
    sp = trace_mod.span("train/fetch", step=1)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as entered:
        entered.set(opt_step=3)  # dropped: nothing is recording


@pytest.mark.parametrize("fn, scope", [("_train_step", "train_step"),
                                       ("_eval_step", "eval_step")])
def test_step_programs_carry_their_scope(mesh8, fn, scope):
    from deep_vision_tpu.parallel.mesh import shard_batch

    trainer = _trainer(mesh8)
    batch = shard_batch(trainer.mesh,
                        trainer._pad_and_mask(_batches(1)[0]))
    with trainer._mesh_context():
        text = getattr(trainer, fn).lower(trainer.state, batch).as_text(
            debug_info=True)
    trainer.close()
    assert f"{scope}/" in text
