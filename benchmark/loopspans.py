"""What the host loop's thread did in each step period of the window, from
the program's own span ring (`deep_vision_tpu/obs/trace.py`: every span of
the run, stamped by the program with `time.time_ns()`; no profiler session
is needed for it and none disturbs it).

Of the ring, the spans of the thread that carries `train/dispatch` are
kept, and of those the window's dispatches: the last `run["steps"]` values
of `step` among the `train/dispatch` spans. The warm-up's three dispatches
come before them; the `train/data_wait` in which the feed ends has a
`step` no dispatch carries. A program without a ring (an older checkout)
gives None, and so does every metric that reads this.
"""
from __future__ import annotations

import statistics
from collections import defaultdict

PREFIX = "train/"
DATA_WAIT, STEP = "train/data_wait", "train/step"
PLACE, DISPATCH, FETCH = "train/place", "train/dispatch", "train/fetch"
BUILD_TRAINER = "setup/build_trainer"


def ring_spans():
    """The program's spans, oldest first; None where it keeps none."""
    try:
        from deep_vision_tpu.obs import trace
    except ImportError:
        return None
    reader = getattr(trace, "spans", None)
    return reader() if reader is not None else None


def window_dispatches(run, spans):
    """-> `{step: [span, ...]}` of the window's dispatches: the loop
    thread's `train/*` spans by their `step`. None where `spans` is."""
    if spans is None:
        return None
    threads = {s.thread for s in spans if s.name == DISPATCH}
    by_step = defaultdict(list)
    for s in spans:
        if s.thread in threads and s.name.startswith(PREFIX) \
                and s.step is not None:
            by_step[s.step].append(s)
    dispatched = sorted(step for step, held in by_step.items()
                        if any(s.name == DISPATCH for s in held))
    return {step: by_step[step] for step in dispatched[-run["steps"]:]}


def _ms(spans, name):
    return sum(s.end_ns - s.start_ns for s in spans if s.name == name) * 1e-6


def median_ms(run, name, spans=None):
    """Median over the window's dispatches of the ms spent in `name`."""
    window = window_dispatches(run, ring_spans() if spans is None else spans)
    if not window:
        return None
    return statistics.median(_ms(held, name) for held in window.values())


def busy_ms(held, fetches, split_wall):
    """One dispatch's `train/data_wait` + `train/step` less the outer
    `train/fetch` (`n=1`: the wait for the device and the report's copy)
    inside that `train/step`, by the program's own rule for a wall under
    spans (`obs.trace.split_wall`); `fetches`: the loop's, of any step (the
    one inside reads the dispatch before)."""
    return _ms(held, DATA_WAIT) + sum(
        split_wall(fetches, step.start_ns, step.end_ns,
                   {FETCH: "fetch"})["other"]
        for step in held if step.name == STEP) * 1e-6


def host_busy_ms(run, spans=None):
    """Median over the window's dispatches of all the loop's thread does in
    a step period but wait for the device."""
    spans = ring_spans() if spans is None else spans
    window = window_dispatches(run, spans)
    if not window:
        return None
    threads = {s.thread for held in window.values() for s in held}
    fetches = [s for s in spans if s.name == FETCH and s.thread in threads
               and (s.args or {}).get("n") == 1]
    from deep_vision_tpu.obs.trace import split_wall

    return statistics.median(busy_ms(held, fetches, split_wall)
                             for held in window.values())


def build_trainer_s(spans=None):
    """Seconds of the newest `setup/build_trainer` span."""
    spans = ring_spans() if spans is None else spans
    built = [s for s in spans or () if s.name == BUILD_TRAINER]
    return (built[-1].end_ns - built[-1].start_ns) * 1e-9 if built else None
