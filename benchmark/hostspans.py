"""What the host was doing in each of the device's idle gaps.

The program's spans (`deep_vision_tpu/obs/trace.py`) are
`jax.profiler.TraceAnnotation`s: a profiler session with its host plane on
holds them on the `/host:CPU` plane of the same `.xplane.pb` as the device
planes, on one clock. This file lays the one over the other. Read with
`jax.profiler.ProfileData` alone, as `trace.py` reads the device planes.

Per device plane the slice and the gaps are `trace.reduce_device`'s: from
the start of the step module's second execution in the trace to the start
of its last, the gaps between the merged intervals of the `XLA Ops` line.
Of the host plane, the events whose name starts with `train/` on the thread
that carries `train/dispatch` are kept. Every instant of every gap gets one name:

- the innermost open span of `train/data_wait`, `train/place`,
  `train/dispatch`, `train/fetch`, `train/log` names it; `train/step` and
  `train/epoch` name nothing, so time between two of their children is
  `other`;
- inside `train/fetch`: an instant before an execution of the step module
  that begins while that span is still open is `launch` (the step is
  dispatched and the device has not begun: its input copy is in flight, or
  the launch is); any other instant is `train/fetch` (the module has ended
  and the host is still fetching, or the device idles inside the program);
- no span open: `other`. That is also how an edge gap is counted whose span
  the session missed because it was open when the session started or
  stopped. (A benchmark run starts and stops its session inside the feed's
  `next()`, and its slice begins at the second module's start, after that
  step's `train/dispatch`: every gap of the slice has its spans.)

A name says where the loop's thread was while the device idled, not what
the device was waiting for: the runtime prepares a batch's copy on threads
of its own (on a TPU it re-tiles the host array first), and the loop is in
`train/dispatch` and then `train/fetch` meanwhile. `train/dispatch` +
`launch` together are the time from the batch's hand-over to the step's
start on the device.

The seven names' seconds per step add up to the slice's idle time per step
(`host_gap_ms`) by construction; the result is the mean over the devices,
as in `trace.reduce_trace`.

The clocks are checked once per run, per device: a `train/dispatch` must
begin before its execution of the step module begins, and the
`train/fetch` that reads that step's report (the one whose `step` argument
is the dispatch's: the loop keeps one step in flight, so it comes after
the next dispatch, and for the trace's last step it may come after the
session) must end after that execution ends. The dispatches are
the trace's executions in order; one execution more than dispatches is
the one the session started inside, the first. A host plane on another
clock than the device's fails this, and the reduction raises instead of
attributing.
"""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

if __name__ == "__main__":  # started as a script: see run.py
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark.trace import (  # noqa: E402
    DEVICE_PLANE_PREFIX,
    MODULES_LINE,
    OPS_LINE,
    clip,
    find_xplane,
    gaps_ns,
    step_runs,
    whole_runs,
)

HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "train/"
DISPATCH, FETCH = "train/dispatch", "train/fetch"
NAMING = ("train/data_wait", "train/place", DISPATCH, FETCH, "train/log")
LAUNCH, OTHER = "launch", "other"
NAMES = (*NAMING, LAUNCH, OTHER)


class ClockMismatch(RuntimeError):
    """The host plane's spans and a device plane's executions do not lie on
    one clock (or are not the same steps)."""


def loop_spans(host_lines):
    """`[(name, start, end, step)]` of the `train/*` events on the thread
    that carries `train/dispatch`. `host_lines`: `{thread: [(name, start_ns,
    duration_ns)]}`, a `train/*` event with its `step` argument fourth."""
    threads = [t for t, events in host_lines.items()
               if any(e[0] == DISPATCH for e in events)]
    if len(threads) != 1:
        raise RuntimeError(
            f"{len(threads)} host threads carry {DISPATCH!r} (need one): the "
            "session's host plane is off, or the program has no such span")
    return threads[0], sorted(
        ((e[0], e[1], e[1] + e[2], e[3]) for e in host_lines[threads[0]]
         if e[0].startswith(SPAN_PREFIX)), key=lambda span: span[:3])


def check_clocks(spans, runs):
    """Raise `ClockMismatch` unless every step's dispatch precedes its
    execution and the fetch of its report outlasts it. `runs`: every
    execution of the step module in the trace. -> the number of steps
    checked."""
    dispatches = [(s, step) for n, s, _, step in spans if n == DISPATCH]
    fetch_ends = defaultdict(list)
    for n, _, end, step in spans:
        if n == FETCH:
            fetch_ends[step].append(end)
    # an execution the session started inside has no dispatch in the trace
    started_inside = len(runs) - len(dispatches)
    if started_inside not in (0, 1):
        raise ClockMismatch(
            f"{len(dispatches)} {DISPATCH} spans for {len(runs)} executions "
            "of the step module: not the same steps")
    for k, ((d_start, step), (m_start, m_end)) in enumerate(
            zip(dispatches, runs[started_inside:])):
        if d_start >= m_start:
            raise ClockMismatch(
                f"step {k}: {DISPATCH} begins {(d_start - m_start) * 1e-6:.3f}"
                " ms after its module begins on the device")
        # the sampled fence is a `train/fetch` of the same step inside it
        fetch_end = max(fetch_ends[step], default=None)
        if fetch_end is None and k == len(dispatches) - 1:
            break  # the session stopped with this step in flight, unread
        if fetch_end is None or fetch_end <= m_end:
            raise ClockMismatch(
                f"step {k}: the {FETCH} of its report ends "
                + ("nowhere in the trace" if fetch_end is None else
                   f"{(m_end - fetch_end) * 1e-6:.3f} ms before its module "
                   "ends on the device"))
    return len(dispatches)


def attribute(gaps, spans, runs):
    """-> `{name: ns}` over `NAMES` for idle `gaps` `[(start, end), ...]`."""
    naming = [(s, e, n) for n, s, e, *_ in spans if n in NAMING]
    starts = sorted(s for s, _ in runs)
    out = dict.fromkeys(NAMES, 0.0)
    for g_start, g_end in gaps:
        inside = [(max(s, g_start), min(e, g_end), s, e, n)
                  for s, e, n in naming if e > g_start and s < g_end]
        cuts = sorted({g_start, g_end, *(c for s, e, *_ in inside
                                         for c in (s, e)),
                       *(m for m in starts if g_start < m < g_end)})
        for lo, hi in zip(cuts, cuts[1:]):
            open_here = [(s0, e0, n) for s, e, s0, e0, n in inside
                         if s <= lo and e >= hi]
            if not open_here:
                out[OTHER] += hi - lo
                continue
            _, span_end, name = max(open_here)  # the innermost: latest start
            if name == FETCH and any(hi <= m < span_end for m in starts):
                name = LAUNCH
            out[name] += hi - lo
    return out


def reduce_device(modules, ops, spans) -> dict | None:
    """One device's table. `modules`, `ops`: `(name, start_ns, duration_ns)`
    of its two lines; `spans`: `loop_spans(...)[1]`. None where
    `trace.reduce_device` gives none."""
    _, runs = step_runs(modules)
    whole = whole_runs(runs)
    if len(whole) < 2:
        return None
    checked = check_clocks(spans, runs)
    lo, hi = whole[0][0], whole[-1][0]
    periods = len(whole) - 1
    busy = clip([(s, s + d) for _, s, d in ops if s + d > lo and s < hi],
                lo, hi)
    table = attribute([(s, e) for s, e, _ in gaps_ns(busy, lo, hi)],
                      spans, runs)
    return {"periods": periods, "steps_checked": checked,
            "gap_s_per_step": {n: v * 1e-9 / periods
                               for n, v in table.items()}}


def reduce_planes(planes) -> dict:
    """`planes`: `{plane name: {line name: [(event name, start_ns,
    duration_ns)]}}`. -> the mean table over the device planes."""
    if HOST_PLANE not in planes:
        raise RuntimeError(f"the trace has no {HOST_PLANE} plane: "
                           + ", ".join(planes))
    thread, spans = loop_spans(planes[HOST_PLANE])
    devices = []
    for name, lines in planes.items():
        if (name.startswith(DEVICE_PLANE_PREFIX) and MODULES_LINE in lines
                and OPS_LINE in lines):
            red = reduce_device(lines[MODULES_LINE], lines[OPS_LINE], spans)
            if red is not None:
                devices.append(red)
    if not devices:
        raise RuntimeError("the trace has no device plane with a repeated "
                           "module: " + ", ".join(planes))
    table = {n: sum(d["gap_s_per_step"][n] for d in devices) / len(devices)
             for n in NAMES}
    return {"thread": thread, "devices": devices,
            "periods": devices[0]["periods"],
            "steps_checked": devices[0]["steps_checked"],
            "gap_s_per_step": table}


def _event(e):
    """An event as the reductions take it; a loop's span with its `step`."""
    if e.name.startswith(SPAN_PREFIX):
        return (e.name, e.start_ns, e.duration_ns, dict(e.stats).get("step"))
    return (e.name, e.start_ns, e.duration_ns)


def reduce_host_spans(trace_dir: str) -> dict:
    """The reduction of the capture under `trace_dir` (or of one
    `.xplane.pb`)."""
    import jax

    path = trace_dir if os.path.isfile(trace_dir) else find_xplane(trace_dir)
    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE or plane.name.startswith(
                DEVICE_PLANE_PREFIX):
            planes[plane.name] = {
                line.name: [_event(e) for e in line.events]
                for line in plane.lines
                if plane.name == HOST_PLANE
                or line.name in (MODULES_LINE, OPS_LINE)}
    return reduce_planes(planes)


def gap_ms(run, name):
    """A `gap_*_ms` metric's reading of the adapter's record: idle
    milliseconds per step under `name`; None where the record has no
    host-span reduction (the session's host plane was off)."""
    red = run.get("host_spans")
    return red["gap_s_per_step"][name] * 1e3 if red else None


if __name__ == "__main__":
    # any capture with its host plane on, e.g. `train.py --profile-dir`
    print(json.dumps(reduce_host_spans(sys.argv[1])))
