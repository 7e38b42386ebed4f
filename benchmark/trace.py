"""Reduction of a profiler trace (`.xplane.pb`) to device intervals.

Read with `jax.profiler.ProfileData` alone. A device plane
(`/device:TPU:<n>`) carries one line of whole-program executions
(`XLA Modules`) and one of the operations inside them (`XLA Ops`). The
training step is the module that takes most of the device's time; the
slice that is measured runs from the start of its second execution in the
trace to the start of its last, so it holds whole step periods and nothing
else, on the device's own clock. The first execution is left out because
a loop that keeps a step in flight starts the session inside it, and it
enters the trace with its start cut to the session's: a period that begins
there is short. Busy time is the union of the operations' intervals in
that slice; everything else in it is idle.
"""
from __future__ import annotations

import glob
import os
import statistics
from collections import defaultdict

DEVICE_PLANE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def union_ns(intervals) -> float:
    """Total length covered by `(start, end)` intervals, overlaps and
    nesting counted once."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def gaps_ns(intervals, lo, hi):
    """Idle gaps `(start, end, index of the interval before it)` inside
    `[lo, hi)`, between the merged `(start, end)` intervals."""
    out, reach, before = [], lo, None
    for i, (start, end) in sorted(enumerate(intervals), key=lambda t: t[1]):
        if start > reach:
            out.append((reach, min(start, hi), before))
        if end > reach:
            reach, before = end, i
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi, before))
    return out


def short_name(event_name: str) -> str:
    """An op's event carries its whole HLO line; its name is the part
    before ` = `, without the `%`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def step_runs(modules):
    """-> (the step module's name, `(start, end)` of each of its executions
    in order). The step module is the one that takes most of the device's
    time. `modules`: `(name, start_ns, duration_ns)` of the modules' line."""
    by_module = defaultdict(list)
    for name, start, dur in modules:
        by_module[name].append((start, start + dur))
    if not by_module:
        return None, []
    step = max(by_module, key=lambda n: sum(e - s for s, e in by_module[n]))
    return step, sorted(by_module[step])


def whole_runs(runs):
    """The executions whose starts bound whole step periods: all but the
    trace's first, which a session that starts inside it cuts short."""
    return runs[1:]


def reduce_device(modules, ops) -> dict | None:
    """One device's reduction. `modules` and `ops` are lists of
    `(name, start_ns, duration_ns)`. None where the step module has fewer
    than two executions after its first."""
    step_name, runs = step_runs(modules)
    runs = whole_runs(runs)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    periods = len(runs) - 1
    inside = [(n, s, d) for n, s, d in ops if s + d > lo and s < hi]
    spans = clip([(s, s + d) for _, s, d in inside], lo, hi)
    busy = union_ns(spans)
    per_op = defaultdict(float)
    for (name, _, _), (s, e) in zip(inside, spans):
        per_op[short_name(name)] += e - s
    per_gap = defaultdict(float)
    for s, e, before in gaps_ns(spans, lo, hi):
        per_gap["after:" + (short_name(inside[before][0])
                            if before is not None else "slice start")] += e - s
    return {
        "step_module": step_name,
        "periods": periods,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "step_device_ms": statistics.median(
            e - s for s, e in runs[:-1]) * 1e-6,
        "op_s_per_step": {n: v * 1e-9 / periods for n, v in per_op.items()},
        "gap_s_per_step": {n: v * 1e-9 / periods for n, v in per_gap.items()},
    }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(trace_dir: str) -> dict:
    """-> {"devices": [reduce_device(...) per device plane], and the means
    over devices of window_s, busy_s, step_device_ms, the op table and the
    gap table}. Raises where the trace holds no device plane with steps."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    devices = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE not in lines or OPS_LINE not in lines:
            continue
        red = reduce_device(
            [(e.name, e.start_ns, e.duration_ns)
             for e in lines[MODULES_LINE].events],
            [(e.name, e.start_ns, e.duration_ns)
             for e in lines[OPS_LINE].events])
        if red is not None:
            devices.append(red)
    if not devices:
        raise RuntimeError(
            "the trace has no device plane with a repeated module: planes "
            + ", ".join(p.name for p in data.planes))
    n = len(devices)
    mean = lambda key: sum(d[key] for d in devices) / n
    tables = {}
    for key in ("op_s_per_step", "gap_s_per_step"):
        merged = defaultdict(float)
        for d in devices:
            for name, v in d[key].items():
                merged[name] += v / n
        tables[key] = dict(merged)
    return {"devices": devices, "window_s": mean("window_s"),
            "busy_s": mean("busy_s"), "periods": devices[0]["periods"],
            "step_device_ms": mean("step_device_ms"),
            "step_module": devices[0]["step_module"], **tables}
