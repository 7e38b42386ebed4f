"""Step-time decomposition from profiler captures: where did the step go.

    PYTHONPATH=. python tools/trace_digest.py artifacts/autoprof/cap-000-spike
    PYTHONPATH=. python tools/trace_digest.py <dir> --top 20 --json

The autoprof policy (obs/autoprof.py) and the static capture window both
write TensorBoard xplane protos (`plugins/profile/<ts>/<host>.xplane.pb`).
This tool reads them back WITHOUT TensorBoard: every XLA op execution on
the device lines, aggregated per op and classified compute vs collective
vs host, rendered as a top-k time table. That is step-time decomposition
v2 — v1 (obs_report --trace) sees only the Python-side spans the journal
chose to stamp; this sees every op the compiled executable actually ran,
so "the step got slower" decomposes into "which op" and "compute or
comm" directly from the capture a spike already triggered.

Consumed three ways: this CLI, `obs_report --digest <dir>` (the same
table inside the postmortem report), and — when called in-process —
`perfwatch.note_digest` so the telemetry /statusz perf section carries
the last decomposition next to the live step-time quantiles.

The capture is read with `jax.profiler.ProfileData`, as the benchmark's
reduction reads it (`benchmark/trace.py`, whose interval union and op
names this file uses): no TensorFlow, no protobuf bindings. Captures taken
by the program (`--profile-dir`, autoprof) also hold its own spans
(`train/fetch`, `train/log`, ... — obs/README.md): in `spans.json` in the
capture's directory, stamped by the program with `time.time_ns()`, the clock
of the capture's `profile_start_time` (`obs.trace.write_capture_spans`), and
in the host plane where the session had one. Their totals are printed beside the op table, and on a TPU capture
the device's idle time a step is split over what the loop's thread was in
meanwhile (`benchmark/hostspans.py`'s seven names; `ClockMismatch` where
the spans and the device planes are not on one clock), so "the step got
slower" splits into device ops and what the host loop was doing.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import hostspans  # noqa: E402
from benchmark.trace import (  # noqa: E402
    DEVICE_PLANE_PREFIX,
    MODULES_LINE,
    OPS_LINE,
    short_name,
    union_ns,
)

from deep_vision_tpu.obs.trace import CAPTURE_SPANS  # noqa: E402

__all__ = ["find_xplanes", "digest", "render_digest", "capture_epoch_ns",
           "capture_spans", "host_gaps", "CATEGORIES", "SPAN_PREFIXES"]

CATEGORIES = ("compute", "collective", "host")

#: op-name tokens that mark a device op as communication rather than
#: math — the hyphen/underscore-normalized spelling of
#: obs/costmodel.COLLECTIVE_KINDS plus the send/recv pair fusion emits
_COLLECTIVE_TOKENS = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute", "send", "recv")

# `fusion.123` / `all-reduce.5` -> the base op name the table keys on
_OP_SUFFIX_RE = re.compile(r"\.\d+$")

#: the program's own spans (obs/trace.py `span(...)`; table in obs/README.md)
SPAN_PREFIXES = ("train/", "data/", "checkpoint/", "gan/", "serve/", "infer/")


def _is_span(name: str) -> bool:
    return name == "eval" or name.startswith(SPAN_PREFIXES)


def _classify(op: str, device_line: bool) -> str:
    # HLO op names never contain "::" — runtime C++ methods interleaved
    # on the XLA client line (ThunkExecutor, ThreadpoolListener) are
    # host machinery, not executed ops
    if not device_line or "::" in op:
        return "host"
    norm = op.replace("_", "-").lower()
    for tok in _COLLECTIVE_TOKENS:
        if tok in norm:
            return "collective"
    return "compute"


def find_xplanes(path: str) -> List[str]:
    """Every .xplane.pb under `path` (a capture dir, its plugins/profile
    tree, or a direct .pb file), newest session first."""
    if os.path.isfile(path):
        return [path] if path.endswith(".xplane.pb") else []
    found: List[str] = []
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith(".xplane.pb"):
                found.append(os.path.join(root, f))
    # session dirs are timestamp-named; newest capture first so the
    # single-capture default digests the most recent profile
    return sorted(found, reverse=True)


_EPOCH_PLANE = "Task Environment"


def capture_epoch_ns(profile_planes) -> "tuple[int, int]":
    """`(profile_start_time, profile_stop_time)` of an opened capture, in
    Unix nanoseconds: every event's `start_ns` in it counts from the
    first, and `time.time_ns()` (the spans' stamps) is the same clock.
    Raises where the capture has no `Task Environment` plane with both."""
    for plane in profile_planes:
        if plane.name == _EPOCH_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats and "profile_stop_time" in stats:
                return (int(stats["profile_start_time"]),
                        int(stats["profile_stop_time"]))
    raise ValueError(
        f"no {_EPOCH_PLANE!r} plane with profile_start_time and "
        "profile_stop_time: planes "
        + ", ".join(p.name for p in profile_planes))


def capture_spans(xplane_path: str, profile_planes):
    """The program's spans of a capture: those of the `spans.json` in its
    directory (`<capture>/plugins/profile/<session>/*.xplane.pb`) that lie
    inside `[profile_start_time, profile_stop_time]`, `start_ns` and
    `end_ns` counted from `profile_start_time` like the capture's own
    events. None where the capture has no `spans.json`."""
    session = os.path.dirname(os.path.abspath(xplane_path))
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        session))), CAPTURE_SPANS)
    if not os.path.exists(path):
        return None
    start, stop = capture_epoch_ns(profile_planes)
    with open(path) as f:
        held = json.load(f)["spans"]
    return [{**sp, "start_ns": sp["start_ns"] - start,
             "end_ns": sp["end_ns"] - start}
            for sp in held if sp["start_ns"] >= start and sp["end_ns"] <= stop]


def host_gaps(profile_planes, spans) -> "dict | None":
    """`hostspans.reduce_planes` over a capture's planes: the device's
    idle seconds a step under the seven names. The host plane is the
    capture's own where it holds `train/dispatch`, else one built from
    `spans` (`capture_spans`). None where the capture has no TPU plane or
    no spans of the loop; raises `hostspans.ClockMismatch`."""
    planes = {}
    for plane in profile_planes:
        if plane.name == hostspans.HOST_PLANE:
            planes[plane.name] = {
                line.name: [hostspans._event(e) for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            planes[plane.name] = {
                line.name: [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
                for line in plane.lines
                if line.name in (MODULES_LINE, OPS_LINE)}
    if not any(n.startswith(DEVICE_PLANE_PREFIX) for n in planes):
        return None
    if not any(e[0] == hostspans.DISPATCH
               for events in planes.get(hostspans.HOST_PLANE, {}).values()
               for e in events):
        by_thread: Dict[int, list] = {}
        for sp in spans or ():
            by_thread.setdefault(sp["thread"], []).append(
                (sp["name"], sp["start_ns"], sp["end_ns"] - sp["start_ns"],
                 sp.get("step")))
        if not any(e[0] == hostspans.DISPATCH
                   for events in by_thread.values() for e in events):
            return None
        planes[hostspans.HOST_PLANE] = by_thread
    return hostspans.reduce_planes(planes)


def digest(path: str, *, top_k: int = 12) -> dict:
    """Per-op time decomposition of the newest capture under `path`.

    Returns {"source", "ops": [{"op", "category", "count", "total_ms",
    "mean_us"}...] top-k by total time, "totals": {compute_ms,
    collective_ms, host_ms} (each the union of its intervals per line, so
    nested events count once), "spans": the program's own spans by name,
    "gaps" (a TPU capture with the loop's spans: `host_gaps`, in ms a
    step), "op_count", and "error" instead when the capture can't be
    parsed or its spans lie on another clock than its device planes}.
    """
    planes = find_xplanes(path)
    if not planes:
        return {"source": path, "error": "no .xplane.pb captures found"}
    src = planes[0]
    try:
        import jax

        data = jax.profiler.ProfileData.from_file(src)
        planes = list(data.planes)
    except Exception as e:
        return {"source": src, "error": f"unreadable xplane capture: {e}"}
    agg: Dict[str, dict] = {}
    spans: Dict[str, dict] = {}
    covered = {c: 0.0 for c in CATEGORIES}
    for plane in planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue  # modules, steps: the same ops' time once more
            # the CPU backend runs its ops on a host-plane line named
            # after the PjRt client
            device_line = on_device or line.name.startswith("tf_XLA")
            intervals = {c: [] for c in CATEGORIES}
            for ev in line.events:
                op = short_name(ev.name)
                if op.startswith(("$", "end: ")):
                    # Python-tracer stack frames ($file.py:line fn) nest
                    # once per stack depth; `end:` marks an op's completion
                    continue
                if not device_line and _is_span(op):
                    row = spans.setdefault(op, {"span": op, "count": 0,
                                                "total_ms": 0.0})
                    row["count"] += 1
                    row["total_ms"] += ev.duration_ns / 1e6
                    continue
                cat = _classify(op, device_line)
                key = _OP_SUFFIX_RE.sub("", op) if cat != "host" else op
                row = agg.setdefault(
                    f"{cat}:{key}",
                    {"op": key, "category": cat, "count": 0, "total_ms": 0.0})
                row["count"] += 1
                row["total_ms"] += ev.duration_ns / 1e6
                intervals[cat].append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
            for cat, iv in intervals.items():
                covered[cat] += union_ns(iv) / 1e6
    ops = sorted(agg.values(), key=lambda r: -r["total_ms"])
    for r in ops:
        r["total_ms"] = round(r["total_ms"], 4)
        r["mean_us"] = round(r["total_ms"] * 1e3 / max(1, r["count"]), 2)
    totals = {f"{c}_ms": round(covered[c], 3) for c in CATEGORIES}
    try:
        beside = capture_spans(src, planes)
    except ValueError as e:
        return {"source": src, "error": f"spans.json beside a capture with {e}"}
    if not spans:  # no host plane, or one without the program's spans
        for sp in beside or ():
            if _is_span(sp["name"]):
                row = spans.setdefault(sp["name"], {
                    "span": sp["name"], "count": 0, "total_ms": 0.0})
                row["count"] += 1
                row["total_ms"] += (sp["end_ns"] - sp["start_ns"]) / 1e6
    span_rows = sorted(spans.values(), key=lambda r: -r["total_ms"])
    for r in span_rows:
        r["total_ms"] = round(r["total_ms"], 4)
    out = {"source": src, "op_count": len(ops), "totals": totals,
           "ops": ops[:max(1, int(top_k))], "spans": span_rows}
    try:
        red = host_gaps(planes, beside)
    except hostspans.ClockMismatch as e:
        return {"source": src, "error": f"ClockMismatch: {e}"}
    if red is not None:
        gap_ms = {n: v * 1e3 for n, v in red["gap_s_per_step"].items()}
        out["gaps"] = {"periods": red["periods"],
                       "steps_checked": red["steps_checked"],
                       "devices": len(red["devices"]),
                       "gap_ms_per_step": gap_ms,
                       "host_gap_ms": sum(gap_ms.values())}
    try:  # surface the decomposition on the live /statusz perf section
        from deep_vision_tpu.obs import perfwatch

        perfwatch.note_digest({"source": src, **totals})
    except Exception:
        pass
    return out


def render_digest(d: dict) -> str:
    if d.get("error"):
        return f"trace digest {d.get('source', '?')}: {d['error']}"
    t = d["totals"]
    lines = [f"-- step-time decomposition: {d['source']} --",
             f"compute {t['compute_ms']:.2f} ms  "
             f"collective {t['collective_ms']:.2f} ms  "
             f"host {t['host_ms']:.2f} ms  "
             f"({d['op_count']} distinct ops, top {len(d['ops'])} shown)"]
    if d["ops"]:
        w = max(len(r["op"]) for r in d["ops"])
        lines.append(f"{'op':<{w}}  {'class':<10}  {'count':>6}  "
                     f"{'total ms':>9}  {'mean us':>9}")
        for r in d["ops"]:
            lines.append(f"{r['op']:<{w}}  {r['category']:<10}  "
                         f"{r['count']:>6}  {r['total_ms']:>9.3f}  "
                         f"{r['mean_us']:>9.2f}")
    if d.get("spans"):
        w = max(len(r["span"]) for r in d["spans"])
        lines.append(f"{'span':<{w}}  {'count':>6}  {'total ms':>9}")
        for r in d["spans"]:
            lines.append(f"{r['span']:<{w}}  {r['count']:>6}  "
                         f"{r['total_ms']:>9.3f}")
    if d.get("gaps"):
        g = d["gaps"]
        lines.append(f"device idle {g['host_gap_ms']:.4f} ms a step over "
                     f"{g['periods']} periods ({g['devices']} device(s), "
                     f"clocks checked on {g['steps_checked']} steps), by "
                     "where the loop's thread was:")
        lines += [f"  {n:<16}  {v:>9.4f}"
                  for n, v in g["gap_ms_per_step"].items()]
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("captures", nargs="+",
                   help="capture dir(s) (autoprof cap-* / --profile-dir) "
                        "or direct .xplane.pb path(s)")
    p.add_argument("--top", type=int, default=12,
                   help="rows in the per-op table (default 12)")
    p.add_argument("--json", action="store_true",
                   help="emit the digest dict(s) as JSON lines")
    args = p.parse_args(argv)
    bad = 0
    for path in args.captures:
        d = digest(path, top_k=args.top)
        if args.json:
            print(json.dumps(d, sort_keys=True))
        else:
            print(render_digest(d))
        bad += 1 if d.get("error") else 0
    return 1 if bad == len(args.captures) else 0


if __name__ == "__main__":
    raise SystemExit(main())
