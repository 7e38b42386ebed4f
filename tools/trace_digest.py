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
by the program (`obs.trace.start_profiler`) also hold its own spans
(`train/fetch`, `train/log`, ... — obs/README.md) in the host plane; their
totals are printed beside the op table, so "the step got slower" splits
into device ops and what the host loop was doing meanwhile.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace import OPS_LINE, short_name, union_ns  # noqa: E402

__all__ = ["find_xplanes", "digest", "render_digest", "CATEGORIES",
           "SPAN_PREFIXES"]

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


def digest(path: str, *, top_k: int = 12) -> dict:
    """Per-op time decomposition of the newest capture under `path`.

    Returns {"source", "ops": [{"op", "category", "count", "total_ms",
    "mean_us"}...] top-k by total time, "totals": {compute_ms,
    collective_ms, host_ms} (each the union of its intervals per line, so
    nested events count once), "spans": the program's own spans by name,
    "op_count", and "error" instead when the capture can't be parsed}.
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
    span_rows = sorted(spans.values(), key=lambda r: -r["total_ms"])
    for r in span_rows:
        r["total_ms"] = round(r["total_ms"], 4)
    out = {"source": src, "op_count": len(ops), "totals": totals,
           "ops": ops[:max(1, int(top_k))], "spans": span_rows}
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
