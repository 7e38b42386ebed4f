"""Render a run journal (obs/journal.py JSONL) into a per-run summary table.

    PYTHONPATH=. python tools/obs_report.py runs/resnet50.journal.jsonl [...]
    PYTHONPATH=. python tools/obs_report.py run.jsonl --trace run.trace.json

One table row block per run_id found in the files: manifest identity,
step-time/data-wait/examples-per-sec statistics (mean/p50/p90 from the
per-step events), recompile and HBM peaks, eval/checkpoint/bench events,
health findings (obs/health.py: non-finite steps, loss spikes, watchdog
hang dumps), and the terminal marker (clean exit vs crash vs
still-running). With `--trace`, a per-span time summary of the matching
Chrome trace (obs/trace.py) follows: total/mean/p50/p95/max wall ms per
span name — the "where did the time go" table without opening Perfetto.
With `--merged`, the input is a `tools/obs_merge.py` multi-host timeline
and the report shows per-host step statistics plus every detected
straggler. This is the diff surface for BENCH_* rounds: two journals
from different PRs summarize into directly comparable tables.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deep_vision_tpu.obs.journal import read_journal  # noqa: E402


def _percentile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    i = min(len(s) - 1, int(round(q * (len(s) - 1))))
    return s[i]


def _stats(xs: List[float]) -> Optional[dict]:
    if not xs:
        return None
    return {
        "n": len(xs),
        "mean": sum(xs) / len(xs),
        "p50": _percentile(xs, 0.5),
        "p90": _percentile(xs, 0.9),
        "max": max(xs),
    }


def summarize_run(events: List[dict]) -> dict:
    """Collapse one run's events into the report row dict."""
    out: dict = {"run_id": events[0].get("run_id", "?")}
    steps = [e for e in events if e.get("event") == "step"]
    manifest = next((e for e in events if e.get("event") == "run_manifest"), None)
    if manifest:
        out["kind"] = manifest.get("kind", "?")
        out["backend"] = manifest.get("backend", "?")
        out["devices"] = "%s x%s" % (
            manifest.get("device_kind", "?"), manifest.get("device_count", "?"))
        cfg = manifest.get("config") or {}
        if cfg:
            out["config"] = "%s (%s)" % (cfg.get("name", "?"), cfg.get("task", "?"))
        out["jax"] = manifest.get("jax_version", "?")
    out["steps"] = len(steps)
    for field in ("step_time_ms", "data_wait_ms", "examples_per_sec", "sync_ms"):
        st = _stats([float(e[field]) for e in steps if field in e])
        if st:
            out[field] = st
    recompiles = [int(e["recompiles"]) for e in steps if "recompiles" in e]
    if recompiles:
        out["recompiles"] = max(recompiles)
    # prefer the backend's true high-water (hbm_peak_bytes, stepclock
    # peak_bytes_in_use) over the max of sampled instantaneous values
    peak = [int(e["hbm_peak_bytes"]) for e in steps if "hbm_peak_bytes" in e]
    hbm = peak or [int(e["hbm_bytes"]) for e in steps if "hbm_bytes" in e]
    if hbm:
        out["hbm_peak_gb"] = max(hbm) / 1e9
    out["epochs"] = [e for e in events if e.get("event") == "epoch"]
    out["evals"] = [e for e in events if e.get("event") == "eval"]
    out["health"] = [e for e in events if e.get("event") == "health"]
    out["captures"] = [e for e in events
                       if e.get("event") == "profile_capture"]
    out["flight_dumps"] = [e for e in events
                           if e.get("event") == "flight_dump"]
    out["lock_violations"] = [e for e in events
                              if e.get("event") == "lock_order_violation"]
    out["lock_contention"] = [e for e in events
                              if e.get("event") == "lock_contention"]
    out["checkpoints"] = sum(
        1 for e in events if e.get("event") == "checkpoint" and e.get("saved"))
    out["benches"] = [e for e in events if e.get("event") == "bench"]
    serving = summarize_serving(events)
    if serving:
        out["serving"] = serving
    fleet_edge = summarize_fleet_edge(events)
    if fleet_edge:
        out["fleet_edge"] = fleet_edge
    data_plane = summarize_data_plane(events)
    if data_plane:
        out["data_plane"] = data_plane
    membership = summarize_membership(events)
    if membership:
        out["membership"] = membership
    cold_path = summarize_cold_path(events)
    if cold_path:
        out["cold_path"] = cold_path
    sharding = summarize_sharding(events)
    if sharding:
        out["sharding"] = sharding
    perf = summarize_perf(events)
    if perf:
        out["perf"] = perf
    goodput = summarize_goodput(events)
    if goodput:
        out["goodput"] = goodput
    alerts = summarize_alerts(events)
    if alerts:
        out["alerts"] = alerts
    terminal = next(
        (e for e in reversed(events) if e.get("event") in ("exit", "crash")),
        None)
    if terminal is None:
        out["status"] = "RUNNING-OR-KILLED (no terminal event)"
    elif terminal["event"] == "crash":
        out["status"] = "CRASHED: " + str(terminal.get("reason", ""))
    else:
        out["status"] = terminal.get("status", "clean_exit")
    first, last = events[0].get("ts"), events[-1].get("ts")
    if first is not None and last is not None:
        out["wall_s"] = float(last) - float(first)
    return out


def summarize_serving(events: List[dict]) -> Optional[dict]:
    """Collapse serve_* events (serve/router.py) into per-model serving
    rows: request counts, latency tail quantiles recomputed from the
    per-request events (exact, unlike the registry's bucket-resolution
    quantiles), batch occupancy and padding waste from the serve_batch
    aggregates, and the drain verdict. Fleet journals (serve/pool.py:
    replica-tagged requests, serve_shed / serve_swap / replica_lost
    events) additionally get per-replica ok/err rows, shed counts by
    reason, the swap timeline with the canary verdict, replica
    lost/recovered history, and pool-level latency tails recomputed
    exactly from the per-request events. None when the journal carries
    no serving traffic — training-only reports stay unchanged."""
    requests = [e for e in events if e.get("event") == "serve_request"]
    batches = [e for e in events if e.get("event") == "serve_batch"]
    drains = [e for e in events if e.get("event") == "serve_drain"]
    sheds = [e for e in events if e.get("event") == "serve_shed"]
    swaps = [e for e in events if e.get("event") == "serve_swap"]
    lost = [e for e in events if e.get("event") == "replica_lost"]
    recovered = [e for e in events if e.get("event") == "replica_recovered"]
    if not (requests or batches or drains or sheds or swaps or lost):
        return None
    models: Dict[str, dict] = {}

    def row_for(e):
        return models.setdefault(
            e.get("model", "?"),
            {"ok": 0, "error": 0, "rejected": 0, "cancelled": 0,
             "latencies": [], "slots": 0, "padded": 0, "batches": 0})

    for e in requests:
        m = row_for(e)
        outcome = e.get("outcome")
        # unknown outcomes (future producer / corrupt row) count as
        # errors rather than crashing the postmortem report — the strict
        # enum lives in check_journal, not here
        m[outcome if outcome in ("ok", "error", "rejected", "cancelled")
          else "error"] += 1
        if outcome == "ok" and isinstance(e.get("latency_ms"), (int, float)):
            m["latencies"].append(float(e["latency_ms"]))
    for e in batches:
        m = row_for(e)
        bucket, size = e.get("bucket"), e.get("size")
        if not isinstance(bucket, int) or not isinstance(size, int):
            continue  # corrupt/foreign row: never crash the postmortem
        m["batches"] += 1
        m["slots"] += bucket
        m["padded"] += max(0, bucket - size)
    out: dict = {"models": {}}
    for name, m in sorted(models.items()):
        row = {"ok": m["ok"], "error": m["error"], "rejected": m["rejected"],
               "cancelled": m["cancelled"], "batches": m["batches"]}
        if m["latencies"]:
            row.update(
                p50_ms=_percentile(m["latencies"], 0.5),
                p95_ms=_percentile(m["latencies"], 0.95),
                p99_ms=_percentile(m["latencies"], 0.99),
                mean_ms=sum(m["latencies"]) / len(m["latencies"]),
            )
        if m["slots"]:
            row["occupancy_pct"] = 100.0 * (m["slots"] - m["padded"]) \
                / m["slots"]
            row["padding_waste_pct"] = 100.0 * m["padded"] / m["slots"]
        out["models"][name] = row
    if drains:
        # the fleet verdict is the POOL's aggregated drain; a canary or
        # replica drain mid-run (swap promote/rollback writes one) must
        # not pose as the shutdown verdict in a crashed-run postmortem
        pool_drains = [e for e in drains if e.get("scope") == "pool"]
        last = (pool_drains or drains)[-1]
        out["drain"] = {k: last.get(k) for k in
                        ("reason", "outcome", "accepted", "completed",
                         "errors", "cancelled", "pending", "shed",
                         "offered", "refused", "replicas")
                        if last.get(k) is not None}
    fleet = summarize_fleet(requests, sheds, swaps, lost, recovered)
    if fleet:
        out["fleet"] = fleet
    return out


def summarize_fleet_edge(events: List[dict]) -> Optional[dict]:
    """The front door's view (serve/transport.py journal events): the
    status-code ledger across every transport_request, outcome counts
    with the offered == sum-of-outcomes balance verdict, deadline sheds
    split by stage (admission vs dispatch — WHERE the budget died), the
    latency tail of the 200s recomputed exactly, and each endpoint's
    lifecycle. None when the journal carries no transport events —
    in-process serving reports render byte-unchanged."""
    requests = [e for e in events if e.get("event") == "transport_request"]
    servers = [e for e in events if e.get("event") == "transport_server"]
    if not (requests or servers):
        return None
    out: dict = {}
    if requests:
        by_status: Dict[str, int] = {}
        outcomes: Dict[str, int] = {}
        deadline_stages: Dict[str, int] = {}
        latencies: List[float] = []
        for e in requests:
            st = e.get("status")
            by_status[str(st)] = by_status.get(str(st), 0) + 1
            oc = str(e.get("outcome", "?"))
            outcomes[oc] = outcomes.get(oc, 0) + 1
            if oc == "deadline":
                stage = str(e.get("stage", "?"))
                deadline_stages[stage] = deadline_stages.get(stage, 0) + 1
            if st == 200 and isinstance(e.get("latency_ms"), (int, float)):
                latencies.append(float(e["latency_ms"]))
        out["requests"] = {
            "offered": len(requests),
            "by_status": {k: by_status[k] for k in sorted(by_status)},
            "outcomes": {k: outcomes[k] for k in sorted(outcomes)},
            # every journaled request carries exactly one outcome, so
            # the wire ledger balances by construction — a False here
            # means a truncated/hand-edited journal
            "balanced": len(requests) == sum(outcomes.values()),
        }
        if deadline_stages:
            out["deadline_stages"] = deadline_stages
        if latencies:
            out["latency"] = {
                "n": len(latencies),
                "p50_ms": _percentile(latencies, 0.5),
                "p99_ms": _percentile(latencies, 0.99),
            }
    if servers:
        eps: Dict[str, dict] = {}
        for e in servers:
            key = f"{e.get('host', '?')}:{e.get('port', '?')}"
            row = eps.setdefault(key, {"started": 0, "stopped": 0,
                                       "failed": 0})
            oc = e.get("outcome")
            if oc in row:
                row[oc] += 1
        out["servers"] = eps
    return out


def summarize_data_plane(events: List[dict]) -> Optional[dict]:
    """The data-plane view (data/snapshot.py + data/service.py events):
    service throughput and reconnects from the `data_service` role
    summaries, worker lost/recovered history, and the `data_resume`
    verdict — the "did the input pipeline resume where the model did"
    answer. None when the journal carries no data-plane events, so
    every existing report renders unchanged."""
    resumes = [e for e in events if e.get("event") == "data_resume"]
    lost = [e for e in events if e.get("event") == "data_worker_lost"]
    recovered = [e for e in events
                 if e.get("event") == "data_worker_recovered"]
    summaries = [e for e in events if e.get("event") == "data_service"]
    if not (resumes or lost or recovered or summaries):
        return None
    out: dict = {}
    if resumes:
        out["resumes"] = [
            {k: e.get(k) for k in
             ("verdict", "epoch", "batches", "shard", "record")
             if e.get(k) is not None}
            for e in resumes]
    roles: Dict[str, dict] = {}
    for e in summaries:
        role = str(e.get("role", "?"))
        row = roles.setdefault(role, {"batches": 0, "reconnects": 0,
                                      "workers_lost": 0,
                                      "workers_recovered": 0, "n": 0})
        row["n"] += 1
        for k in ("batches", "reconnects", "workers_lost",
                  "workers_recovered"):
            if isinstance(e.get(k), int):
                row[k] += e[k]
    if roles:
        out["service"] = roles
    if lost or recovered:
        out["workers"] = {"lost": len(lost), "recovered": len(recovered)}
    return out


def summarize_membership(events: List[dict]) -> Optional[dict]:
    """The host-membership timeline (resilience/rendezvous.py events):
    generation history from `world_resized`, per-host loss/join rows
    with lease gaps from `host_lost`/`host_joined`, and the data-plane
    reshards that followed. None when the journal carries no membership
    events — every existing report renders byte-unchanged."""
    lost = [e for e in events if e.get("event") == "host_lost"]
    joined = [e for e in events if e.get("event") == "host_joined"]
    resized = [e for e in events if e.get("event") == "world_resized"]
    reshards = [e for e in events if e.get("event") == "data_reshard"]
    if not (lost or joined or resized or reshards):
        return None
    out: dict = {}
    if resized:
        out["generations"] = [
            {k: e.get(k) for k in
             ("generation", "from", "to", "resume_step", "ts")
             if e.get(k) is not None}
            for e in resized]
    if lost:
        out["lost"] = [
            {k: e.get(k) for k in ("host", "generation", "lease_gap_s", "ts")
             if e.get(k) is not None}
            for e in lost]
    if joined:
        out["joined"] = [
            {k: e.get(k) for k in ("host", "generation", "ts")
             if e.get(k) is not None}
            for e in joined]
    if reshards:
        out["reshards"] = [
            {k: e.get(k) for k in
             ("generation", "from", "to", "shard_index", "num_shards")
             if e.get(k) is not None}
            for e in reshards]
    return out


def summarize_cold_path(events: List[dict]) -> Optional[dict]:
    """The executable-cache / quantization view (core/excache.py +
    serve/quantize.py events): hit/miss/store/invalid counts with the
    invalid reasons spelled out, plus each calibration verdict. None
    when the journal carries no cold-path events — training-only and
    pre-cache serving reports render byte-unchanged."""
    hits = [e for e in events if e.get("event") == "excache_hit"]
    misses = [e for e in events if e.get("event") == "excache_miss"]
    stores = [e for e in events if e.get("event") == "excache_store"]
    invalid = [e for e in events if e.get("event") == "excache_invalid"]
    quants = [e for e in events if e.get("event") == "quant_calibrated"]
    if not (hits or misses or stores or invalid or quants):
        return None
    out: dict = {"hits": len(hits), "misses": len(misses),
                 "stores": len(stores), "invalid": len(invalid)}
    if invalid:
        by_reason: dict = {}
        for e in invalid:
            r = str(e.get("reason", "?"))
            by_reason[r] = by_reason.get(r, 0) + 1
        out["invalid_reasons"] = by_reason
    if quants:
        out["quant"] = [
            {"model": e.get("model", "?"),
             "metric": e.get("metric", "?"),
             "delta": e.get("delta"),
             "tolerance": e.get("tolerance"),
             "accepted": bool(e.get("accepted"))}
            for e in quants]
    return out


def summarize_sharding(events: List[dict]) -> Optional[dict]:
    """The declarative-sharding view (parallel/shardmap.py): each
    `sharding_resolved` event's coverage ledger (matched/unmatched,
    sharded vs replicated float leaves, the mesh it resolved on) with
    the top rule hit counts, plus scaling-efficiency rows when the
    journal carries a MULTICHIP bench event (tools/scaling.py rows as
    `tools/shard_smoke.py` journals them, recognized by their
    data+efficiency keys).
    None when the journal has neither — every existing report renders
    byte-unchanged."""
    resolved = [e for e in events if e.get("event") == "sharding_resolved"]
    scaling: List[dict] = []
    for e in events:
        if e.get("event") != "bench":
            continue
        rows = (e.get("result") or {}).get("rows")
        if isinstance(rows, list) and rows and all(
                isinstance(r, dict) and "data" in r and "efficiency" in r
                for r in rows):
            scaling.extend(rows)
    if not (resolved or scaling):
        return None
    out: dict = {}
    if resolved:
        tables = []
        for e in resolved:
            row = {k: e.get(k) for k in
                   ("model", "matched", "unmatched", "sharded_leaves",
                    "replicated", "float_leaves", "mesh", "dropped_dims")
                   if e.get(k) is not None}
            rules = e.get("rules")
            if isinstance(rules, dict):
                hits = [(p, n) for p, n in rules.items()
                        if isinstance(n, int) and n > 0]
                hits.sort(key=lambda pn: -pn[1])
                row["top_rules"] = hits[:5]
            paths = e.get("unmatched_paths")
            if isinstance(paths, list) and paths:
                row["unmatched_paths"] = [str(p) for p in paths[:5]]
            tables.append(row)
        out["tables"] = tables
    if scaling:
        out["scaling"] = scaling
    return out


def summarize_perf(events: List[dict]) -> Optional[dict]:
    """The performance-attribution view (obs/perfwatch.py +
    tools/perf_gate.py events): one row per profiled jit pair with its
    XLA cost analysis and collective roll-up, the per-(kind, dtype)
    collective inventory under it, and every gate breach with the
    baseline/threshold it broke. None when the journal carries no perf
    events — every existing report renders byte-unchanged."""
    profiles = [e for e in events if e.get("event") == "perf_profile"]
    collectives = [e for e in events if e.get("event") == "perf_collective"]
    regressions = [e for e in events if e.get("event") == "perf_regression"]
    if not (profiles or collectives or regressions):
        return None
    out: dict = {}
    if profiles:
        pairs = []
        for e in profiles:
            row = {k: e.get(k) for k in
                   ("name", "flops", "bytes_accessed", "temp_bytes",
                    "collective_count", "collective_bytes", "source")
                   if e.get(k) is not None}
            row["collectives"] = [
                {k: c.get(k) for k in
                 ("kind", "dtype", "ops", "bytes", "group_size")
                 if c.get(k) is not None}
                for c in collectives if c.get("name") == e.get("name")]
            pairs.append(row)
        out["pairs"] = pairs
    if regressions:
        out["regressions"] = [
            {k: e.get(k) for k in
             ("metric", "baseline", "observed", "threshold", "direction")
             if e.get(k) is not None}
            for e in regressions]
    return out


def summarize_goodput(events: List[dict]) -> Optional[dict]:
    """The wall-clock attribution view (obs/goodput.py events): the
    terminal `goodput_summary` when the run wrote one (the meter's
    closer guarantees it on any journal'd exit), else the running total
    accumulated over `goodput_interval` rows (a SIGKILLed run leaves
    only those). The imbalance flag marks an accounting leak — buckets
    that do not sum to wall clock within 2%. None when the journal
    carries no goodput events, so every pre-goodput report renders
    byte-unchanged."""
    summaries = [e for e in events if e.get("event") == "goodput_summary"]
    intervals = [e for e in events if e.get("event") == "goodput_interval"]
    if not (summaries or intervals):
        return None
    if summaries:
        last = summaries[-1]
        buckets = {k: float(v) for k, v in (last.get("buckets") or {}).items()
                   if isinstance(v, (int, float))}
        return {"source": "summary",
                "wall_s": float(last.get("wall_s", 0.0) or 0.0),
                "goodput_frac": float(last.get("goodput_frac", 0.0) or 0.0),
                "imbalance_frac": float(
                    last.get("imbalance_frac", 0.0) or 0.0),
                "buckets": buckets}
    buckets = {}
    wall = 0.0
    for e in intervals:
        wall += float(e.get("dur_s", 0.0) or 0.0)
        for k, v in (e.get("buckets") or {}).items():
            if isinstance(v, (int, float)):
                buckets[k] = buckets.get(k, 0.0) + float(v)
    total = sum(buckets.values())
    return {"source": "intervals",
            "wall_s": wall,
            "goodput_frac": (buckets.get("productive_step", 0.0) / wall
                             if wall > 0 else 0.0),
            "imbalance_frac": (abs(wall - total) / wall if wall > 0
                               else 0.0),
            "buckets": buckets}


def summarize_alerts(events: List[dict]) -> Optional[dict]:
    """The burn-rate alert timeline (obs/alerts.py events): each
    `alert_fired` paired FIFO-per-rule with its `alert_resolved`, plus
    any alert still firing when the journal ended. None when the journal
    carries no alert events — alert-free reports render byte-unchanged."""
    fired = [e for e in events if e.get("event") == "alert_fired"]
    resolved = [e for e in events if e.get("event") == "alert_resolved"]
    if not (fired or resolved):
        return None
    open_by_rule: Dict[str, List[dict]] = {}
    episodes: List[dict] = []
    for e in fired:
        row = {k: e.get(k) for k in
               ("rule", "severity", "value", "threshold", "window_s")
               if e.get(k) is not None}
        row["fired_ts"] = e.get("ts")
        episodes.append(row)
        open_by_rule.setdefault(str(e.get("rule", "?")), []).append(row)
    for e in resolved:
        q = open_by_rule.get(str(e.get("rule", "?")))
        if q:
            row = q.pop(0)
            row["resolved_ts"] = e.get("ts")
            if isinstance(e.get("dur_s"), (int, float)):
                row["dur_s"] = float(e["dur_s"])
    return {"episodes": episodes,
            "still_firing": sum(1 for r in episodes
                                if "resolved_ts" not in r)}


def summarize_fleet(requests: List[dict], sheds: List[dict],
                    swaps: List[dict], lost: List[dict],
                    recovered: List[dict]) -> Optional[dict]:
    """The per-replica / swap-timeline view of a fleet journal
    (serve/pool.py). None when nothing carries a replica tag and no
    fleet events exist — single-server journals render exactly as
    before."""
    replicas: Dict[str, dict] = {}

    def replica_row(rid):
        return replicas.setdefault(
            rid, {"ok": 0, "error": 0, "rejected": 0, "cancelled": 0,
                  "lost": 0, "recovered": 0})

    for e in requests:
        rid = e.get("replica")
        if not isinstance(rid, str):
            continue
        row = replica_row(rid)
        outcome = e.get("outcome")
        row[outcome if outcome in ("ok", "error", "rejected", "cancelled")
            else "error"] += 1
    for key, events in (("lost", lost), ("recovered", recovered)):
        for e in events:
            if isinstance(e.get("replica"), str):
                replica_row(e["replica"])[key] += 1
    shed_rows: Dict[str, Dict[str, int]] = {}
    for e in sheds:
        by_reason = shed_rows.setdefault(str(e.get("model", "?")), {})
        reason = str(e.get("reason", "?"))
        by_reason[reason] = by_reason.get(reason, 0) + 1
    timelines: Dict[int, List[dict]] = {}
    for e in swaps:
        sid = e.get("swap")
        sid = sid if isinstance(sid, int) else 0
        timelines.setdefault(sid, []).append(
            {k: e.get(k) for k in
             ("phase", "outcome", "reason", "error", "canary_ok",
              "canary_err", "error_rate", "p99_ms", "pct", "replica")
             if e.get(k) is not None})
    if not (replicas or shed_rows or timelines):
        return None
    out: dict = {}
    if replicas:
        out["replicas"] = {rid: replicas[rid] for rid in sorted(replicas)}
        # the pool-level tail across every replica and model: the number
        # an operator pages on, exact from the per-request events
        lat = [float(e["latency_ms"]) for e in requests
               if e.get("outcome") == "ok"
               and isinstance(e.get("latency_ms"), (int, float))]
        if lat:
            out["pool_latency"] = {
                "n": len(lat),
                "p50_ms": _percentile(lat, 0.5),
                "p95_ms": _percentile(lat, 0.95),
                "p99_ms": _percentile(lat, 0.99),
            }
    if shed_rows:
        out["shed"] = shed_rows
    if timelines:
        out["swaps"] = [timelines[sid] for sid in sorted(timelines)]
    return out


def _fmt_stat(st: dict, unit: str = "") -> str:
    return (f"mean {st['mean']:.2f}{unit}  p50 {st['p50']:.2f}{unit}  "
            f"p90 {st['p90']:.2f}{unit}  max {st['max']:.2f}{unit}  "
            f"(n={st['n']})")


def render(summary: dict) -> str:
    rows = [("run", summary["run_id"]),
            ("status", summary["status"])]
    for k in ("kind", "config", "backend", "devices", "jax"):
        if k in summary:
            rows.append((k, summary[k]))
    if "wall_s" in summary:
        rows.append(("wall clock", f"{summary['wall_s']:.1f} s"))
    rows.append(("steps", str(summary["steps"])))
    for field, unit in (("step_time_ms", " ms"), ("data_wait_ms", " ms"),
                        ("sync_ms", " ms"), ("examples_per_sec", "")):
        if field in summary:
            rows.append((field, _fmt_stat(summary[field], unit)))
    if "recompiles" in summary:
        rows.append(("recompiles", str(summary["recompiles"])))
    if "hbm_peak_gb" in summary:
        rows.append(("hbm peak", f"{summary['hbm_peak_gb']:.2f} GB"))
    if summary["checkpoints"]:
        rows.append(("checkpoints", str(summary["checkpoints"])))
    for e in summary["epochs"]:
        parts = " ".join(f"{k}={v:.4f}" for k, v in
                         (e.get("summary") or {}).items()
                         if isinstance(v, (int, float)))
        label = f"epoch {e.get('epoch')}"
        if e.get("name"):
            label += f" [{e['name']}]"
        rows.append((label, parts))
    for e in summary["evals"]:
        parts = " ".join(f"{k}={v:.4f}" for k, v in
                         (e.get("summary") or {}).items()
                         if isinstance(v, (int, float)))
        rows.append((f"eval e{e.get('epoch')}", parts))
    for e in summary["benches"]:
        res = e.get("result") or {}
        parts = " ".join(f"{k}={v}" for k, v in res.items()
                         if isinstance(v, (int, float)))
        rows.append((f"bench {e.get('name')}", parts))
    # serving summary (serve/router.py journal events): one row per
    # model, then the drain verdict — the SLO table without a live
    # registry endpoint
    serving = summary.get("serving")
    if serving:
        for name, r in serving["models"].items():
            parts = f"{r['ok']} ok, {r['error']} err"
            if r.get("rejected"):
                parts += f", {r['rejected']} rejected"
            if r.get("cancelled"):
                parts += f", {r['cancelled']} cancelled"
            if "p50_ms" in r:
                parts += (f"  latency p50 {r['p50_ms']:.2f}ms "
                          f"p95 {r['p95_ms']:.2f}ms "
                          f"p99 {r['p99_ms']:.2f}ms")
            if r.get("batches"):
                parts += f"  batches {r['batches']}"
            if "occupancy_pct" in r:
                parts += (f"  occupancy {r['occupancy_pct']:.1f}%"
                          f"  padding waste {r['padding_waste_pct']:.1f}%")
            rows.append((f"serving {name}", parts))
        # fleet view (serve/pool.py journals): per-replica ledgers, the
        # pool-level tail, shed-by-reason, and each swap's timeline —
        # the 3am "which replica / which swap / how much shed" answers
        fleet = serving.get("fleet")
        if fleet:
            for rid, r in fleet.get("replicas", {}).items():
                parts = f"{r['ok']} ok, {r['error']} err"
                if r.get("cancelled"):
                    parts += f", {r['cancelled']} cancelled"
                if r.get("lost"):
                    parts += (f"  lost x{r['lost']}"
                              f" recovered x{r['recovered']}")
                rows.append((f"replica {rid}", parts))
            pl = fleet.get("pool_latency")
            if pl:
                rows.append(("pool latency",
                             f"p50 {pl['p50_ms']:.2f}ms "
                             f"p95 {pl['p95_ms']:.2f}ms "
                             f"p99 {pl['p99_ms']:.2f}ms "
                             f"(n={pl['n']} admitted ok)"))
            for model, by_reason in fleet.get("shed", {}).items():
                total = sum(by_reason.values())
                detail = " ".join(f"{k}x{n}"
                                  for k, n in sorted(by_reason.items()))
                rows.append((f"shed {model}", f"{total} ({detail})"))
            for i, timeline in enumerate(fleet.get("swaps", []), 1):
                steps = []
                verdict = ""
                for t in timeline:
                    if t.get("outcome") == "started":
                        continue  # the terminal outcome per phase tells it
                    steps.append(f"{t.get('phase')} {t.get('outcome')}")
                    if t.get("phase") == "canary" and "canary_ok" in t:
                        verdict = (f"  [canary {t['canary_ok']} ok, "
                                   f"{t.get('canary_err', 0)} err"
                                   + (f", p99 {t['p99_ms']:.1f}ms"
                                      if isinstance(t.get("p99_ms"),
                                                    (int, float)) else "")
                                   + "]")
                    if t.get("reason"):
                        steps[-1] += f" ({t['reason']})"
                rows.append((f"swap #{i}", " -> ".join(steps) + verdict))
        drain = serving.get("drain")
        if drain:
            parts = (f"accepted={drain.get('accepted')} "
                     f"completed={drain.get('completed')} "
                     f"errors={drain.get('errors')}")
            if drain.get("cancelled"):
                parts += f" cancelled={drain['cancelled']}"
            if drain.get("shed"):
                parts += f" shed={drain['shed']}"
            if drain.get("offered"):
                parts += f" offered={drain['offered']}"
            rows.append(("serve drain",
                         f"{drain.get('reason')} -> {drain.get('outcome')} "
                         f"({parts} pending={drain.get('pending')})"))
    # the fleet edge (serve/transport.py): what the WIRE saw — the
    # status-code ledger, where deadlines died, and the socket tail
    fleet_edge = summary.get("fleet_edge")
    if fleet_edge:
        req = fleet_edge.get("requests")
        if req:
            codes = " ".join(f"{k}x{v}"
                             for k, v in req["by_status"].items())
            rows.append(("fleet edge",
                         f"{req['offered']} request(s) over the wire "
                         f"[{codes}]"
                         + ("" if req.get("balanced")
                            else "  LEDGER IMBALANCED")))
            oc = req.get("outcomes", {})
            shedlike = {k: v for k, v in oc.items()
                        if k in ("shed", "deadline", "torn", "bad_request")
                        and v}
            if shedlike:
                rows.append(("  edge outcomes",
                             " ".join(f"{k}={v}"
                                      for k, v in sorted(
                                          shedlike.items()))))
        stages = fleet_edge.get("deadline_stages")
        if stages:
            rows.append(("  deadline shed",
                         " ".join(f"{k}={v}" for k, v in
                                  sorted(stages.items()))
                         + "  (admission = never queued; dispatch = "
                         "queued but expired before its batch)"))
        lat = fleet_edge.get("latency")
        if lat:
            rows.append(("  edge latency",
                         f"p50 {lat['p50_ms']:.1f}ms  "
                         f"p99 {lat['p99_ms']:.1f}ms  (n={lat['n']})"))
        for ep, r in sorted(fleet_edge.get("servers", {}).items()):
            life = f"started x{r['started']}, stopped x{r['stopped']}"
            if r.get("failed"):
                life += f", FAILED x{r['failed']}"
            rows.append((f"  endpoint {ep}", life))
    # data plane (data/snapshot.py + data/service.py): service
    # throughput/reconnects, worker death history, and the resume
    # verdict — whether the input stream continued where the model did
    data_plane = summary.get("data_plane")
    if data_plane:
        for role, r in sorted(data_plane.get("service", {}).items()):
            parts = f"{r['batches']} batches"
            if role == "client" and r.get("reconnects"):
                parts += f", {r['reconnects']} reconnect(s)"
            if role == "server" and (r.get("workers_lost")
                                     or r.get("workers_recovered")):
                parts += (f", workers lost x{r['workers_lost']}"
                          f" recovered x{r['workers_recovered']}")
            rows.append((f"data service [{role}]", parts))
        w = data_plane.get("workers")
        if w and "service" not in data_plane:
            rows.append(("data workers",
                         f"lost x{w['lost']} recovered x{w['recovered']}"))
        for e in data_plane.get("resumes", []):
            detail = (f"epoch {e.get('epoch')} batch {e.get('batches')}"
                      if e.get("verdict") == "restored" else "from scratch")
            if e.get("shard"):
                detail += f" (shard {os.path.basename(str(e['shard']))})"
            rows.append(("data resume", f"{e.get('verdict')} ({detail})"))
    # host-membership timeline (resilience/rendezvous.py): which hosts
    # died at which generation (and how stale their lease was), each
    # world resize with its resume step, and the input-pipeline reshards
    # that followed — the 3am "why is this run suddenly world 2" answer
    membership = summary.get("membership")
    if membership:
        for e in membership.get("generations", []):
            detail = (f"world {e.get('from', '?')} -> {e.get('to', '?')}"
                      f" at generation {e.get('generation', '?')}")
            rs = e.get("resume_step")
            if isinstance(rs, int) and rs >= 0:
                detail += f", resume step {rs}"
            elif rs is not None:
                detail += ", no checkpoint to resume"
            rows.append(("membership", detail))
        for e in membership.get("lost", []):
            detail = f"at generation {e.get('generation', '?')}"
            if isinstance(e.get("lease_gap_s"), (int, float)):
                detail += f" (lease gap {e['lease_gap_s']:.1f}s)"
            rows.append((f"  host_lost {e.get('host', '?')}", detail))
        for e in membership.get("joined", []):
            rows.append((f"  host_joined {e.get('host', '?')}",
                         f"at generation {e.get('generation', '?')}"))
        for e in membership.get("reshards", []):
            rows.append(("  data_reshard",
                         f"hosts {e.get('from', '?')} -> {e.get('to', '?')}"
                         f", this host now shard "
                         f"{e.get('shard_index', '?')}/"
                         f"{e.get('num_shards', '?')}"))
    # cold path (core/excache.py + serve/quantize.py): cache hit/miss/
    # store accounting with refused entries by reason, and each int8
    # calibration verdict — the "did this restart pay the compiler"
    # and "is the int8 engine inside its gate" answers
    cold = summary.get("cold_path")
    if cold:
        parts = (f"{cold['hits']} hit, {cold['misses']} miss, "
                 f"{cold['stores']} stored")
        if cold["invalid"]:
            reasons = ", ".join(f"{n} {r}" for r, n in
                                sorted(cold["invalid_reasons"].items()))
            parts += f", {cold['invalid']} refused ({reasons})"
        rows.append(("executable cache", parts))
        for q in cold.get("quant", []):
            verdict = "accepted" if q["accepted"] else "REFUSED"
            detail = f"{q['metric']} delta {q['delta']}"
            if q.get("tolerance") is not None:
                detail += f" (tolerance {q['tolerance']})"
            rows.append((f"  int8 {q['model']}", f"{verdict}: {detail}"))
    # declarative sharding (parallel/shardmap.py sharding_resolved + the
    # scaling rows of tools/shard_smoke.py): which table resolved, how
    # many leaves each rule claimed, what actually sharded, and the
    # scaling-efficiency curve — the "is the parallelism real and what
    # does it buy" answers
    sharding = summary.get("sharding")
    if sharding:
        for t in sharding.get("tables", []):
            mesh = t.get("mesh") or {}
            mesh_s = ",".join(f"{k}={v}" for k, v in mesh.items())
            parts = (f"{t.get('sharded_leaves', '?')} sharded / "
                     f"{t.get('replicated', '?')} replicated of "
                     f"{t.get('float_leaves', '?')} float leaves "
                     f"(mesh {mesh_s})")
            if t.get("unmatched"):
                parts += f"  {t['unmatched']} catch-all-only"
            if t.get("dropped_dims"):
                parts += f"  {t['dropped_dims']} dims dropped"
            rows.append((f"sharding {t.get('model', '?')}", parts))
            for pat, n in t.get("top_rules", []):
                rows.append(("  rule", f"{pat} -> {n} leaves"))
            for p in t.get("unmatched_paths", []):
                rows.append(("  catch-all", p))
        for r in sharding.get("scaling", []):
            rows.append((f"scaling data={r.get('data')}",
                         f"{r.get('examples_per_sec')} ex/s  "
                         f"{r.get('per_device_examples_per_sec')} /device  "
                         f"efficiency {r.get('efficiency')}"))
    # performance attribution (obs/perfwatch.py + tools/perf_gate.py):
    # what each compiled jit pair costs (XLA cost analysis), which
    # collectives the partitioner gave it, and any gate breach with the
    # baseline it broke — the "why is this PR slower" paper trail
    perf = summary.get("perf")
    if perf:
        for pr in perf.get("pairs", []):
            parts = []
            if pr.get("flops") is not None:
                parts.append(f"flops {pr['flops']:.3g}")
            if pr.get("bytes_accessed") is not None:
                parts.append(f"bytes {pr['bytes_accessed']:.3g}")
            parts.append(f"collectives {pr.get('collective_count', 0)}"
                         f" ({pr.get('collective_bytes', 0)} B)")
            rows.append((f"perf {pr.get('name', '?')}", "  ".join(parts)))
            for c in pr.get("collectives", []):
                detail = (f"{c.get('kind')} {c.get('dtype')} "
                          f"x{c.get('ops')}  {c.get('bytes')} B")
                if c.get("group_size"):
                    detail += f"  group {c['group_size']}"
                rows.append(("  collective", detail))
        for r in perf.get("regressions", []):
            rows.append(("PERF REGRESSION",
                         f"{r.get('metric')}: observed {r.get('observed')}"
                         f" vs baseline {r.get('baseline')} "
                         f"(threshold {r.get('threshold')}, "
                         f"{r.get('direction', '?')} is better)"))
    # goodput attribution (obs/goodput.py): where every wall-clock
    # second went — the "where did the time go" table, with the
    # accounting-leak flag when buckets fail to cover the wall clock
    goodput = summary.get("goodput")
    if goodput:
        head = (f"{goodput['goodput_frac'] * 100:.1f}% productive over "
                f"{goodput['wall_s']:.1f} s wall")
        if goodput.get("source") == "intervals":
            head += "  (no terminal summary; accumulated from intervals)"
        if goodput.get("imbalance_frac", 0.0) > 0.02:
            head += (f"  ACCOUNTING LEAK "
                     f"{goodput['imbalance_frac'] * 100:.1f}%")
        rows.append(("goodput", head))
        wall = goodput.get("wall_s") or 0.0
        for name, secs in sorted(goodput.get("buckets", {}).items(),
                                 key=lambda kv: -kv[1]):
            if secs <= 0:
                continue
            pct = f" ({secs / wall * 100:.1f}%)" if wall > 0 else ""
            rows.append((f"  {name}", f"{secs:.2f} s{pct}"))
    # burn-rate alert timeline (obs/alerts.py): each fired episode with
    # its resolution — the pager history, replayable offline from the
    # same journal the live engine consumed
    alerts = summary.get("alerts")
    if alerts:
        head = f"{len(alerts['episodes'])} episode(s)"
        if alerts.get("still_firing"):
            head += f", {alerts['still_firing']} STILL FIRING"
        rows.append(("alerts", head))
        for a in alerts["episodes"]:
            detail = f"[{a.get('severity', '?')}]"
            if isinstance(a.get("value"), (int, float)) and \
                    isinstance(a.get("threshold"), (int, float)):
                detail += (f" value {a['value']:.4g} > "
                           f"threshold {a['threshold']:.4g}")
            if "resolved_ts" in a:
                detail += (f", resolved after {a.get('dur_s', 0.0):.1f} s"
                           if isinstance(a.get("dur_s"), (int, float))
                           else ", resolved")
            else:
                detail += ", still firing at journal end"
            rows.append((f"  {a.get('rule', '?')}", detail))
    # profiler captures: every decision the autoprof policy made, so the
    # table answers "why does this run have three trace dirs" directly
    for e in summary.get("captures", []):
        detail = f"step {e.get('step', '?')}"
        if e.get("z") is not None:
            detail += f" z={e['z']}"
        if e.get("outcome") in ("captured", "started") and e.get("dir"):
            detail += f" -> {e['dir']}"
        rows.append((f"capture {e.get('reason', '?')}",
                     f"{e.get('outcome', '?')} ({detail})"))
    for e in summary.get("flight_dumps", []):
        rows.append((f"flight {e.get('reason', '?')}",
                     f"{e.get('outcome', '?')} -> {e.get('dir', '?')}"))
    # lock health (obs/locksmith.py events): the one-line answer to "did
    # the serving plane's locking behave" — order violations are bugs,
    # contention rows are the tuning signal (which lock, how long)
    violations = summary.get("lock_violations", [])
    contention = summary.get("lock_contention", [])
    if violations or contention:
        by_lock: Dict[str, List[float]] = {}
        for e in contention:
            if isinstance(e.get("ms"), (int, float)):
                by_lock.setdefault(str(e.get("lock", "?")), []).append(
                    float(e["ms"]))
        parts = f"{len(violations)} order violation(s)"
        if by_lock:
            top = max(by_lock.items(), key=lambda kv: len(kv[1]))
            holds = [float(e["ms"]) for e in contention
                     if e.get("kind") == "hold"
                     and isinstance(e.get("ms"), (int, float))]
            parts += (f"; top contended {top[0]} ({len(top[1])}x, "
                      f"worst {max(top[1]):.1f} ms)")
            if holds:
                parts += f"; max hold {max(holds):.1f} ms"
        rows.append(("lock health", parts))
        for e in violations[:4]:
            rows.append(("  inversion",
                         f"{e.get('lock_a')} -> {e.get('lock_b')} on "
                         f"{e.get('thread', '?')} (reverse order seen on "
                         f"{e.get('prior_thread', '?')})"))
        if len(violations) > 4:
            rows.append(("  ...", f"{len(violations) - 4} more inversions"))
    # health findings: one row per event, aggregated counts first so a
    # 10k-spike run stays readable (only the first few render verbatim)
    health = summary.get("health", [])
    if health:
        by_kind: Dict[str, int] = {}
        for e in health:
            by_kind[e.get("kind", "?")] = by_kind.get(e.get("kind", "?"), 0) + 1
        rows.append(("health", " ".join(
            f"{k}x{n}" for k, n in sorted(by_kind.items()))))
        for e in health[:8]:
            kind = e.get("kind", "?")
            where = (f"step {e['step']}" if "step" in e
                     else f"epoch {e['epoch']}" if "epoch" in e else "")
            detail = ""
            if kind == "non_finite":
                detail = "fields=" + ",".join(e.get("fields", []))
            elif kind in ("loss_spike", "divergence"):
                detail = (f"loss={e.get('loss', 0):.4g} "
                          f"z={e.get('z', 0):.1f} "
                          f"streak={e.get('streak', '?')}")
            elif kind == "hang":
                detail = (f"stalled {e.get('stalled_s', '?')}s "
                          f"(deadline {e.get('timeout_s', '?')}s), "
                          f"{len(e.get('stacks', {}))} thread stacks dumped")
            rows.append((f"  {kind}", f"{where} {detail}".strip()))
        if len(health) > 8:
            rows.append(("  ...", f"{len(health) - 8} more health events"))
    width = max(len(k) for k, _ in rows)
    lines = ["=" * (width + 46)]
    lines += [f"{k:<{width}}  {v}" for k, v in rows]
    lines.append("=" * (width + 46))
    return "\n".join(lines)


def summarize_trace(path: str) -> List[dict]:
    """Per-span-name aggregate over a Chrome trace (obs/trace.py output):
    count, total/mean/p50/p95/max duration ms, sorted by total descending.
    The tail quantiles are what make a capture window or a straggler gap
    quantifiable from the CLI — a mean hides exactly the steps that
    triggered the capture."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    durs: Dict[str, List[float]] = {}
    for e in events:
        if e.get("ph") != "X":
            continue  # metadata / instant events carry no duration
        durs.setdefault(e.get("name", "?"), []).append(
            float(e.get("dur", 0.0)) / 1e3)
    out = []
    for name, ds in durs.items():
        out.append({
            "name": name,
            "count": len(ds),
            "total_ms": sum(ds),
            "mean_ms": sum(ds) / len(ds),
            "p50_ms": _percentile(ds, 0.5),
            "p95_ms": _percentile(ds, 0.95),
            "max_ms": max(ds),
        })
    return sorted(out, key=lambda a: -a["total_ms"])


def render_trace(spans: List[dict], path: str) -> str:
    if not spans:
        return f"trace {path}: no complete spans"
    w = max(len(s["name"]) for s in spans)
    lines = [f"-- span time summary: {path} --",
             f"{'span':<{w}}  {'count':>6}  {'total ms':>10}  "
             f"{'mean ms':>9}  {'p50 ms':>9}  {'p95 ms':>9}  {'max ms':>9}"]
    for s in spans:
        lines.append(f"{s['name']:<{w}}  {s['count']:>6}  "
                     f"{s['total_ms']:>10.1f}  {s['mean_ms']:>9.2f}  "
                     f"{s['p50_ms']:>9.2f}  {s['p95_ms']:>9.2f}  "
                     f"{s['max_ms']:>9.1f}")
    return "\n".join(lines)


_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(vals: List[float]) -> str:
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK[0] * len(vals)
    return "".join(_SPARK[int((v - lo) / (hi - lo) * (len(_SPARK) - 1))]
                   for v in vals)


def render_ledger(path: str, *, window: int = 16) -> str:
    """The perf-trajectory table over a tools/perf_gate.py ledger: one
    row per (metric, env fingerprint) with the last value, a sparkline
    of the last `window` runs, and the most recent gate verdict — the
    "is this metric drifting" answer without opening the JSONL."""
    from tools.perf_gate import PerfLedger

    rows = PerfLedger(path).read()
    if not rows:
        return f"perf ledger {path}: empty"
    series: Dict[tuple, List[dict]] = {}
    for r in rows:
        if isinstance(r.get("value"), (int, float)):
            series.setdefault(
                (str(r.get("metric", "?")), str(r.get("env_key", ""))),
                []).append(r)
    lines = [f"-- perf trajectory: {path} ({len(rows)} runs, "
             f"{len(series)} series) --"]
    w = max(len(m) for m, _ in series) if series else 6
    for (metric, _key), rs in sorted(series.items()):
        tail = rs[-int(window):]
        vals = [float(r["value"]) for r in tail]
        last = tail[-1]
        unit = last.get("unit") or ""
        verdict = last.get("verdict", "?")
        line = (f"{metric:<{w}}  {_sparkline(vals)}  "
                f"last {vals[-1]:.4g}{(' ' + unit) if unit else ''}  "
                f"[{verdict}]  (n={len(rs)})")
        lines.append(line)
    return "\n".join(lines)


def render_merged(events: List[dict]) -> str:
    """Render an obs_merge timeline: per-host step statistics side by
    side, then every detected straggler — the cross-host view a single
    journal cannot show."""
    hosts: Dict[int, List[dict]] = {}
    stragglers = []
    header = None
    for e in events:
        if e.get("event") == "note" and e.get("note") == "obs_merge":
            header = e
        elif e.get("event") == "straggler":
            stragglers.append(e)
        elif isinstance(e.get("host"), int):
            # integer hosts are obs_merge host INDICES; telemetry_server
            # events carry a bind address string in the same field and
            # belong to no host lane
            hosts.setdefault(int(e["host"]), []).append(e)
    lines = ["== merged multi-host timeline =="]
    if header:
        lines.append(f"hosts {header.get('hosts')}  "
                     f"sources {len(header.get('sources', []))}  "
                     f"stragglers {header.get('stragglers', 0)}")
    for h in sorted(hosts):
        evs = hosts[h]
        steps = [e for e in evs if e.get("event") == "step"]
        st = _stats([float(e["step_time_ms"]) for e in steps
                     if "step_time_ms" in e])
        terminal = next((e for e in reversed(evs)
                         if e.get("event") in ("exit", "crash")), None)
        status = ("no terminal event" if terminal is None
                  else terminal["event"])
        line = f"host {h}: {len(steps)} steps, {status}"
        if st:
            line += ("  step_time " + _fmt_stat(st, " ms"))
        lines.append(line)
    if stragglers:
        lines.append(f"-- stragglers ({len(stragglers)}) --")
        for e in stragglers[:16]:
            lines.append(
                f"step {e.get('step'):>6}  host {e.get('host')}  "
                f"gap {e.get('gap_ms'):.1f} ms  "
                f"(max {e.get('max_ms'):.1f} vs median "
                f"{e.get('median_ms'):.1f} over {e.get('hosts')} hosts)")
        if len(stragglers) > 16:
            lines.append(f"... {len(stragglers) - 16} more")
    else:
        lines.append("no stragglers detected")
    # cross-PROCESS request timelines (obs/merge.py trace_timelines):
    # one request's hops — stamped by obs/propagate.py trace context —
    # stitched across journals into a single causal sequence
    from deep_vision_tpu.obs.merge import trace_timelines

    timelines = trace_timelines(events)
    if timelines:
        lines.append(f"-- request timelines ({len(timelines)}) --")
        for tl in timelines[:8]:
            lines.append(
                f"trace {tl['trace_id']}  {len(tl['hops'])} hop(s), "
                f"{tl['spans']} span(s) across "
                f"{len(tl['processes'])} process(es)  "
                f"{tl['duration_ms']:.1f} ms")
            t0 = tl["hops"][0].get("ts") or 0.0
            for hop in tl["hops"][:12]:
                bits = [hop.get("event", "?")]
                for k in ("role", "service", "model", "outcome", "note"):
                    if hop.get(k) is not None:
                        bits.append(f"{k}={hop[k]}")
                if hop.get("run_id"):
                    bits.append(f"run {hop['run_id']}")
                dt = ((hop.get("ts") or t0) - t0) * 1e3
                lines.append(f"  +{dt:8.1f} ms  " + "  ".join(bits))
            if len(tl["hops"]) > 12:
                lines.append(f"  ... {len(tl['hops']) - 12} more hops")
        if len(timelines) > 8:
            lines.append(f"... {len(timelines) - 8} more traces")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("journals", nargs="+", help="journal JSONL path(s)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="also render a per-span time summary of this "
                        "Chrome trace JSON (train.py --trace output)")
    p.add_argument("--merged", action="store_true",
                   help="the input is a tools/obs_merge.py merged "
                        "multi-host timeline: render per-host step "
                        "statistics and the detected stragglers")
    p.add_argument("--ledger", default=None, metavar="PATH",
                   help="also render the perf-trajectory table of this "
                        "tools/perf_gate.py ledger (sparkline per "
                        "metric, last gate verdict)")
    p.add_argument("--digest", default=None, metavar="PATH",
                   help="also render a step-time decomposition of this "
                        "profiler capture dir (tools/trace_digest.py)")
    args = p.parse_args(argv)

    if args.merged:
        events: List[dict] = []
        for path in args.journals:
            events.extend(read_journal(path))
        if not events:
            print("no events found", file=sys.stderr)
            return 1
        print(render_merged(events))
        _render_extras(args)
        return 0

    by_run: Dict[str, List[dict]] = {}
    for path in args.journals:
        for e in read_journal(path):
            by_run.setdefault(e.get("run_id", path), []).append(e)
    if not by_run:
        print("no events found", file=sys.stderr)
        return 1
    for run_id, events in by_run.items():
        print(render(summarize_run(events)))
    _render_extras(args)
    return 0


def _render_extras(args) -> None:
    if args.trace:
        print(render_trace(summarize_trace(args.trace), args.trace))
    if args.ledger:
        print(render_ledger(args.ledger))
    if args.digest:
        from tools.trace_digest import digest, render_digest

        print(render_digest(digest(args.digest)))


if __name__ == "__main__":
    raise SystemExit(main())
