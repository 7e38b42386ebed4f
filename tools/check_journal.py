"""Validate journal JSONL + Chrome trace JSON against the obs/ schemas.

    PYTHONPATH=. python tools/check_journal.py run.jsonl [run2.jsonl ...]
        [--trace trace.json] [--require-exit] [--strict]

The CI teeth behind obs/README.md: every event line must parse, carry
the `event`/`ts`/`run_id` envelope, and (for known event types) carry
that type's required fields. Unknown event types are tolerated by
default — a journal written by a newer producer must stay validatable
by an older checker — while `--strict` makes them violations AND
demands a clean `exit` terminal event (what `make obs-smoke` asserts
after its tiny train run: a smoke run that crashed, or that emitted an
event this schema has never heard of, is a failure even if every line
it did write was well-formed). `--require-exit` demands only the
terminal event. Trace files must be valid JSON in Trace Event Format:
a `traceEvents` list whose complete events ("ph": "X") carry
name/ts/dur/pid/tid.

Exit status 0 = all files valid; 2 = any file invalid (each violation
printed with its file:line); 64 = usage error.
"""
from __future__ import annotations

import json
import os
import re
import sys
from typing import List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deep_vision_tpu.cli import (  # noqa: E402
    EXIT_INVALID,
    EXIT_OK,
    EXIT_USAGE,
    UsageErrorParser,
)

__all__ = ["check_journal", "check_trace", "main",
           "EXIT_OK", "EXIT_INVALID", "EXIT_USAGE"]

# envelope fields on every line, then per-event required fields
ENVELOPE = ("event", "ts", "run_id")
EVENT_FIELDS = {
    "run_manifest": ("kind", "argv"),
    "step": ("step",),
    "epoch": ("epoch", "summary"),
    "eval": ("epoch", "summary"),
    "checkpoint": ("step", "saved"),
    "health": ("kind",),
    "profile": ("action",),
    "bench": ("name", "result"),
    "retry": ("name", "attempt", "error", "outcome"),
    "fault": ("point", "kind"),
    "data_skip": ("path", "offset", "reason"),
    "ckpt_quarantine": ("step", "reason"),
    "backend_lost": ("attempt", "error", "kind"),
    "backend_recovered": ("attempt",),
    "preempt_checkpoint": ("step", "saved"),
    "profile_capture": ("reason", "outcome"),
    "flight_dump": ("reason", "dir", "outcome"),
    "straggler": ("step", "gap_ms", "host"),
    "setup": ("build_trainer_s", "split_s"),
    "stall": ("step", "step_time_ms", "median_ms", "cause", "split_ms"),
    "serve_request": ("model", "latency_ms", "outcome"),
    "serve_batch": ("model", "bucket", "size"),
    "serve_drain": ("reason", "outcome", "accepted", "completed"),
    "serve_shed": ("model", "reason"),
    "serve_swap": ("phase", "outcome"),
    "replica_lost": ("replica", "attempt"),
    "replica_recovered": ("replica", "attempt"),
    "lock_order_violation": ("lock_a", "lock_b", "thread"),
    "lock_contention": ("lock", "kind", "ms"),
    "data_resume": ("verdict", "epoch", "batches"),
    "data_worker_lost": ("worker", "attempt"),
    "data_worker_recovered": ("worker", "attempt"),
    "data_service": ("role", "batches"),
    "excache_hit": ("key",),
    "excache_miss": ("key",),
    "excache_store": ("key",),
    "excache_invalid": ("key", "reason"),
    "quant_calibrated": ("model", "delta", "accepted"),
    "sharding_resolved": ("model", "matched", "unmatched",
                          "sharded_leaves", "mesh"),
    "host_lost": ("host", "generation"),
    "host_joined": ("host", "generation"),
    "world_resized": ("from", "to", "generation", "resume_step"),
    "data_reshard": ("generation", "from", "to"),
    "note": (),
    "exit": ("status",),
    "crash": ("reason",),
    "telemetry_server": ("host", "port", "outcome"),
    "transport_request": ("status", "deadline_ms", "outcome"),
    "transport_server": ("host", "port", "outcome"),
    "perf_profile": ("name", "collective_count", "collective_bytes"),
    "perf_collective": ("name", "kind", "dtype", "ops", "bytes"),
    "perf_regression": ("metric", "baseline", "observed", "threshold"),
    "goodput_interval": ("dur_s", "buckets"),
    "goodput_summary": ("wall_s", "buckets", "goodput_frac",
                        "imbalance_frac"),
    "alert_fired": ("rule", "severity", "value", "threshold"),
    "alert_resolved": ("rule", "severity", "dur_s"),
}
HEALTH_KINDS = {"non_finite", "loss_spike", "divergence", "hang",
                "watchdog_started"}
RETRY_OUTCOMES = {"retrying", "gave_up", "recovered"}
PROFILE_CAPTURE_REASONS = {"static_window", "step_time_z", "data_wait_z",
                           "recompile_burst", "hbm_jump", "manual"}
PROFILE_CAPTURE_OUTCOMES = {"started", "captured", "closed_early",
                            "skipped_cooldown", "skipped_budget",
                            "skipped_inflight", "failed"}
FLIGHT_REASONS = {"crash", "hang", "health_abort", "preempt",
                  "injected_crash", "injected_crash_after_write", "manual"}
FLIGHT_OUTCOMES = {"written", "failed"}
SERVE_REQUEST_OUTCOMES = {"ok", "error", "rejected", "cancelled"}
SERVE_DRAIN_REASONS = {"close", "sigterm"}
SERVE_DRAIN_OUTCOMES = {"flushed", "timeout"}
# serve/slo.py SHED_REASONS and serve/swap.py SWAP_PHASES/SWAP_OUTCOMES
# (kept in sync by tests/test_serve_pool.py)
SERVE_SHED_REASONS = {"queue_full", "rate_limited", "draining"}
SERVE_SWAP_PHASES = {"warm", "canary", "promote", "rollback"}
SERVE_SWAP_OUTCOMES = {"started", "ok", "failed"}
LOCK_CONTENTION_KINDS = {"hold", "wait"}
# resilience/elastic.py BACKEND_LOST_KINDS (kept in sync by
# tests/test_elastic.py): the classifier's verdict on a lost backend
BACKEND_LOST_KINDS = {"connection_lost", "timeout", "version_skew",
                      "unknown"}
# data plane (data/snapshot.py + data/service.py; kept in sync by
# tests/test_data_service.py): 'restored' = the loader replays its exact
# checkpointed position, 'fresh' = the checkpoint carried no loader state
DATA_RESUME_VERDICTS = {"restored", "fresh"}
DATA_SERVICE_ROLES = {"server", "client"}
# cold path (core/excache.py EXCACHE_INVALID_REASONS, kept in sync by
# tests/test_excache.py): why a present cache entry was refused
EXCACHE_INVALID_REASONS = {"version_skew", "topology_skew", "corrupt",
                           "deserialize_failed"}
# live telemetry plane (obs/telemetry.py TELEMETRY_OUTCOMES, kept in
# sync by tests/test_telemetry.py)
TELEMETRY_SERVER_OUTCOMES = {"started", "stopped", "failed"}
# serve/transport.py TRANSPORT_OUTCOMES / TRANSPORT_SERVER_OUTCOMES
# (kept in sync by tests/test_transport.py): the front door's per-request
# verdicts and the endpoint's lifecycle
TRANSPORT_OUTCOMES = {"ok", "error", "shed", "deadline", "bad_request",
                      "torn"}
TRANSPORT_SERVER_OUTCOMES = {"started", "stopped", "failed"}
# perf attribution plane (obs/costmodel.py COLLECTIVE_KINDS, kept in
# sync by tests/test_perfwatch.py): the HLO collective opcodes the
# inventory parser recognizes
PERF_COLLECTIVE_KINDS = {"all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute"}
# goodput plane (obs/goodput.py GOODPUT_BUCKETS, kept in sync by
# tests/test_goodput.py): every wall-clock second of a run lands in
# exactly one of these
GOODPUT_BUCKETS = {"productive_step", "data_wait", "compile", "checkpoint",
                   "host_loss_recovery", "replica_respawn",
                   "rendezvous_wait", "drain", "overhead"}
# burn-rate alerting (obs/alerts.py ALERT_SEVERITIES, kept in sync by
# tests/test_alerts.py)
ALERT_SEVERITIES = {"page", "ticket"}
# cross-process trace context (obs/propagate.py): W3C-traceparent-shaped
# ids stamped onto journal events written under an installed context —
# any event may carry them, so the hex-shape check applies everywhere
TRACE_ID_RE = re.compile(r"^[0-9a-f]{32}$")
SPAN_ID_RE = re.compile(r"^[0-9a-f]{16}$")


def check_journal(path: str, require_exit: bool = False,
                  strict: bool = False) -> List[str]:
    """Returns a list of violations ('' prefix stripped); empty = valid.

    strict: unknown event types become violations (default: tolerated for
    forward compatibility) and a clean terminal `exit` event is required.
    """
    require_exit = require_exit or strict
    errors: List[str] = []
    events: List[dict] = []
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            # only the FINAL line may be torn (crash mid-write); anywhere
            # else it is corruption
            if i == len(lines):
                errors.append(f"{path}:{i}: torn final line (tolerated by "
                              "readers, but the run died mid-write)")
            else:
                errors.append(f"{path}:{i}: unparseable JSON")
            continue
        if not isinstance(row, dict):
            errors.append(f"{path}:{i}: not a JSON object")
            continue
        for k in ENVELOPE:
            if k not in row:
                errors.append(f"{path}:{i}: missing envelope field {k!r}")
        ev = row.get("event")
        if ev not in EVENT_FIELDS:
            if strict:
                errors.append(f"{path}:{i}: unknown event type {ev!r}")
            events.append(row)
            continue
        for k in EVENT_FIELDS[ev]:
            if k not in row:
                errors.append(f"{path}:{i}: {ev} event missing field {k!r}")
        if ev == "health":
            if row.get("kind") not in HEALTH_KINDS:
                errors.append(f"{path}:{i}: unknown health kind "
                              f"{row.get('kind')!r}")
            if row.get("kind") == "hang" and not row.get("stacks"):
                errors.append(f"{path}:{i}: hang event carries no thread "
                              "stacks")
        if ev == "retry" and row.get("outcome") not in RETRY_OUTCOMES:
            errors.append(f"{path}:{i}: unknown retry outcome "
                          f"{row.get('outcome')!r}")
        if ev == "profile_capture":
            if row.get("reason") not in PROFILE_CAPTURE_REASONS:
                errors.append(f"{path}:{i}: unknown profile_capture reason "
                              f"{row.get('reason')!r}")
            if row.get("outcome") not in PROFILE_CAPTURE_OUTCOMES:
                errors.append(f"{path}:{i}: unknown profile_capture outcome "
                              f"{row.get('outcome')!r}")
        if ev == "flight_dump":
            if row.get("reason") not in FLIGHT_REASONS:
                errors.append(f"{path}:{i}: unknown flight_dump reason "
                              f"{row.get('reason')!r}")
            if row.get("outcome") not in FLIGHT_OUTCOMES:
                errors.append(f"{path}:{i}: unknown flight_dump outcome "
                              f"{row.get('outcome')!r}")
        if ev == "serve_request" and \
                row.get("outcome") not in SERVE_REQUEST_OUTCOMES:
            errors.append(f"{path}:{i}: unknown serve_request outcome "
                          f"{row.get('outcome')!r}")
        if ev == "serve_batch":
            bucket, size = row.get("bucket"), row.get("size")
            if not isinstance(bucket, int) or not isinstance(size, int):
                errors.append(f"{path}:{i}: serve_batch bucket/size must "
                              f"be ints, got {bucket!r}/{size!r}")
            elif not 1 <= size <= bucket:
                errors.append(f"{path}:{i}: serve_batch size {size} "
                              f"outside [1, bucket={bucket}] — padding "
                              "arithmetic is broken")
        if ev == "serve_drain":
            if row.get("reason") not in SERVE_DRAIN_REASONS:
                errors.append(f"{path}:{i}: unknown serve_drain reason "
                              f"{row.get('reason')!r}")
            if row.get("outcome") not in SERVE_DRAIN_OUTCOMES:
                errors.append(f"{path}:{i}: unknown serve_drain outcome "
                              f"{row.get('outcome')!r}")
        if ev == "serve_shed" and row.get("reason") not in SERVE_SHED_REASONS:
            errors.append(f"{path}:{i}: unknown serve_shed reason "
                          f"{row.get('reason')!r}")
        if ev == "serve_swap":
            if row.get("phase") not in SERVE_SWAP_PHASES:
                errors.append(f"{path}:{i}: unknown serve_swap phase "
                              f"{row.get('phase')!r}")
            if row.get("outcome") not in SERVE_SWAP_OUTCOMES:
                errors.append(f"{path}:{i}: unknown serve_swap outcome "
                              f"{row.get('outcome')!r}")
        if ev in ("replica_lost", "replica_recovered"):
            if not isinstance(row.get("replica"), str) or not row.get("replica"):
                errors.append(f"{path}:{i}: {ev} replica must be a replica "
                              f"id, got {row.get('replica')!r}")
            if not isinstance(row.get("attempt"), int):
                errors.append(f"{path}:{i}: {ev} attempt must be an int, "
                              f"got {row.get('attempt')!r}")
        if ev == "lock_contention":
            if row.get("kind") not in LOCK_CONTENTION_KINDS:
                errors.append(f"{path}:{i}: unknown lock_contention kind "
                              f"{row.get('kind')!r}")
            if not isinstance(row.get("ms"), (int, float)):
                errors.append(f"{path}:{i}: lock_contention ms must be "
                              f"numeric, got {row.get('ms')!r}")
        if ev == "lock_order_violation":
            for k in ("lock_a", "lock_b"):
                if not isinstance(row.get(k), str) or not row.get(k):
                    errors.append(f"{path}:{i}: lock_order_violation {k} "
                                  f"must be a lock name, got "
                                  f"{row.get(k)!r}")
        if ev == "data_resume":
            if row.get("verdict") not in DATA_RESUME_VERDICTS:
                errors.append(f"{path}:{i}: unknown data_resume verdict "
                              f"{row.get('verdict')!r}")
            for k in ("epoch", "batches"):
                if not isinstance(row.get(k), int):
                    errors.append(f"{path}:{i}: data_resume {k} must be "
                                  f"an int, got {row.get(k)!r}")
        if ev in ("data_worker_lost", "data_worker_recovered"):
            for k in ("worker", "attempt"):
                if not isinstance(row.get(k), int):
                    errors.append(f"{path}:{i}: {ev} {k} must be an int, "
                                  f"got {row.get(k)!r}")
        if ev == "data_service":
            if row.get("role") not in DATA_SERVICE_ROLES:
                errors.append(f"{path}:{i}: unknown data_service role "
                              f"{row.get('role')!r}")
            if not isinstance(row.get("batches"), int):
                errors.append(f"{path}:{i}: data_service batches must be "
                              f"an int, got {row.get('batches')!r}")
        if ev in ("excache_hit", "excache_miss", "excache_store",
                  "excache_invalid"):
            if not isinstance(row.get("key"), str) or not row.get("key"):
                errors.append(f"{path}:{i}: {ev} key must be a cache key "
                              f"string, got {row.get('key')!r}")
            if ev == "excache_invalid" and \
                    row.get("reason") not in EXCACHE_INVALID_REASONS:
                errors.append(f"{path}:{i}: unknown excache_invalid reason "
                              f"{row.get('reason')!r}")
        if ev == "telemetry_server":
            if row.get("outcome") not in TELEMETRY_SERVER_OUTCOMES:
                errors.append(f"{path}:{i}: unknown telemetry_server "
                              f"outcome {row.get('outcome')!r}")
            if not isinstance(row.get("port"), int):
                errors.append(f"{path}:{i}: telemetry_server port must be "
                              f"an int, got {row.get('port')!r}")
        if ev == "transport_request":
            if row.get("outcome") not in TRANSPORT_OUTCOMES:
                errors.append(f"{path}:{i}: unknown transport_request "
                              f"outcome {row.get('outcome')!r}")
            # status 0 = no response ever hit the wire (a torn frame
            # closes the connection instead of answering)
            if not isinstance(row.get("status"), int) \
                    or row.get("status", -1) < 0:
                errors.append(f"{path}:{i}: transport_request status must "
                              f"be a non-negative int HTTP status, got "
                              f"{row.get('status')!r}")
            if not isinstance(row.get("deadline_ms"), (int, float)) \
                    or row.get("deadline_ms", -1) < 0:
                errors.append(f"{path}:{i}: transport_request deadline_ms "
                              f"must be non-negative (0 = none), got "
                              f"{row.get('deadline_ms')!r}")
        if ev == "transport_server":
            if row.get("outcome") not in TRANSPORT_SERVER_OUTCOMES:
                errors.append(f"{path}:{i}: unknown transport_server "
                              f"outcome {row.get('outcome')!r}")
            if not isinstance(row.get("port"), int):
                errors.append(f"{path}:{i}: transport_server port must be "
                              f"an int, got {row.get('port')!r}")
        if ev == "perf_profile":
            # compiled-artifact introspection (obs/perfwatch.py): name is
            # the jit pair, the collective roll-up must be consistent
            # (flops/bytes_accessed may be None where the backend hides
            # its cost analysis — absence of data, not a violation)
            if not isinstance(row.get("name"), str) or not row.get("name"):
                errors.append(f"{path}:{i}: perf_profile name must be a "
                              f"jit-pair name, got {row.get('name')!r}")
            for k in ("collective_count", "collective_bytes"):
                if not isinstance(row.get(k), int) or row.get(k, -1) < 0:
                    errors.append(f"{path}:{i}: perf_profile {k} must be "
                                  f"a non-negative int, got {row.get(k)!r}")
            for k in ("flops", "bytes_accessed"):
                if row.get(k) is not None and \
                        not isinstance(row.get(k), (int, float)):
                    errors.append(f"{path}:{i}: perf_profile {k} must be "
                                  f"numeric or null, got {row.get(k)!r}")
        if ev == "perf_collective":
            if row.get("kind") not in PERF_COLLECTIVE_KINDS:
                errors.append(f"{path}:{i}: unknown perf_collective kind "
                              f"{row.get('kind')!r}")
            if not isinstance(row.get("ops"), int) or row.get("ops", 0) < 1:
                errors.append(f"{path}:{i}: perf_collective ops must be a "
                              f"positive int, got {row.get('ops')!r}")
            if not isinstance(row.get("bytes"), int) or \
                    row.get("bytes", 0) <= 0:
                errors.append(f"{path}:{i}: perf_collective bytes must be "
                              f"positive, got {row.get('bytes')!r}")
        if ev == "perf_regression":
            # the gate's breach record (tools/perf_gate.py): all three
            # numbers must be present and numeric — a regression event
            # that can't say what it compared is not evidence
            if not isinstance(row.get("metric"), str) or \
                    not row.get("metric"):
                errors.append(f"{path}:{i}: perf_regression metric must "
                              f"be a metric name, got {row.get('metric')!r}")
            for k in ("baseline", "observed", "threshold"):
                if not isinstance(row.get(k), (int, float)):
                    errors.append(f"{path}:{i}: perf_regression {k} must "
                                  f"be numeric, got {row.get(k)!r}")
        if ev in ("goodput_interval", "goodput_summary"):
            # wall-clock attribution (obs/goodput.py): buckets is a
            # {bucket: seconds} mapping over the closed enum — a key
            # this checker has never heard of means the producer and
            # the offline tooling disagree about where time can go
            b = row.get("buckets")
            if not isinstance(b, dict) or not all(
                    k in GOODPUT_BUCKETS and
                    isinstance(v, (int, float)) and not isinstance(v, bool)
                    and v >= 0 for k, v in b.items()):
                errors.append(f"{path}:{i}: {ev} buckets must map known "
                              f"bucket names to non-negative seconds, got "
                              f"{b!r}")
            dur_key = "dur_s" if ev == "goodput_interval" else "wall_s"
            d = row.get(dur_key)
            if not isinstance(d, (int, float)) or isinstance(d, bool) \
                    or d < 0:
                errors.append(f"{path}:{i}: {ev} {dur_key} must be "
                              f"non-negative seconds, got {d!r}")
        if ev == "goodput_summary":
            for k in ("goodput_frac", "imbalance_frac"):
                v = row.get(k)
                if not isinstance(v, (int, float)) or isinstance(v, bool) \
                        or not 0.0 <= v <= 1.0:
                    errors.append(f"{path}:{i}: goodput_summary {k} must "
                                  f"be a fraction in [0, 1], got {v!r}")
        if ev in ("alert_fired", "alert_resolved"):
            if not isinstance(row.get("rule"), str) or not row.get("rule"):
                errors.append(f"{path}:{i}: {ev} rule must be a rule name, "
                              f"got {row.get('rule')!r}")
            if row.get("severity") not in ALERT_SEVERITIES:
                errors.append(f"{path}:{i}: unknown {ev} severity "
                              f"{row.get('severity')!r}")
        if ev == "alert_fired":
            for k in ("value", "threshold"):
                if not isinstance(row.get(k), (int, float)) or \
                        isinstance(row.get(k), bool):
                    errors.append(f"{path}:{i}: alert_fired {k} must be "
                                  f"numeric, got {row.get(k)!r}")
        if ev == "alert_resolved" and (
                not isinstance(row.get("dur_s"), (int, float))
                or isinstance(row.get("dur_s"), bool)
                or row.get("dur_s", -1) < 0):
            errors.append(f"{path}:{i}: alert_resolved dur_s must be "
                          f"non-negative seconds, got {row.get('dur_s')!r}")
        # trace context rides ANY event written under an installed
        # context (obs/journal.py stamps it); when present the ids must
        # be W3C-shaped or obs/merge.py's timelines silently fragment
        if "trace_id" in row or "span_id" in row:
            tid, sid = row.get("trace_id"), row.get("span_id")
            if not (isinstance(tid, str) and TRACE_ID_RE.match(tid)):
                errors.append(f"{path}:{i}: trace_id must be 32 lowercase "
                              f"hex chars, got {tid!r}")
            if not (isinstance(sid, str) and SPAN_ID_RE.match(sid)):
                errors.append(f"{path}:{i}: span_id must be 16 lowercase "
                              f"hex chars, got {sid!r}")
            psid = row.get("parent_span_id")
            if psid is not None and not (isinstance(psid, str)
                                         and SPAN_ID_RE.match(psid)):
                errors.append(f"{path}:{i}: parent_span_id must be 16 "
                              f"lowercase hex chars, got {psid!r}")
        if ev == "quant_calibrated":
            if not isinstance(row.get("accepted"), bool):
                errors.append(f"{path}:{i}: quant_calibrated accepted must "
                              f"be a bool, got {row.get('accepted')!r}")
            if not isinstance(row.get("delta"), (int, float)):
                errors.append(f"{path}:{i}: quant_calibrated delta must be "
                              f"numeric, got {row.get('delta')!r}")
        if ev == "sharding_resolved":
            # declarative sharding resolution (parallel/shardmap.py):
            # model names the rules table, the three counts are the
            # coverage ledger, mesh is the {axis: size} it resolved on
            if not isinstance(row.get("model"), str) or not row.get("model"):
                errors.append(f"{path}:{i}: sharding_resolved model must "
                              f"be a table name, got {row.get('model')!r}")
            for k in ("matched", "unmatched", "sharded_leaves"):
                if not isinstance(row.get(k), int):
                    errors.append(f"{path}:{i}: sharding_resolved {k} "
                                  f"must be an int, got {row.get(k)!r}")
            m = row.get("mesh")
            if not isinstance(m, dict) or not m or not all(
                    isinstance(k, str) and isinstance(v, int)
                    for k, v in m.items()):
                errors.append(f"{path}:{i}: sharding_resolved mesh must "
                              "be a non-empty {axis: size} mapping, got "
                              f"{m!r}")
        if ev in ("host_lost", "host_joined"):
            # elastic membership events (resilience/rendezvous.py):
            # host is a member ID string, generation the rendezvous
            # generation the event happened at
            if not isinstance(row.get("host"), str) or not row.get("host"):
                errors.append(f"{path}:{i}: {ev} host must be a member id "
                              f"string, got {row.get('host')!r}")
            if not isinstance(row.get("generation"), int):
                errors.append(f"{path}:{i}: {ev} generation must be an "
                              f"int, got {row.get('generation')!r}")
        if ev == "world_resized":
            for k in ("from", "to", "generation", "resume_step"):
                if not isinstance(row.get(k), int):
                    errors.append(f"{path}:{i}: world_resized {k} must be "
                                  f"an int, got {row.get(k)!r}")
            frm, to = row.get("from"), row.get("to")
            # same-SIZE resizes are legal (one host lost + one joined in
            # the same generation); an empty new world is not
            if isinstance(to, int) and to < 1:
                errors.append(f"{path}:{i}: world_resized {frm} -> {to}: "
                              "the new world must have >= 1 host")
        if ev == "data_reshard":
            for k in ("generation", "from", "to"):
                if not isinstance(row.get(k), int):
                    errors.append(f"{path}:{i}: data_reshard {k} must be "
                                  f"an int, got {row.get(k)!r}")
        if ev == "backend_lost" and row.get("kind") not in BACKEND_LOST_KINDS:
            errors.append(f"{path}:{i}: unknown backend_lost kind "
                          f"{row.get('kind')!r}")
        if ev == "backend_recovered" and \
                not isinstance(row.get("attempt"), int):
            errors.append(f"{path}:{i}: backend_recovered attempt must be "
                          f"an int, got {row.get('attempt')!r}")
        if ev == "preempt_checkpoint":
            if not isinstance(row.get("saved"), bool):
                errors.append(f"{path}:{i}: preempt_checkpoint saved must "
                              f"be a bool, got {row.get('saved')!r}")
            if not isinstance(row.get("step"), int):
                errors.append(f"{path}:{i}: preempt_checkpoint step must "
                              f"be an int, got {row.get('step')!r}")
        if ev == "straggler":
            if not isinstance(row.get("host"), int):
                errors.append(f"{path}:{i}: straggler host must be a "
                              "process index (int), got "
                              f"{row.get('host')!r}")
            if not isinstance(row.get("gap_ms"), (int, float)):
                errors.append(f"{path}:{i}: straggler gap_ms must be "
                              f"numeric, got {row.get('gap_ms')!r}")
        events.append(row)
    if not events:
        errors.append(f"{path}: no events")
        return errors
    terminal = [e for e in events if e.get("event") in ("exit", "crash")]
    if require_exit:
        if not terminal:
            errors.append(f"{path}: no terminal event (run still alive or "
                          "SIGKILLed)")
        elif terminal[-1]["event"] != "exit":
            errors.append(f"{path}: terminal event is a crash marker: "
                          f"{terminal[-1].get('reason')!r}")
    return errors


def check_trace(path: str) -> List[str]:
    """Validate Trace Event Format structure; empty list = valid."""
    errors: List[str] = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: not valid JSON: {e}"]
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            return [f"{path}: object form must carry a traceEvents list"]
    elif isinstance(doc, list):
        events = doc
    else:
        return [f"{path}: trace must be a JSON array or object"]
    n_complete = 0
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            errors.append(f"{path}: event[{i}] is not an object")
            continue
        if "name" not in e or "ph" not in e:
            errors.append(f"{path}: event[{i}] missing name/ph")
            continue
        if e["ph"] == "X":
            n_complete += 1
            for k in ("ts", "dur", "pid", "tid"):
                if k not in e:
                    errors.append(
                        f"{path}: complete event[{i}] "
                        f"({e['name']!r}) missing {k!r}")
            if e.get("dur", 0) < 0:
                errors.append(f"{path}: event[{i}] negative duration")
    if n_complete == 0:
        errors.append(f"{path}: no complete ('X') span events")
    return errors


def main(argv=None) -> int:
    p = UsageErrorParser(description=__doc__.splitlines()[0])
    p.add_argument("journals", nargs="+", help="journal JSONL path(s)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="also validate this Chrome trace JSON")
    p.add_argument("--require-exit", action="store_true",
                   help="fail unless the journal ends in a clean exit "
                        "event (the obs-smoke gate)")
    p.add_argument("--strict", action="store_true",
                   help="unknown event types are violations too, and a "
                        "clean exit marker is required")
    args = p.parse_args(argv)

    errors: List[str] = []
    for path in args.journals:
        errs = check_journal(path, require_exit=args.require_exit,
                             strict=args.strict)
        errors += errs
        if not errs:
            from deep_vision_tpu.obs.journal import read_journal

            counts: dict = {}
            for e in read_journal(path):
                counts[e["event"]] = counts.get(e["event"], 0) + 1
            print(f"OK {path}: " + " ".join(
                f"{k}x{n}" for k, n in sorted(counts.items())))
    if args.trace:
        errs = check_trace(args.trace)
        errors += errs
        if not errs:
            with open(args.trace) as f:
                doc = json.load(f)
            events = doc["traceEvents"] if isinstance(doc, dict) else doc
            names = sorted({e["name"] for e in events if e.get("ph") == "X"})
            print(f"OK {args.trace}: {len(events)} events, "
                  f"spans: {', '.join(names)}")
    for e in errors:
        print("FAIL " + e, file=sys.stderr)
    return EXIT_INVALID if errors else EXIT_OK


if __name__ == "__main__":
    raise SystemExit(main())
