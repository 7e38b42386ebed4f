"""Unified telemetry: metrics registry, run journal, step-time breakdown,
span tracing, and the training health monitor.

The observability layer every perf PR reports through (SURVEY.md §2.7
records the reference's instrumentation as one examples/sec print):

- `registry`: counters / gauges / log-scale histograms, exported as
  Prometheus text format or JSONL snapshots (`Registry`, `get_registry`).
- `journal`: append-only JSONL of typed run events — manifest, steps,
  evals, checkpoints, health, crash/exit markers (`RunJournal`,
  `read_journal`).
- `stepclock`: host data-wait vs dispatch vs device-compute breakdown
  with periodic `block_until_ready` fences, plus recompile and HBM
  tracking (`StepClock`, `recompile_count`, `hbm_bytes_in_use`).
- `trace`: spans across the data pipeline, trainers and inference —
  *where* the time went: always in a ring on the clock a profiler
  capture is on (`trace.spans()`), and as Chrome trace events under
  `--trace` (`Tracer`, `span`, `set_tracer`).
- `health`: NaN/Inf guard with warn/skip_step/abort policies, rolling
  z-score divergence detection, and a hang watchdog that dumps thread
  stacks — *why* the run died (`HealthMonitor`, `TrainingHealthError`).
- `flight`: always-on bounded-memory flight recorder that dumps an
  atomic crc-checked postmortem bundle on crash/hang/abort/preemption —
  the black box (`FlightRecorder`, `set_flight`, `validate_bundle`).
- `autoprof`: anomaly-triggered `jax.profiler` capture with cooldown
  and budget, plus the configurable static window (`AutoProfiler`).
- `merge`: per-host journal merge + cross-host straggler detection for
  multi-host runs (`merge_journal_files`; CLI in tools/obs_merge.py),
  plus per-request trace-id stitching into causal cross-process
  timelines (`trace_timelines`; rendered by `obs_report --merged`).
- `telemetry`: the live plane — per-process HTTP `/metrics` `/varz`
  `/healthz` `/statusz` on a daemon thread, with run-dir discovery
  files and typed `telemetry_server` journal events (`TelemetryServer`;
  poller in tools/obs_poll.py).
- `propagate`: W3C-traceparent-style trace context minted at
  request/batch ingress, carried over the data-service frame protocol
  and the serve request path, auto-stamped onto journal events and
  trace spans (`TraceContext`, `new_trace`, `use`, `current`).
- `costmodel`: compiled-artifact introspection — XLA cost/memory
  analysis plus the collective inventory parsed from compiled HLO
  (`cost_summary`, `collective_inventory`, `tree_bytes`) — the
  predicted flop/byte/comm bill of every jit pair.
- `perfwatch`: the performance-attribution hook — profiles compiled
  executables where a build already happened (Engine.warmup, the
  Trainer's cached steps) into typed `perf_profile`/`perf_collective`
  events and registry gauges, and feeds the `/statusz` perf section
  (step-time quantiles, last perf-gate verdict, last trace digest);
  ledger + regression gate in tools/perf_gate.py, step-time
  decomposition in tools/trace_digest.py (`profile_compiled`,
  `telemetry_status`).
- `locksmith`: opt-in runtime lock-order sanitizer — named lock/condition
  wrappers adopted by serve/ and obs/, order-inversion + hold-time-outlier
  detection journaled as `lock_order_violation`/`lock_contention` events;
  armed in serve-smoke/chaos-smoke, a module-global None-check when
  disabled (`locksmith.lock`, `locksmith.arm`, `locksmith.report`). The
  static half is lint/concur.py (jaxlint DV101-DV104).

- `goodput`: the wall-clock attribution ledger — every second of a run
  lands in exactly one typed bucket (productive_step, data_wait,
  compile, checkpoint, host_loss_recovery, replica_respawn,
  rendezvous_wait, drain, overhead) with `sum(buckets) == wall_clock`
  by construction; live tap (`GoodputMeter`) and offline replay
  (`attribute_journal`) run the same accountant, and `goodput_frac`
  feeds the perf ledger's MAD gate.
- `alerts`: multi-window burn-rate SLO rules over the journal stream —
  serving error/latency budgets and training budgets (goodput floor,
  recompile bursts, starvation) evaluated at event time, live on
  `/alertz` and offline over merged journals, with typed
  `alert_fired`/`alert_resolved` events (`AlertEngine`,
  `evaluate_journal`).

Metric/journal/trace writers are process-0-only in single-process runs;
multi-process runs write per-host `.pN` files (registry.process_suffix)
that `tools/obs_merge.py` stitches back into one timeline.
"""
from deep_vision_tpu.obs.alerts import (
    AlertEngine,
    default_rules,
    default_serving_rules,
    default_training_rules,
    evaluate_journal,
)
from deep_vision_tpu.obs.autoprof import AutoProfiler
from deep_vision_tpu.obs.goodput import (
    GOODPUT_BUCKETS,
    GoodputAccountant,
    GoodputMeter,
    attribute_journal,
)
from deep_vision_tpu.obs.flight import (
    FlightRecorder,
    get_flight,
    set_flight,
    validate_bundle,
)
from deep_vision_tpu.obs.health import (
    HealthMonitor,
    TrainingHealthError,
    dump_all_stacks,
)
from deep_vision_tpu.obs.journal import RunJournal, read_journal
from deep_vision_tpu.obs.propagate import (
    TraceContext,
    from_traceparent,
    new_trace,
)
from deep_vision_tpu.obs.telemetry import TelemetryServer
from deep_vision_tpu.obs.trace import (
    Tracer,
    get_tracer,
    set_tracer,
    span,
    trace_event,
    traced,
)
from deep_vision_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_registry,
    is_primary_host,
    process_suffix,
)
from deep_vision_tpu.obs.stepclock import (
    StepClock,
    compile_seconds,
    hbm_bytes_in_use,
    hbm_stats,
    recompile_count,
)

__all__ = [
    "AlertEngine",
    "AutoProfiler",
    "Counter",
    "GOODPUT_BUCKETS",
    "GoodputAccountant",
    "GoodputMeter",
    "FlightRecorder",
    "Gauge",
    "HealthMonitor",
    "Histogram",
    "Registry",
    "RunJournal",
    "StepClock",
    "TelemetryServer",
    "TraceContext",
    "Tracer",
    "TrainingHealthError",
    "attribute_journal",
    "compile_seconds",
    "default_rules",
    "default_serving_rules",
    "default_training_rules",
    "dump_all_stacks",
    "evaluate_journal",
    "from_traceparent",
    "get_flight",
    "get_registry",
    "get_tracer",
    "hbm_bytes_in_use",
    "hbm_stats",
    "is_primary_host",
    "new_trace",
    "process_suffix",
    "read_journal",
    "recompile_count",
    "set_flight",
    "set_tracer",
    "span",
    "trace_event",
    "traced",
    "validate_bundle",
]
