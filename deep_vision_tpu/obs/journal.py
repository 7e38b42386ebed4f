"""Run journal: append-only JSONL of typed run events.

One file per run, one JSON object per line, `event` + `ts` on every line.
Event types (full schema in obs/README.md):

  run_manifest  config, argv, mesh, device/process topology, jax version
  step          per-step timing/metrics (step_time_ms, data_wait_ms, ...)
  epoch         MetricLogger epoch summaries
  eval          eval-pass summaries
  checkpoint    checkpoint saves/restores
  health        health monitor findings (obs/health.py: non_finite,
                loss_spike, divergence, hang with thread stacks)
  profile       profiler trace start/stop
  bench         one named measurement (tools/shard_smoke.py scaling rows)
  retry         one retried/abandoned I/O attempt (resilience/retry.py)
  fault         an injected fault fired (resilience/faults.py)
  data_skip     a bad record skipped under the bad-record budget
  ckpt_quarantine  a corrupt/incomplete checkpoint step quarantined
  lock_order_violation  runtime lock-order inversion (obs/locksmith.py)
  lock_contention  a lock hold/wait over the locksmith threshold
  note          free-form annotation
  crash         atexit marker: the process died without close()
  exit          clean close, with status

The writer appends with a flush per line (a crash loses at most the
in-flight line) and registers an atexit hook that stamps a `crash`
event — so a reader can always tell a finished run (`exit`) from a dead
one (`crash`, or no terminal event at all for SIGKILL). Single-process
runs write the plain path; multi-process runs write one file PER HOST at
`<path>.p<process_index>` (obs.registry.process_suffix) so host 7's last
seconds survive host 7 — `tools/obs_merge.py` stitches them back into
one timeline. Readers: `read_journal`, tools/obs_report.py.

Taps (`add_tap`) observe every event row after it is written — the
flight recorder (obs/flight.py) rides one to keep its postmortem ring
buffers current without a second instrumentation surface. A tap must be
cheap and must never raise into the run it observes (exceptions are
swallowed).
"""
from __future__ import annotations

import atexit
import json
import os
import platform
import sys
import threading
import time
from typing import Callable, List, Optional

from deep_vision_tpu.obs import locksmith, propagate
from deep_vision_tpu.obs.registry import is_primary_host, process_suffix


def _jsonable(v):
    """Best-effort conversion for numpy/jax scalars and containers."""
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, (str, int, bool)) or v is None:
        return v
    if isinstance(v, float):
        return v if v == v and abs(v) != float("inf") else repr(v)
    try:
        return float(v)  # numpy/jax 0-d arrays and scalars
    except (TypeError, ValueError):
        return repr(v)


class RunJournal:
    """Append-only JSONL journal for one run (or one bench session)."""

    def __init__(self, path: str, run_id: Optional[str] = None,
                 kind: str = "train", per_process: bool = True,
                 writer: Optional[bool] = None):
        # multi-process runs: every host owns a suffixed file (`.pN`) so a
        # follower's telemetry outlives the follower; per_process=False
        # keeps the legacy process-0-only single shared path. writer=True
        # forces THIS process to write regardless of rank: elastic runs
        # name per-host files themselves (journal_<host>.jsonl) because a
        # rank-derived suffix would change across generations and strand
        # the pre-resize history in a terminal-less file
        sfx = process_suffix() if per_process else ""
        self.path = path + sfx
        self.kind = kind
        self.run_id = run_id or f"{kind}-{os.getpid()}-{int(time.time())}"
        self._closed = False
        self._closers: List[Callable[[], None]] = []
        self._taps: List[Callable[[dict], None]] = []
        self._primary = (bool(writer) if writer is not None
                         else (is_primary_host() or bool(sfx)))
        # writes come from the train loop AND side threads (the health
        # watchdog, data prefetch errors): one lock keeps lines whole.
        # locksmith-named: the runtime sanitizer checks nothing ever holds
        # this while taking a lock that can be held around a write()
        self._lock = locksmith.lock("obs.journal")
        self._manifest_row: Optional[dict] = None  # statusz identity card
        self._f = None
        self.dropped_lines = 0  # lines lost to journal I/O errors
        if self._primary:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._f = open(self.path, "a")
        # the crash marker: fires only if close() never ran
        atexit.register(self._atexit)

    # -- lifecycle ---------------------------------------------------------

    def add_tap(self, fn: Callable[[dict], None]) -> None:
        """Register an observer called with every event row after it is
        written (flight recorder, tests). Taps run outside the file lock
        and may themselves call write() (e.g. a flight dump journaling its
        own `flight_dump` event); a raising tap is swallowed — telemetry
        observers must never kill the run they observe."""
        self._taps.append(fn)

    def add_closer(self, fn: Callable[[], None]) -> None:
        """Register cleanup run by close() (and by the atexit crash path):
        e.g. Trainer.close so an unwinding run still stops an in-flight
        profiler trace and flushes writers."""
        self._closers.append(fn)

    def _run_closers(self) -> None:
        closers, self._closers = self._closers, []
        for fn in closers:
            try:
                fn()
            except Exception as e:  # a failing closer must not mask the rest
                self.write("note", note=f"closer {fn!r} failed: {e!r}")

    def _atexit(self) -> None:
        if self._closed:
            return
        self._run_closers()
        self.write("crash", reason="process exited without journal.close()")
        self._closed = True
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None

    def close(self, status: str = "clean_exit") -> None:
        if self._closed:
            return
        self._run_closers()
        self.write("exit", status=status)
        self._closed = True
        atexit.unregister(self._atexit)
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close("clean_exit" if exc_type is None
                   else f"exception: {exc_type.__name__}")

    # -- writers -----------------------------------------------------------

    def write(self, event: str, **fields) -> None:
        row = {"event": event, "ts": round(time.time(), 3),
               "run_id": self.run_id}
        # cross-process causality: a write made while a trace context is
        # installed on THIS thread (obs/propagate.py) carries the request's
        # ids — explicit trace fields passed by the caller win (the serve
        # dispatcher stamps a request's context from another thread)
        ctx = propagate.current()
        if ctx is not None and "trace_id" not in fields:
            row.update(ctx.fields())
        row.update({k: _jsonable(v) for k, v in fields.items()})
        # the fault hook sits OUTSIDE the lock: an injected fault that
        # journals its own `fault` event re-enters write(), and the lock is
        # not reentrant (the injector skips journaling for this one point,
        # but the ordering keeps the invariant structural, not behavioral)
        try:
            from deep_vision_tpu.resilience import faults

            faults.fire("journal.flush")
            with self._lock:
                if self._f is not None:
                    self._f.write(json.dumps(row) + "\n")
                    self._f.flush()
        except OSError as e:
            # telemetry must degrade, never kill the training it observes:
            # a failed journal write drops the line, counts it, and the
            # first drop is loud on stderr
            self.dropped_lines += 1
            if self.dropped_lines == 1:
                print(f"journal: WRITE FAILED ({type(e).__name__}: {e}); "
                      "dropping lines (journal_dropped_lines_total counts "
                      "them)", file=sys.stderr)
            try:
                from deep_vision_tpu.obs.registry import get_registry

                get_registry().counter(
                    "journal_dropped_lines_total",
                    "journal lines lost to I/O errors").inc()
            except Exception:
                pass
        # taps observe the row even when the file write failed or this host
        # is a non-writer: the flight recorder's postmortem buffers must
        # stay current precisely when the journal volume is the thing dying
        for tap in self._taps:
            try:
                tap(row)
            except Exception:
                pass

    def manifest(self, config: Optional[dict] = None, **extra) -> None:
        """The run's identity card: everything needed to interpret (or
        machine-diff) the numbers that follow."""
        info = {
            "kind": self.kind,
            "argv": list(sys.argv),
            "python": platform.python_version(),
            "hostname": platform.node(),
            "pid": os.getpid(),
        }
        try:
            import jax

            info.update(
                jax_version=jax.__version__,
                backend=jax.default_backend(),
                device_kind=jax.devices()[0].device_kind,
                device_count=jax.device_count(),
                local_device_count=jax.local_device_count(),
                process_index=jax.process_index(),
                process_count=jax.process_count(),
            )
        except Exception as e:
            info["jax"] = f"unavailable: {e!r}"
        if config is not None:
            info["config"] = config
        info.update(extra)
        self._manifest_row = {k: _jsonable(v) for k, v in info.items()}
        self.write("run_manifest", **info)

    def manifest_row(self) -> Optional[dict]:
        """The captured manifest (None before manifest() runs) — the
        telemetry /statusz page serves it without re-reading the file."""
        return self._manifest_row

    def step(self, step: int, **fields) -> None:
        self.write("step", step=int(step), **fields)

    def bench(self, name: str, result: dict, **extra) -> None:
        self.write("bench", name=name, result=result, **extra)


def read_journal(path: str) -> List[dict]:
    """Parse a journal JSONL; tolerates a torn final line (crash mid-write)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                events.append({"event": "_torn_line", "raw": line[:200]})
    return events
