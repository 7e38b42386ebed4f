"""Span tracer: Chrome trace-event JSON that explains where time went.

The journal (journal.py) answers *what happened* per step; spans answer
*where inside the step the time went* — data fetch vs augment vs dispatch
vs eval vs checkpoint I/O — across every layer the journal touches. The
output is the Trace Event Format's complete-event ("ph": "X") list, so
one file loads directly in Perfetto / chrome://tracing and diffs across
PRs the same way journals do.

Design constraints, in order:

- **One call, two sinks.** Every instrumentation site calls the
  module-level `span(...)`. Where `jax` is loaded the span is also a
  `jax.profiler.TraceAnnotation` of the same name and args: whenever any
  profiler session is live (`--profile`, an autoprof capture, a
  benchmark's) the span lies in that session's host plane, on the clock
  its device planes use, and a reader can lay it over the device's idle
  gaps. With no session and no tracer it costs one annotation
  enter/exit (about a microsecond) and nothing else.
- **jax-free at import.** The data pipeline and spawned workers import
  this module, so it never imports jax: the annotation class is taken
  only once `jax` is in `sys.modules`; until then `span(...)` returns a
  shared no-op context manager.
- **Always-valid JSON on disk.** A hung or SIGKILLed run is exactly when
  the trace matters most, so flush() rewrites the whole file atomically
  (tmp + os.replace) instead of streaming an unterminated array. Spans
  buffer in memory and flush every `flush_every` completions and from an
  atexit hook.
- **Thread-safe, process-0-only.** Producer threads (data prefetch,
  watchdog) record spans concurrently with the train loop; each event
  carries its thread id and a one-time thread-name metadata event.
  Non-zero `jax.process_index()` hosts keep collecting (cheap) but never
  write.

Cross-referencing: the tracer carries the journal's `run_id` in the
trace metadata, and spans carry a `step` arg where the caller knows it,
so a Perfetto timeline and an obs_report table describe the same run.
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from deep_vision_tpu.obs import locksmith, propagate
from deep_vision_tpu.obs.registry import is_primary_host, process_suffix

# Trace-event timestamps are microseconds. Use an epoch-anchored clock so
# trace ts and journal ts (unix seconds) cross-reference directly:
# perf_counter offsets from a wall-clock anchor keep monotonicity within
# the run while staying on the journal's time axis.
_ANCHOR_WALL = time.time()
_ANCHOR_PERF = time.perf_counter()


def _now_us() -> float:
    return (_ANCHOR_WALL + (time.perf_counter() - _ANCHOR_PERF)) * 1e6


class _NullSpan:
    """Shared do-nothing span: the off-switch for every call site."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


_annotation = None  # the profiler's annotation class, once jax is loaded


def _annotation_class():
    """`jax.profiler.TraceAnnotation` with the spans' `set`, or None while
    `jax` is not in `sys.modules` (this module never imports it)."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        base = getattr(profiler, "TraceAnnotation", None)
        if base is not None:
            class _Annotation(base):
                __slots__ = ()
                set = base.set_metadata  # recorded only inside a session

            _annotation = _Annotation
    return _annotation


class _Span:
    """One in-flight span; records a complete ("X") event on exit, and is
    the profiler's annotation meanwhile."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._annotation = None

    def set(self, **args) -> None:
        """Attach args discovered mid-span (e.g. the optimizer step, which
        is only known after the state fetch)."""
        self.args.update(args)
        if self._annotation is not None:
            self._annotation.set(**args)

    def __enter__(self):
        cls = _annotation or _annotation_class()
        if cls is not None:
            self._annotation = cls(self.name, **self.args)
            self._annotation.__enter__()
        self._t0 = _now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _now_us()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        self._tracer._record(self.name, self._t0, t1, self.args)
        return False


class Tracer:
    """Buffered Chrome trace-event writer for one run.

    Usage:

        tracer = Tracer("runs/train.trace.json", run_id=journal.run_id)
        with tracer.span("train/step", step=12):
            ...
        tracer.close()

    or install it process-wide (`set_tracer`) and use the module-level
    `span(...)` from any layer.
    """

    def __init__(self, path: str, run_id: Optional[str] = None,
                 flush_every: int = 256, max_events: int = 200_000,
                 per_process: bool = True):
        # multi-process runs: one trace file per host at `<path>.pN` (same
        # contract as the journal) — followers become writers of their own
        # file instead of silent collectors
        sfx = process_suffix() if per_process else ""
        self.path = path + sfx
        self.run_id = run_id
        self.flush_every = max(1, int(flush_every))
        # ring-buffer cap: a post-mortem wants the most RECENT window, and
        # an uncapped buffer on a week-long run is an OOM of its own
        self.max_events = max(1000, int(max_events))
        self._events: List[dict] = []
        self._dropped = 0
        self._lock = locksmith.lock("obs.trace.buffer")
        # flush serialization is separate from the buffer lock: the file
        # write must not block recorders, but two concurrent flushes with
        # one tmp name would publish a torn file
        self._flush_lock = locksmith.lock("obs.trace.flush")
        self._closed = False
        self._primary = is_primary_host() or bool(sfx)
        self._pid = os.getpid()
        self._thread_named: Dict[int, str] = {}  # ident -> last-seen name
        self._unflushed = 0
        if self._primary:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
        atexit.register(self._atexit)

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **args) -> _Span:
        # cross-process causality: a span opened while a trace context is
        # installed (obs/propagate.py) carries the request's ids, so the
        # Perfetto view and the journal agree on which request this was
        ctx = propagate.current()
        if ctx is not None and "trace_id" not in args:
            args = dict(args, **ctx.fields())
        return _Span(self, name, args)

    def event(self, name: str, t0_us: float, t1_us: Optional[float] = None,
              **args) -> None:
        """Explicit complete event for callers that time a region that
        doesn't nest as a with-block (e.g. the data pipeline's per-batch
        assembly, which spans loop iterations)."""
        self._record(name, t0_us, t1_us if t1_us is not None else _now_us(),
                     args)

    def _record(self, name: str, t0_us: float, t1_us: float,
                args: dict) -> None:
        if self._closed or not self._primary:
            # followers never write a file, so buffering their events
            # would be a leak with no consumer
            return
        t = threading.current_thread()
        tid = t.ident or 0
        ev = {
            "name": name,
            "ph": "X",
            "ts": round(t0_us, 1),
            "dur": round(max(t1_us - t0_us, 0.0), 1),
            "pid": self._pid,
            "tid": tid,
        }
        if args:
            ev["args"] = {k: _arg(v) for k, v in args.items()}
        with self._lock:
            # keyed on ident AND name: the OS reuses thread ids, so a
            # short-lived worker's successor with the same ident still
            # gets its own metadata event (last-writer-wins in viewers)
            if self._thread_named.get(tid) != t.name:
                self._thread_named[tid] = t.name
                self._events.append({
                    "name": "thread_name", "ph": "M", "pid": self._pid,
                    "tid": tid, "args": {"name": t.name},
                })
            self._events.append(ev)
            if len(self._events) > self.max_events:
                # drop the oldest quarter in one slice (per-event pops
                # would be O(n) each); metadata reports the loss
                cut = len(self._events) // 4
                del self._events[:cut]
                self._dropped += cut
            self._unflushed += 1
            # adaptive cadence: every flush rewrites the whole file (the
            # price of always-valid JSON), so the interval grows with the
            # buffer — total I/O stays ~4x the final file size instead of
            # O(n^2/flush_every)
            do_flush = self._unflushed >= max(self.flush_every,
                                              len(self._events) // 4)
        if do_flush:
            self.flush()

    # -- persistence -------------------------------------------------------

    def flush(self) -> None:
        """Atomically rewrite the trace file with everything recorded so
        far; the on-disk file is valid Chrome trace JSON at all times."""
        if not self._primary:
            return
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
            self._unflushed = 0
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"run_id": self.run_id, "pid": self._pid,
                         "dropped_events": dropped},
        }
        # serialized: concurrent flushes sharing one tmp name would
        # truncate each other mid-dump and publish a torn file
        with self._flush_lock:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, self.path)

    def _atexit(self) -> None:
        if not self._closed:
            self.close()

    def close(self) -> None:
        if self._closed:
            return
        self.flush()
        self._closed = True
        atexit.unregister(self._atexit)

    @property
    def num_events(self) -> int:
        with self._lock:
            return len(self._events)

    def tail(self, n: int = 256) -> List[dict]:
        """The most recent `n` buffered events (complete + metadata) — the
        span tail a flight-recorder bundle snapshots at dump time."""
        with self._lock:
            return [dict(e) for e in self._events[-max(0, int(n)):]]


def _arg(v):
    """Span args must never poison the JSON dump (same contract as
    journal._jsonable, minus containers — span args are flat)."""
    if isinstance(v, (str, int, bool)) or v is None:
        return v
    if isinstance(v, float):
        return v if v == v and abs(v) != float("inf") else repr(v)
    try:
        return float(v)
    except (TypeError, ValueError):
        return repr(v)


# -- process-wide active tracer ----------------------------------------------

_active: Optional[Tracer] = None


def set_tracer(tracer: Optional[Tracer]) -> None:
    """Install (or clear, with None) the process-wide tracer that the
    module-level `span`/`trace_event` report to."""
    global _active
    _active = tracer


def get_tracer() -> Optional[Tracer]:
    return _active


def span(name: str, **args):
    """A span: on the active tracer if one is installed, and in the host
    plane of any live `jax.profiler` session. A shared no-op in a process
    that has neither a tracer nor jax.

    The instrumentation idiom used by every layer:

        with span("data/fetch", loader=self.name):
            batch = q.get()
    """
    t = _active
    if t is not None:
        return t.span(name, **args)
    cls = _annotation or _annotation_class()
    if cls is None:
        return _NULL_SPAN
    return cls(name, **args)


def trace_event(name: str, t0_us: float, t1_us: Optional[float] = None,
                **args) -> None:
    """Explicit complete event on the active tracer (no-op when off)."""
    t = _active
    if t is not None:
        t.event(name, t0_us, t1_us, **args)


def now_us() -> float:
    """The tracer's clock, for callers building explicit trace_event()s."""
    return _now_us()


def start_profiler(log_dir: str) -> None:
    """Start a `jax.profiler` session as every capture of this program is
    taken (`--profile-dir`, autoprof's triggers; stop it with
    `jax.profiler.stop_trace()`): the host plane holds the spans of this
    module and the runtime's own events, on the device planes' clock. The
    Python tracer stays off: with it a ResNet-50 step loop ran four times
    slower and a 20-step capture was 79-247 MB. The host plane is not
    free either: on a TPU the runtime re-tiles every host batch it copies
    and records one `Transpose` event a tile (100,000 a step at 77 MB),
    which stretched the wait for the copy from ~10 ms to 13-400 ms in
    captured steps; the loop's own spans read the same (PERF.md,
    section 6, PR 26). Read a capture's device times with that in mind."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)


def traced(name: Optional[str] = None, **static_args) -> Callable:
    """Decorator: wrap a function in a span named after it.

        @traced("checkpoint/save")
        def save(...): ...
    """
    def deco(fn: Callable) -> Callable:
        span_name = name or fn.__qualname__

        def wrapper(*a, **kw):
            with span(span_name, **static_args):
                return fn(*a, **kw)

        wrapper.__name__ = fn.__name__
        wrapper.__qualname__ = fn.__qualname__
        wrapper.__doc__ = fn.__doc__
        wrapper.__wrapped__ = fn
        return wrapper

    return deco
