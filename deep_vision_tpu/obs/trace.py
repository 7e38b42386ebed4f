"""Spans: where inside a step the time went, on the clock a capture is on.

The journal (journal.py) answers *what happened* per step; spans answer
*where inside the step the time went* — data fetch vs augment vs dispatch
vs eval vs checkpoint I/O — across every layer the journal touches.

Design constraints, in order:

- **One call, one pair of stamps, three readers.** Every instrumentation
  site calls the module-level `span(...)`. It returns one object: the
  profiler's `jax.profiler.TraceAnnotation` of that name and args (so a
  session whose host plane is on holds it), which stamps its entry and its
  exit with `time.time_ns()`, writes the completed span into the process's
  **ring** at exit, and hands the same two stamps to the `Tracer` where
  one is installed (Chrome trace-event JSON for Perfetto).
- **The ring is always on.** `RING_CAPACITY` completed spans, preallocated,
  the oldest overwritten and counted: `(name, start_ns, end_ns, step,
  thread ident)` and the span's other arguments where it has any. A span
  costs two clock reads and one slot store: no lock, no file, nothing that
  grows. Readers take a snapshot (`spans(...)`, `overwritten()`): the
  flight recorder's span tail, the stall event's split of a long step
  (train/trainer.py), the benchmark's host-loop metrics, and `spans.json`
  beside a capture.
- **The capture's clock.** `jax.profiler` stamps a capture's
  `profile_start_time` (plane `Task Environment`) with the Unix clock in
  nanoseconds and counts every event's `start_ns` from it; `time.time_ns()`
  is that clock, so a span lies on the device planes' axis with both host
  tracers off: `write_capture_spans` leaves them beside a capture as they
  are, and a reader subtracts the capture's epoch (tools/trace_digest.py).
- **jax-free at import.** The data pipeline and spawned workers import
  this module, so it never imports jax: the annotation class is taken
  only once `jax` is in `sys.modules`; until then, and with no tracer,
  `span(...)` returns a shared no-op context manager and nothing is
  recorded (such a process has no capture and no flight recorder).
- **Always-valid JSON on disk.** A hung or SIGKILLed run is exactly when
  the Chrome trace matters most, so `Tracer.flush()` rewrites the whole
  file atomically (tmp + os.replace) instead of streaming an unterminated
  array. Its events buffer in memory and flush every `flush_every`
  completions and from an atexit hook.
- **Thread-safe, process-0-only files.** Producer threads (data prefetch,
  watchdog) record spans concurrently with the train loop; each carries
  its thread id. Non-zero `jax.process_index()` hosts never write a
  Chrome file.

Cross-referencing: the tracer carries the journal's `run_id` in the
trace metadata, and spans carry a `step` arg where the caller knows it
(what the spans of one dispatch share), so a Perfetto timeline and an
obs_report table describe the same run. Nesting is containment on one
thread.
"""
from __future__ import annotations

import atexit
import gc
import itertools
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional

from deep_vision_tpu.obs import locksmith, propagate
from deep_vision_tpu.obs.registry import is_primary_host, process_suffix

RING_CAPACITY = 65_536


class Span(NamedTuple):
    """A completed span as the ring's readers get it; stamps are
    `time.time_ns()`."""

    name: str
    start_ns: int
    end_ns: int
    step: Optional[int]
    thread: int
    args: Optional[dict]


class SpanRing:
    """Fixed ring of completed spans; the oldest is overwritten.

    `put` is the hot path: a sequence number from an `itertools.count`
    (its `next` is one C call, so two threads never get one slot) and one
    list store. Readers copy the slots and sort by sequence number."""

    __slots__ = ("capacity", "_mask", "_slots", "_seq")

    def __init__(self, capacity: int = RING_CAPACITY):
        if capacity < 1 or capacity & (capacity - 1):
            raise ValueError(f"ring capacity {capacity} is no power of two")
        self.capacity = capacity
        self._mask = capacity - 1
        self._slots: list = [None] * capacity
        self._seq = itertools.count()

    def put(self, name: str, start_ns: int, end_ns: int, step,
            args: Optional[dict]) -> None:
        # a wall clock stepped back under the span: kept, at zero length
        seq = next(self._seq)
        self._slots[seq & self._mask] = (seq, name, start_ns,
                                         max(end_ns, start_ns), step,
                                         threading.get_ident(), args)

    def spans(self, since_ns: Optional[int] = None,
              thread: Optional[int] = None) -> List[Span]:
        """The spans held, oldest first (in the order they ended); with
        `since_ns`, those that ended at or after it; with `thread`, those
        of that thread ident."""
        held = sorted(s for s in list(self._slots) if s is not None)
        return [Span(*s[1:]) for s in held
                if (since_ns is None or s[3] >= since_ns)
                and (thread is None or s[5] == thread)]

    def overwritten(self) -> int:
        """How many spans were written over by newer ones."""
        last = max((s[0] for s in list(self._slots) if s is not None),
                   default=-1)
        return max(0, last + 1 - self.capacity)


_ring = SpanRing()


def spans(since_ns: Optional[int] = None,
          thread: Optional[int] = None) -> List[Span]:
    """A snapshot of the process's span ring, oldest first."""
    return _ring.spans(since_ns, thread)


def overwritten() -> int:
    """How many of the process's spans the ring no longer holds."""
    return _ring.overwritten()


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


def _make_span_class(base):
    """The span class over `base`: `jax.profiler.TraceAnnotation`, or
    `object` for a tracer's spans in a process without jax."""
    annotated = base is not object

    class _Span(base):
        """One span: stamped at entry and exit, written to the ring at
        exit and handed to its tracer; the profiler's annotation
        meanwhile (recorded only inside a session with its host plane
        on)."""

        __slots__ = ("_tracer", "name", "step", "args", "start_ns", "end_ns")

        def __init__(self, tracer: Optional["Tracer"], name: str, step,
                     args: dict):
            if annotated:
                if step is None:
                    base.__init__(self, name, **args)
                else:
                    base.__init__(self, name, step=step, **args)
            self._tracer = tracer
            self.name = name
            self.step = step
            self.args = args

        def set(self, **args) -> None:
            """Attach args discovered mid-span (e.g. the optimizer step,
            which is only known after the state fetch)."""
            if annotated:
                self.set_metadata(**args)
            if "step" in args:
                self.step = args.pop("step")
            self.args.update(args)

        def __enter__(self):
            if annotated:
                base.__enter__(self)
            self.start_ns = time.time_ns()
            return self

        def __exit__(self, exc_type, exc, tb):
            self.end_ns = end_ns = max(time.time_ns(), self.start_ns)
            if annotated:
                base.__exit__(self, exc_type, exc, tb)
            if exc_type is not None:
                self.args.setdefault("error", exc_type.__name__)
            _ring.put(self.name, self.start_ns, end_ns, self.step,
                      self.args or None)
            if self._tracer is not None:
                self._tracer._record(
                    self.name, self.start_ns / 1e3, end_ns / 1e3,
                    self.args if self.step is None
                    else {"step": self.step, **self.args})
            return False

    return _Span


_PlainSpan = _make_span_class(object)
_annotated = None  # the span class over the profiler's, once jax is loaded


def _annotated_class():
    """The span class over `jax.profiler.TraceAnnotation`, or None while
    `jax` is not in `sys.modules` (this module never imports it)."""
    global _annotated
    if _annotated is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        base = getattr(profiler, "TraceAnnotation", None)
        if base is not None:
            _annotated = _make_span_class(base)
    return _annotated


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

    def span(self, name: str, step=None, **args):
        # cross-process causality: a span opened while a trace context is
        # installed (obs/propagate.py) carries the request's ids, so the
        # Perfetto view and the journal agree on which request this was
        ctx = propagate.current()
        if ctx is not None and "trace_id" not in args:
            args.update(ctx.fields())
        cls = _annotated or _annotated_class() or _PlainSpan
        return cls(self, name, step, args)

    def event(self, name: str, t0_us: float, t1_us: Optional[float] = None,
              **args) -> None:
        """Explicit complete event for callers that time a region that
        doesn't nest as a with-block (e.g. the data pipeline's per-batch
        assembly, which spans loop iterations)."""
        self._record(name, t0_us, t1_us if t1_us is not None else now_us(),
                     args)

    def _record(self, name: str, t0_us: float, t1_us: float,
                args: dict) -> None:
        if self._closed or not self._primary:
            # followers never write a file, so buffering their events
            # would be a leak with no consumer
            return
        t = threading.current_thread()
        tid = t.ident or 0
        ev = _complete_event(name, t0_us, t1_us, self._pid, tid, args)
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


def _complete_event(name: str, t0_us: float, t1_us: float, pid: int,
                    tid: int, args: Optional[dict]) -> dict:
    """A Trace Event Format complete event ("ph": "X")."""
    ev = {"name": name, "ph": "X", "ts": round(t0_us, 1),
          "dur": round(max(t1_us - t0_us, 0.0), 1), "pid": pid, "tid": tid}
    if args:
        ev["args"] = {k: _arg(v) for k, v in args.items()}
    return ev


def chrome_event(s: Span, pid: int) -> dict:
    """A ring span as the trace event a `Tracer` would have written."""
    args = s.args if s.step is None else {"step": s.step, **(s.args or {})}
    return _complete_event(s.name, s.start_ns / 1e3, s.end_ns / 1e3, pid,
                           s.thread, args)


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


def span(name: str, step=None, **args):
    """A span: the profiler's annotation, written to the ring when it
    ends, and on the active tracer if one is installed. A shared no-op in
    a process that has neither a tracer nor jax.

    The instrumentation idiom used by every layer:

        with span("data/fetch", loader=self.name):
            batch = q.get()
    """
    t = _active
    if t is not None:
        return t.span(name, step, **args)
    cls = _annotated or _annotated_class()
    if cls is None:
        return _NULL_SPAN
    return cls(None, name, step, args)


def trace_event(name: str, t0_us: float, t1_us: Optional[float] = None,
                **args) -> None:
    """Explicit complete event, for a region that doesn't nest as a
    with-block: to the ring and the active tracer (no-op where `span`
    is)."""
    t = _active
    if t is None and (_annotated or _annotated_class()) is None:
        return
    if t1_us is None:
        t1_us = now_us()
    rest = {k: v for k, v in args.items() if k != "step"}
    _ring.put(name, int(t0_us * 1e3), int(t1_us * 1e3), args.get("step"),
              rest or None)
    if t is not None:
        t.event(name, t0_us, t1_us, **args)


def now_us() -> float:
    """The spans' clock in microseconds, for callers building explicit
    trace_event()s."""
    return time.time_ns() / 1e3


# -- collections, as spans ----------------------------------------------------

GC_SPAN = "gc/collect"
GC_MIN_NS = 1_000_000  # younger generations' collections under this: dropped
_gc_start_ns = 0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_start_ns
    if phase == "start":
        _gc_start_ns = time.time_ns()
        return
    end_ns = time.time_ns()
    if info["generation"] == 2 or end_ns - _gc_start_ns > GC_MIN_NS:
        _ring.put(GC_SPAN, _gc_start_ns, end_ns, None,
                  {"generation": info["generation"]})


def watch_gc() -> None:
    """Write the interpreter's collections to the ring as `gc/collect`
    (`generation`), on the thread they ran on: those of generation 2 and
    any that took over a millisecond. Idempotent."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def split_wall(held: List[Span], lo_ns: int, hi_ns: int,
               buckets: Dict[str, str], other: str = "other") -> dict:
    """Every instant of `[lo_ns, hi_ns]` under one name -> `{name: ns}`:
    the innermost of `held` (one thread's spans; containment is nesting)
    open at that instant whose name is in `buckets` names it by
    `buckets[name]`, `other` what none covers."""
    open_in = [(max(s.start_ns, lo_ns), min(s.end_ns, hi_ns), s.start_ns,
                buckets[s.name]) for s in held
               if s.name in buckets and s.end_ns > lo_ns
               and s.start_ns < hi_ns]
    cuts = sorted({lo_ns, hi_ns, *(c for s, e, *_ in open_in
                                    for c in (s, e))})
    out = dict.fromkeys([*buckets.values(), other], 0)
    for a, b in zip(cuts, cuts[1:]):
        here = [(s0, name) for s, e, s0, name in open_in
                if s <= a and e >= b]
        out[max(here)[1] if here else other] += b - a
    return out


# -- beside a capture ----------------------------------------------------------

CAPTURE_SPANS = "spans.json"


def write_capture_spans(capture_dir: str, since_ns: int,
                        until_ns: int) -> str:
    """`<capture_dir>/spans.json`: the ring's spans that lie inside
    `[since_ns, until_ns]`, two `time.time_ns()` reads around the
    capture's `start_trace` and `stop_trace`, with their stamps as the
    ring holds them. A capture's events count from its
    `profile_start_time` on the same clock; a reader that has the capture
    open subtracts it (tools/trace_digest.py). -> the file's path."""
    held = [{"name": s.name, "start_ns": s.start_ns, "end_ns": s.end_ns,
             "step": s.step, "thread": s.thread,
             **({"args": {k: _arg(v) for k, v in s.args.items()}}
                if s.args else {})}
            for s in _ring.spans(since_ns=since_ns)
            if s.start_ns >= since_ns and s.end_ns <= until_ns]
    path = os.path.join(capture_dir, CAPTURE_SPANS)
    with open(path + ".tmp", "w") as f:
        json.dump({"since_ns": since_ns, "until_ns": until_ns,
                   "overwritten": _ring.overwritten(), "spans": held}, f)
    os.replace(path + ".tmp", path)
    return path


def start_profiler(log_dir: str) -> int:
    """Start a `jax.profiler` session as every capture of this program is
    taken (`--profile-dir`, autoprof's triggers; `AutoProfiler` stops it
    and writes `spans.json` into its directory): the host plane holds the
    runtime's own events and this module's spans as annotations. The
    Python tracer stays off: with it a ResNet-50 step loop ran four times
    slower and a 20-step capture was 79-247 MB. A reader of the loop's
    spans needs no host plane (`spans.json` is on the capture's clock
    without it); whether this one can go is PERF.md, section 7.
    -> `time.time_ns()` just before the session began."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    since_ns = time.time_ns()
    jax.profiler.start_trace(log_dir, profiler_options=options)
    return since_ns


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
