"""Step-time breakdown + recompile and HBM tracking.

Under JAX's async dispatch the wall time around a `train_step` call
measures *enqueue*, not compute — the reference's examples/sec print
(YOLO/tensorflow/train.py:217-223) and any naive timer conflate host
data-wait, dispatch, and device work. StepClock separates them:

  data_wait_ms   host blocked in the data iterator's next()
  dispatch_ms    host time to trace/shard/enqueue the step
  step_time_ms   wall from the previous step's commit (for the first step
                 of a feed: from `iter_data`'s start) to this step's; over
                 an epoch they add up to the epoch's wall. A clock driven
                 without `iter_data`: data wait + enter -> commit
  sync_ms        on sampled steps only: how long the host waited, in a
                 block_until_ready, for the device to finish the step

Every duration here is a difference of `time.perf_counter()` reads: they
feed triggers (obs/autoprof.py, the alert rules, the stall rule below), and
a monotonic clock cannot be stepped under them. The spans of the same
regions (`{name}/data_wait`, `{name}/fetch`) are stamped with
`time.time_ns()` for the ring (obs/trace.py); a committed record lays its
`step_time_ms` on that axis as `wall_ns`, from one `time_ns()` read at
commit, for the stall event's split.

`Trainer`'s loop keeps one step in flight: it dispatches step N, then
reads step N-1's report (post-update step counter, learning rate and
metrics, outputs of the step program, in one `device_get`), commits and
logs it, and goes to fetch batch N+1 while the device runs step N. It
never waits for the step it has just dispatched but at a flush (end of
an epoch, a preemption save, an exception leaving the epoch), so the
sampled fence (`await_report`, every `sample_every` steps, default 16)
waits for the report the loop is about to read anyway and `sync_ms` is
the host's wait for the device. One step late, therefore: the health
guard (an `abort` names step N after step N+1 was dispatched), the
preemption poll (keyed to the step just read; the save flushes the step
in flight first) and autoprof's `observe_step`.
`{name}_steps_covered_total` counts the steps whose report was not ready
when the loop came to read it: the host was back first and the device
never waited for it (covered / steps near 1 is a device-bound loop, near
0 a host-bound one). `{name}_host_fetches_total` counts the blocking
device->host fetches, one a dispatch; the spans `train/data_wait` (here)
and `train/place`, `train/dispatch`, `train/fetch`, `train/log`
(train/trainer.py) say where the host was. The GAN loops keep their
metrics on the device until the epoch ends and fence at the end of the
sampled step itself (`fence_on`). Recompiles are counted
process-wide from the
`/jax/core/compile/backend_compile_duration` monitoring event (fires per
backend compile, silent on cache hits — verified against jit cache
behavior in tests), HBM from `device.memory_stats()` where the backend
provides it (TPU yes, CPU None).
"""
from __future__ import annotations

import statistics
import threading
import time
from collections import deque
from typing import Iterable, Iterator, Optional

from deep_vision_tpu.obs.registry import Registry, get_registry
from deep_vision_tpu.obs.trace import GC_SPAN, Span, span, spans, split_wall

# -- recompile tracking ------------------------------------------------------

_compile_lock = threading.Lock()
_compile_events = 0
_compile_seconds = 0.0
_listener_installed = False


def _install_compile_listener() -> None:
    """Idempotent: jax.monitoring listeners cannot be individually removed,
    so exactly one module-level listener feeds a process-wide counter."""
    global _listener_installed
    with _compile_lock:
        if _listener_installed:
            return
        import jax

        def _on_duration(event: str, duration: float, **kw) -> None:
            global _compile_events, _compile_seconds
            if "backend_compile" in event:
                with _compile_lock:
                    _compile_events += 1
                    _compile_seconds += float(duration)

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True


def recompile_count() -> int:
    """Backend compiles observed process-wide since the listener was
    installed (first StepClock construction or first explicit call)."""
    _install_compile_listener()
    return _compile_events


def compile_seconds() -> float:
    """Wall seconds the process spent in backend compiles, from the same
    monitoring listener as `recompile_count`. The goodput plane's
    compile feed: each step journal row carries the delta since the
    previous committed step as `compile_ms`, so offline attribution
    (obs/goodput.py) can carve compile time out of step gaps without a
    live listener."""
    _install_compile_listener()
    with _compile_lock:
        return _compile_seconds


def hbm_stats(device=None) -> "tuple[Optional[int], Optional[int]]":
    """(bytes_in_use, peak bytes) for one device; None where the backend
    has no stats (CPU). The peak is the larger of `peak_bytes_in_use` and
    `peak_bytes_reserved`: live arrays alone leave out what the step
    program reserves while it runs (11 GB of ResNet-50's on a v5e).

    The peak matters more than the instant: OOMs and fragmentation are
    high-water phenomena, an autoprof HBM trigger keyed on the
    instantaneous value would miss a transient allocation spike that
    freed before the sampled fence, and a postmortem wants the worst the
    run ever did — not where it happened to be when it died.
    """
    try:
        import jax

        dev = device or jax.local_devices()[0]
        stats = dev.memory_stats()
        if not stats:
            return None, None
        in_use = int(stats.get("bytes_in_use", stats.get("bytes_in_use_", 0)))
        peaks = [stats[k] for k in ("peak_bytes_in_use",
                                    "peak_bytes_reserved") if k in stats]
        return in_use, (int(max(peaks)) if peaks else None)
    except Exception:
        return None, None


def hbm_bytes_in_use(device=None) -> Optional[int]:
    """Live device memory, or None where the backend has no stats (CPU)."""
    return hbm_stats(device)[0]


class StepClock:
    """Per-step timing harness around a host training loop.

    Usage (what Trainer._run_epoch does):

        clock.start_epoch()
        for batch in clock.iter_data(data):      # times next() = data wait
            with clock.step(batch_size=n, auto_commit=False) as rec:
                report = dispatch(batch)         # times dispatch
            ... one step later ...
            rec.await_report(report)             # covered? sampled fence
            rec.commit(step=..., metrics=...)    # registry + journal row

    (a loop that reads nothing a step: `rec.fence_on(out)` inside the
    with-block, and the record commits itself at its end.) All timing is
    host-side perf_counter; the only device interaction is the sampled
    fence, and `examples_per_sec` is computed from the wall step time so
    it matches what an operator observes end to end.
    """

    def __init__(self, registry: Optional[Registry] = None,
                 journal=None, name: str = "train",
                 sample_every: int = 16, track_memory: bool = True):
        self.registry = registry or get_registry()
        self.journal = journal
        self.name = name
        self.sample_every = max(1, int(sample_every))
        self.track_memory = track_memory
        self._steps_seen = 0
        self._sync_samples = 0
        self._last_data_wait_ms = 0.0
        # where the next committed step's wall begins: the previous commit,
        # or the start of the feed (`iter_data`); None for a clock driven
        # without `iter_data`
        self._t_mark: Optional[float] = None
        self._recompiles_at_start: Optional[int] = None
        _install_compile_listener()
        # compile-seconds high-water at construction: step rows carry the
        # delta since the previous committed step, so a clock built after
        # another run's compiles never re-attributes them
        self._compile_s_last = compile_seconds()

        r = self.registry
        self._g_data_wait = r.gauge(f"{name}_data_wait_ms",
                                    "host ms blocked on the data iterator")
        self._g_step = r.gauge(f"{name}_step_time_ms",
                               "wall ms per step (wait + dispatch)")
        self._g_eps = r.gauge(f"{name}_examples_per_sec",
                              "wall-clock examples/sec")
        self._g_recompiles = r.gauge("jit_recompiles_total",
                                     "backend compiles observed this process")
        self._g_hbm = r.gauge("hbm_bytes_in_use",
                              "device bytes in use (0 where unavailable)")
        self._g_hbm_peak = r.gauge(
            "hbm_peak_bytes_in_use",
            "device high-water bytes (0 where unavailable)")
        self._h_step = r.histogram(f"{name}_step_ms",
                                   "per-step wall ms distribution")
        self._h_wait = r.histogram(f"{name}_data_wait_ms_hist",
                                   "per-step data-wait ms distribution")
        self._c_steps = r.counter(f"{name}_steps_total", "steps executed")
        self._c_examples = r.counter(f"{name}_examples_total",
                                     "examples consumed")
        self._c_tokens = r.counter(
            f"{name}_tokens_total",
            "tokens consumed (rows x sequence length a dispatch; a feed of "
            "images counts none)")
        self._c_starved = r.counter(
            f"{name}_data_starved_steps_total",
            "steps whose data wait exceeded their dispatch time")
        self._c_fetches = r.counter(
            f"{name}_host_fetches_total",
            "blocking device->host fetches made by the step loop")
        self._c_covered = r.counter(
            f"{name}_steps_covered_total",
            "steps whose report was not ready when the loop came to read "
            "it: the device never waited for the host")

    # -- data-wait side ----------------------------------------------------

    def iter_data(self, data: Iterable) -> Iterator:
        """Wrap a batch iterable, timing each next() as data wait.

        With device_prefetch armed the iterable is the prefetcher's
        consumer side: next() blocks only until a device-placed batch is
        queued, so the producer thread's device_put time — overlapped
        with the previous step's compute — is hidden from this timer by
        construction. That is the goodput contract: those seconds are
        already inside the overlapped step's `step_time_ms`
        (productive), never double-counted as data_wait
        (tests/test_goodput.py pins this with a depth-2 prefetcher)."""
        it = iter(data)
        wait_span = f"{self.name}/data_wait"
        self._t_mark = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            # `step`: the dispatch this batch feeds (see `step()`)
            with span(wait_span, step=self._steps_seen + 1):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            self._last_data_wait_ms = (time.perf_counter() - t0) * 1e3
            yield batch

    # -- step side ---------------------------------------------------------

    def step(self, batch_size: int = 0, auto_commit: bool = True,
             tokens: int = 0) -> "_StepRecord":
        """`auto_commit=False` defers the registry/journal write to an
        explicit `rec.commit(step=..., metrics=...)` AFTER the with-block
        (the Trainer's loop: one step later, once it has read the step's
        report), so what the host does between dispatch and logging counts
        toward step_time_ms but never pollutes dispatch_ms."""
        self._steps_seen += 1
        do_sample = (self._steps_seen % self.sample_every) == 0
        return _StepRecord(self, batch_size, self._last_data_wait_ms,
                           do_sample, auto_commit, tokens)

    def _finish(self, rec: "_StepRecord") -> None:
        self._c_steps.inc()
        if rec.covered:
            self._c_covered.inc()
        if rec.batch_size:
            self._c_examples.inc(rec.batch_size)
        if rec.tokens:
            self._c_tokens.inc(rec.tokens)
        self._g_data_wait.set(rec.data_wait_ms)
        self._g_step.set(rec.step_time_ms)
        self._h_step.observe(rec.step_time_ms)
        self._h_wait.observe(rec.data_wait_ms)
        if rec.examples_per_sec is not None:
            self._g_eps.set(rec.examples_per_sec)
        if rec.data_wait_ms > rec.dispatch_ms:
            self._c_starved.inc()
        cs = compile_seconds()
        if cs > self._compile_s_last:
            rec.compile_ms = (cs - self._compile_s_last) * 1e3
            self._compile_s_last = cs
        if rec.sampled:
            self._sync_samples += 1
            n = recompile_count()
            self._g_recompiles.set(n)
            rec.recompiles = n
            if self.track_memory:
                hbm, peak = hbm_stats()
                if hbm is not None:
                    self._g_hbm.set(hbm)
                    rec.hbm_bytes = hbm
                if peak is not None:
                    self._g_hbm_peak.set(peak)
                    rec.hbm_peak_bytes = peak
        if self.journal is not None:
            self.journal.step(rec.step if rec.step is not None
                              else self._steps_seen, **rec.fields())

    def note_host_fetches(self, n: int) -> None:
        """Count `n` blocking device->host fetches the loop just made."""
        self._c_fetches.inc(n)

    @property
    def sync_samples(self) -> int:
        return self._sync_samples

    @property
    def steps_seen(self) -> int:
        return self._steps_seen


class _StepRecord:
    """Context manager for one step; collects the timing fields."""

    def __init__(self, clock: StepClock, batch_size: int,
                 data_wait_ms: float, sampled: bool, auto_commit: bool,
                 tokens: int = 0):
        self._clock = clock
        self.batch_size = batch_size
        self.tokens = tokens
        self.data_wait_ms = data_wait_ms
        self.sampled = sampled
        self.index = clock.steps_seen  # this dispatch, as the spans count it
        self.covered = False  # see `await_report`
        self.step: Optional[int] = None  # caller may set the optimizer step
        self.metrics: dict = {}
        self.extra: dict = {}  # caller-supplied journal fields (e.g. the
                               # multistep width of a scan superstep)
        self.dispatch_ms = 0.0
        self.sync_ms: Optional[float] = None
        self.step_time_ms = 0.0
        self.examples_per_sec: Optional[float] = None
        self.recompiles: Optional[int] = None
        self.compile_ms: Optional[float] = None
        self.hbm_bytes: Optional[int] = None
        self.hbm_peak_bytes: Optional[int] = None
        self._t0 = 0.0
        # `step_time_ms` laid on the span ring's axis (`time.time_ns()`,
        # read once at commit): the interval that ends at this commit
        self.wall_ns: "tuple[int, int]" = (0, 0)
        self._fenced = None
        self._auto_commit = auto_commit
        self._committed = False

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def fence_on(self, out) -> None:
        """Hand the step's output here; on sampled steps it is fenced with
        block_until_ready so sync_ms captures the device pipeline drain."""
        self._fenced = out

    def __exit__(self, exc_type, exc, tb):
        self.dispatch_ms = (time.perf_counter() - self._t0) * 1e3
        if self._fenced is not None and exc_type is None:
            self._fence(self._fenced)
        if exc_type is None and self._auto_commit:
            self.commit()
        return False

    def _fence(self, out) -> None:
        if not self.sampled:
            return
        import jax

        t1 = time.perf_counter()
        # the host blocked on the device, as in the loop's own fetches
        with span(f"{self._clock.name}/fetch", step=self.index, n=0):
            jax.block_until_ready(out)
        self.sync_ms = (time.perf_counter() - t1) * 1e3

    def await_report(self, report) -> None:
        """For a loop that reads this step's `report` (a pytree of the
        step program's outputs) after it has dispatched the next step:
        call when about to read it. Notes whether the device still had it
        in work (`covered`: the host was back first, so the device never
        waited for the host), and on a sampled step times the wait for it
        as `sync_ms`."""
        import jax

        self.covered = not jax.tree_util.tree_leaves(report)[0].is_ready()
        self._fence(report)

    def commit(self, step: Optional[int] = None,
               metrics: Optional[dict] = None,
               extra: Optional[dict] = None) -> None:
        """Close the record and write registry/journal. step_time_ms runs
        from the clock's mark (the previous commit, or the feed's start) to
        now, so a loop that commits late stays honest about the wall; with
        no mark (no `iter_data`) it is data wait + enter -> commit.
        `extra` fields ride the journal step event verbatim (unknown step
        fields are forward-compatible by the check_journal schema)."""
        if self._committed:
            return
        self._committed = True
        if step is not None:
            self.step = step
        if metrics is not None:
            self.metrics = metrics
        if extra:
            self.extra.update(extra)
        now = time.perf_counter()
        mark = self._clock._t_mark
        if mark is None:
            self.step_time_ms = self.data_wait_ms + (now - self._t0) * 1e3
        else:
            self.step_time_ms = (now - mark) * 1e3
            self._clock._t_mark = now
        end_ns = time.time_ns()
        self.wall_ns = (end_ns - int(self.step_time_ms * 1e6), end_ns)
        if self.batch_size and self.step_time_ms > 0:
            self.examples_per_sec = self.batch_size / self.step_time_ms * 1e3
        self._clock._finish(self)

    def fields(self) -> dict:
        out = {
            "step_time_ms": round(self.step_time_ms, 3),
            "data_wait_ms": round(self.data_wait_ms, 3),
            "dispatch_ms": round(self.dispatch_ms, 3),
        }
        if self.examples_per_sec is not None:
            out["examples_per_sec"] = round(self.examples_per_sec, 2)
        if self.sync_ms is not None:
            out["sync_ms"] = round(self.sync_ms, 3)
        if self.recompiles is not None:
            out["recompiles"] = self.recompiles
        if self.compile_ms is not None:
            out["compile_ms"] = round(self.compile_ms, 3)
        if self.hbm_bytes is not None:
            out["hbm_bytes"] = self.hbm_bytes
        if self.hbm_peak_bytes is not None:
            out["hbm_peak_bytes"] = self.hbm_peak_bytes
        if self.extra:
            out.update(self.extra)
        if self.metrics:
            out["metrics"] = {k: float(v) for k, v in self.metrics.items()}
        return out


# -- stalls --------------------------------------------------------------------

STALL_FACTOR, STALL_HISTORY, STALL_MIN_SEEN = 3.0, 64, 16
#: the stall event's names for the loop's spans (innermost first; what no
#: span covers is `other`); `{name}` is the clock's
STALL_BUCKETS = {"{name}/data_wait": "data_wait", "{name}/place": "place",
                 "{name}/dispatch": "dispatch", "{name}/fetch": "fetch",
                 "{name}/log": "log", GC_SPAN: "gc"}


class StallRule:
    """Which committed steps are stalls: a `step_time_ms` over
    `STALL_FACTOR` times the median of the last `STALL_HISTORY` committed,
    once `STALL_MIN_SEEN` have been seen. The check is one comparison a
    step; the limit is taken anew every `STALL_MIN_SEEN` steps."""

    def __init__(self):
        self._recent: deque = deque(maxlen=STALL_HISTORY)
        self._seen = 0
        self.limit_ms = float("inf")

    @property
    def median_ms(self) -> float:
        return self.limit_ms / STALL_FACTOR

    def observe(self, step_time_ms: float) -> bool:
        stalled = step_time_ms > self.limit_ms
        self._recent.append(step_time_ms)
        self._seen += 1
        if self._seen % STALL_MIN_SEEN == 0:
            self.limit_ms = STALL_FACTOR * statistics.median(self._recent)
        return stalled


def stall_split(rec: "_StepRecord", open_spans=()) -> dict:
    """A committed step's wall in ms over `data_wait / place / dispatch /
    fetch / log / gc / other`, from the ring's spans of the calling thread
    that overlap `rec.wall_ns`, clipped to it; `open_spans`: those the
    caller is still inside (the `train/log` that commits), taken to the
    commit."""
    lo, hi = rec.wall_ns
    name = rec._clock.name
    held = spans(since_ns=lo, thread=threading.get_ident())
    held += [Span(s.name, s.start_ns, hi, s.step, 0, None)
             for s in open_spans]
    split = split_wall(held, lo, hi, {k.format(name=name): v
                                      for k, v in STALL_BUCKETS.items()})
    return {k: round(v * 1e-6, 3) for k, v in split.items()}
