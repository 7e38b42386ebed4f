"""Anomaly-triggered `jax.profiler` capture with cooldown and budget.

`--profile` used to mean "hope the interesting thing happens between
steps 10 and 20": the window was hard-coded, and a second start while a
trace was in flight would double-start the profiler. Production TPU
stacks (xprof-style on-demand capture) treat the anomaly itself as the
trigger: when the step time regresses, THAT window is the one worth the
~2x profiling overhead. This module is both modes behind one owner:

- **Static window** (`--profile-dir` + `--profile-window START:STOP`):
  capture exactly [START, STOP), configurable instead of 10:20, and
  tolerant of resuming past START (capture begins at the first step
  inside the window).
- **Auto policy** (`--autoprof`): rolling z-score on `step_time_ms` and
  `data_wait_ms`, recompile bursts between telemetry samples, and HBM
  high-water jumps each ARM a one-shot N-step capture that starts at
  the next step boundary. A cooldown and a per-run capture budget keep
  a sustained regression from profiling the whole run to death.

One capture at a time, process-wide: `jax.profiler` owns global state,
so a module-level latch guards re-entry no matter how many profilers or
trainers exist — a second trigger while a trace is in flight journals
`outcome=skipped_inflight` instead of crashing the profiler.

Every decision is a typed `profile_capture` journal event (reason +
outcome + step), so the journal answers "why does this run have three
trace dirs" without guessing: `started` / `captured` / `closed_early`
(a run that ended mid-capture — Trainer.close stops the trace instead
of leaking it) / `skipped_cooldown` / `skipped_budget` /
`skipped_inflight` / `failed`.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Callable, Optional, Tuple

from deep_vision_tpu.obs.registry import Registry, get_registry
from deep_vision_tpu.obs.trace import start_profiler, write_capture_spans

REASONS = ("static_window", "step_time_z", "data_wait_z",
           "recompile_burst", "hbm_jump", "manual")
OUTCOMES = ("started", "captured", "closed_early", "skipped_cooldown",
            "skipped_budget", "skipped_inflight", "failed")

# jax.profiler is process-global: exactly one trace may be in flight no
# matter how many AutoProfiler instances exist (trainer + a tool, tests)
_capture_lock = threading.Lock()
_capture_active = False


def _release_capture() -> None:
    global _capture_active
    with _capture_lock:
        _capture_active = False


class AutoProfiler:
    """Owner of profiler captures for one run.

    Wiring (what Trainer does):

        prof.on_step_start(step)        # before dispatch, every step
        ... run the step ...
        prof.observe_step(step, rec.fields())   # after commit
        ...
        prof.close()                    # stops an in-flight capture

    `fence` (set by the trainer) is called before `stop_trace` so the
    device pipeline drains into the trace instead of being cut off
    mid-flight.
    """

    def __init__(
        self,
        profile_dir: str,
        journal=None,
        registry: Optional[Registry] = None,
        window: Optional[Tuple[int, int]] = None,  # static [start, stop)
        auto: bool = False,
        window_steps: int = 8,       # auto-capture length
        cooldown_steps: int = 200,
        max_captures: int = 2,       # auto-capture budget per run
        z_threshold: float = 5.0,
        history: int = 64,
        min_history: int = 16,
        recompile_burst: int = 3,
        hbm_jump_frac: float = 0.25,
    ):
        if window is not None:
            start, stop = int(window[0]), int(window[1])
            if not 0 <= start < stop:
                raise ValueError(
                    f"profile window must be 0 <= start < stop, got "
                    f"{start}:{stop}")
            window = (start, stop)
        self.profile_dir = profile_dir
        self.journal = journal
        self.registry = registry or get_registry()
        self.window = window
        self.auto = bool(auto)
        self.window_steps = max(1, int(window_steps))
        self.cooldown_steps = max(0, int(cooldown_steps))
        self.max_captures = max(0, int(max_captures))
        self.z_threshold = float(z_threshold)
        self.min_history = max(2, int(min_history))
        self.recompile_burst = max(1, int(recompile_burst))
        self.hbm_jump_frac = float(hbm_jump_frac)
        #: trainer-set: drains the device pipeline before stop_trace
        self.fence: Optional[Callable[[], None]] = None

        self._step_times: deque = deque(maxlen=int(history))
        self._data_waits: deque = deque(maxlen=int(history))
        self._last_recompiles: Optional[int] = None
        self._hbm_high_water: Optional[int] = None

        self._steps = 0                 # last step index seen
        self._static_pending = window is not None
        self._armed: Optional[Tuple[str, dict]] = None
        self._capturing = False
        self._capture_reason = ""
        self._capture_dir = ""
        self._capture_since_ns = 0
        self._capture_start = 0
        self._stop_at = 0
        self._captures = 0              # auto captures started (budget)
        self._cooldown_until = 0
        self._skip_latched = False      # one skipped_cooldown per cooldown
        self._budget_latched = False    # one skipped_budget per run
        self._seq = 0
        self._closed = False

        r = self.registry
        self._c_captures = r.counter("autoprof_captures_total",
                                     "profiler captures started")
        self._c_triggers = r.counter("autoprof_triggers_total",
                                     "anomaly triggers observed (incl. "
                                     "skipped ones)")

    # -- step boundary hooks ------------------------------------------------

    @property
    def capturing(self) -> bool:
        return self._capturing

    @property
    def needs_step_index(self) -> bool:
        """True while on_step_start needs the REAL optimizer step (a
        pending static window must anchor to it, e.g. after a resume).
        Otherwise the internal counter — recalibrated by every
        observe_step — suffices, and callers can skip the blocking
        device fetch the real index costs (see Trainer._profiler_hook)."""
        return self._static_pending

    def on_step_start(self, step: Optional[int] = None) -> None:
        """Called before each step's dispatch: starts a due capture, stops
        a finished one. `step` defaults to an internal counter for loops
        that would pay a device sync to know it."""
        if self._closed:
            return
        # counterless callers advance the internal counter here; callers
        # that DO pass (or later observe) the real optimizer step
        # recalibrate it, so the two styles can mix within one run
        step = self._steps + 1 if step is None else int(step)
        self._steps = step
        if self._capturing:
            if step >= self._stop_at:
                self._stop(step, "captured")
            return
        if (self._static_pending and self.window is not None
                and self.window[0] <= step < self.window[1]):
            # pending until a start SUCCEEDS: a failed start (unwritable
            # dir) or one skipped while another capture holds the latch
            # retries at the next step still inside the window, instead of
            # silently dropping the user's explicit capture request
            if self._start(step, "static_window", stop_at=self.window[1]):
                self._static_pending = False
            return
        if self._static_pending and self.window is not None \
                and step >= self.window[1]:
            self._static_pending = False  # window over: stop re-anchoring
        if self._armed is not None:
            reason, detail = self._armed
            self._armed = None
            self._start(step, reason, stop_at=step + self.window_steps,
                        **detail)

    def observe_step(self, step: int, fields: dict) -> None:
        """Feed one committed step record (StepClock `rec.fields()`);
        evaluates the anomaly triggers and arms a capture when one fires
        outside cooldown and under budget."""
        if self._closed:
            return
        self._steps = int(step)
        if self._capturing or not self.auto:
            # captured steps run ~2x slow under the profiler: keeping them
            # out of the baseline windows stops one capture from making
            # every following step look fast
            return
        st = _num(fields.get("step_time_ms"))
        dw = _num(fields.get("data_wait_ms"))
        trigger: Optional[Tuple[str, dict]] = None

        z = _zscore(self._step_times, st, self.min_history)
        if z is not None and z > self.z_threshold:
            trigger = ("step_time_z",
                       {"z": round(z, 2), "value_ms": round(st, 3)})
        else:
            zw = _zscore(self._data_waits, dw, self.min_history)
            if zw is not None and zw > self.z_threshold:
                trigger = ("data_wait_z",
                           {"z": round(zw, 2), "value_ms": round(dw, 3)})

        rc = fields.get("recompiles")
        if rc is not None:
            if (trigger is None and self._last_recompiles is not None
                    and rc - self._last_recompiles >= self.recompile_burst):
                trigger = ("recompile_burst",
                           {"new_compiles": int(rc - self._last_recompiles)})
            self._last_recompiles = int(rc)

        hbm = fields.get("hbm_peak_bytes", fields.get("hbm_bytes"))
        if hbm is not None:
            hw = self._hbm_high_water
            if (trigger is None and hw is not None and hw > 0
                    and hbm > hw * (1.0 + self.hbm_jump_frac)):
                trigger = ("hbm_jump", {"peak_bytes": int(hbm),
                                        "prev_high_water": int(hw)})
            self._hbm_high_water = max(int(hbm), hw or 0)

        # spiking values stay OUT of the baselines (the health monitor's
        # trick): admitting them would inflate the std until the very
        # regressions being hunted stop registering
        if trigger is None or trigger[0] != "step_time_z":
            if st is not None:
                self._step_times.append(st)
        if trigger is None or trigger[0] != "data_wait_z":
            if dw is not None:
                self._data_waits.append(dw)
        if trigger is not None:
            self._request(step, trigger[0], trigger[1])

    # -- capture control ----------------------------------------------------

    def _request(self, step: int, reason: str, detail: dict) -> None:
        self._c_triggers.inc()
        if self._captures >= self.max_captures:
            if not self._budget_latched:
                self._budget_latched = True
                self._journal(reason, "skipped_budget", step=step,
                              budget=self.max_captures, **detail)
            return
        if step < self._cooldown_until:
            if not self._skip_latched:
                self._skip_latched = True
                self._journal(reason, "skipped_cooldown", step=step,
                              cooldown_until=self._cooldown_until, **detail)
            return
        if self._armed is None:
            self._armed = (reason, detail)

    def _start(self, step: int, reason: str, stop_at: int,
               **detail) -> bool:
        global _capture_active
        with _capture_lock:
            if _capture_active:
                self._journal(reason, "skipped_inflight", step=step,
                              **detail)
                return False
            _capture_active = True
        self._seq += 1
        d = os.path.join(self.profile_dir, f"cap-{self._seq:03d}-{reason}")
        try:
            os.makedirs(d, exist_ok=True)
            self._capture_since_ns = start_profiler(d)
        except Exception as e:
            _release_capture()
            self._journal(reason, "failed", step=step,
                          error=f"{type(e).__name__}: {e}", **detail)
            return False
        self._capturing = True
        self._capture_reason = reason
        self._capture_dir = d
        self._capture_start = step
        self._stop_at = int(stop_at)
        if reason != "static_window":
            self._captures += 1  # explicit windows don't spend the budget
        self._c_captures.inc()
        self._journal(reason, "started", step=step, dir=d,
                      stop_at=self._stop_at, **detail)
        return True

    def _stop(self, step: Optional[int], outcome: str) -> None:
        try:
            if self.fence is not None:
                self.fence()
        except Exception:
            pass
        try:
            import jax

            jax.profiler.stop_trace()
            # the loop's spans during the capture, on its clock: what a
            # reader lays over the device's idle gaps (tools/trace_digest)
            write_capture_spans(self._capture_dir, self._capture_since_ns,
                                time.time_ns())
        except Exception:
            pass
        finally:
            _release_capture()
        self._capturing = False
        end = self._steps if step is None else int(step)
        if self._capture_reason != "static_window":
            # like the budget, the cooldown is spent only by TRIGGERED
            # captures: an explicitly requested static window must not
            # blind the anomaly policy for cooldown_steps after it ends
            self._cooldown_until = end + self.cooldown_steps
            self._skip_latched = False
        self._journal(self._capture_reason, outcome, step=end,
                      dir=self._capture_dir,
                      captured_steps=max(0, end - self._capture_start))

    def interrupt(self) -> None:
        """Stop an in-flight capture without disabling the profiler (the
        epoch-driver teardown path); idempotent."""
        if self._capturing:
            self._stop(None, "closed_early")

    def close(self) -> None:
        """Terminal: stop any in-flight capture and refuse further work.
        Safe to call twice (Trainer.close is idempotent)."""
        self.interrupt()
        self._closed = True

    # -- journal ------------------------------------------------------------

    def _journal(self, reason: str, outcome: str, **fields) -> None:
        if self.journal is not None:
            try:
                self.journal.write("profile_capture", reason=reason,
                                   outcome=outcome, **fields)
            except Exception:
                pass


def _num(v) -> Optional[float]:
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def _zscore(window: deque, value: Optional[float],
            min_history: int) -> Optional[float]:
    if value is None or len(window) < min_history:
        return None
    mean = sum(window) / len(window)
    var = sum((x - mean) ** 2 for x in window) / len(window)
    std = var ** 0.5
    return (value - mean) / max(std, 1e-9)
