"""Shared retry policy: exponential backoff + jitter, deadline, typed events.

Production checkpoint/data systems treat storage and transport as
unreliable by design (Check-N-Run, NSDI '22; Varuna, EuroSys '22).
`RetryPolicy` is the one implementation every I/O boundary shares — the
Trainer's backend rebuild-replay loop (`elastic.BackendSupervisor`), the
checkpoint sidecar writer, and shard opens in the tolerant record reader
all consult it — so backoff behavior, exception classification, and the
`retry` journal event schema cannot drift between callers.

Three usage shapes:

    policy = RetryPolicy(name="ckpt.sidecar", max_attempts=4)

    # 1. driver: call through the policy
    policy.call(write_file, path, data)

    # 2. decorator
    @policy
    def write_file(path, data): ...

    # 3. attempt loop (tenacity-style), for bodies that need local state
    for attempt in policy.attempts():
        with attempt:
            write_file(path, data)

Every failed-then-retried attempt emits a typed `retry` journal event
(when a journal is attached) and bumps `retry_attempts_total{policy=}`;
a giveup bumps `retry_giveups_total{policy=}` and re-raises the last
exception unchanged (callers keep their existing except clauses).
Jitter is drawn from a policy-owned seeded RNG so tests are
deterministic; pass `jitter=0` to disable entirely.
"""
from __future__ import annotations

import random
import time
from typing import Callable, Iterator, Optional, Tuple, Type, Union

_RetryOn = Union[Type[BaseException], Tuple[Type[BaseException], ...]]

#: the default classification: transient-looking I/O and transport errors.
#: RuntimeError is NOT here — jax wraps both transient runtime failures and
#: genuine program bugs in it; callers that know better (bench) pass
#: retry_on=Exception explicitly.
DEFAULT_RETRY_ON: Tuple[Type[BaseException], ...] = (
    OSError,  # includes IOError, ConnectionError, TimeoutError(OSError)
    TimeoutError,
)


class RetryPolicy:
    """Backoff schedule + retryable-exception classification + budget.

    name:          labels journal events and metrics counters.
    max_attempts:  total tries including the first (<=0 means "no retries").
    base_delay_s / multiplier / max_delay_s: exponential backoff envelope
                   (delay before retry k is base * multiplier**(k-1), capped).
    jitter:        +-fraction applied to each delay (0.5 -> 50%-150%).
    deadline_s:    wall budget for one call()/attempts() session; when the
                   NEXT delay would cross it, give up instead of sleeping.
    retry_on:      exception class(es) considered transient.
    retry_if:      optional predicate(exc) -> bool consulted when the class
                   check fails (e.g. match "UNAVAILABLE" in the message).
    journal:       obs.RunJournal (or None) for typed `retry` events.
    registry:      obs Registry; defaults to the process-wide one, lazily.
    sleep/clock:   injectable for tests.
    """

    def __init__(
        self,
        name: str = "default",
        max_attempts: int = 5,
        base_delay_s: float = 0.5,
        multiplier: float = 2.0,
        max_delay_s: float = 30.0,
        jitter: float = 0.5,
        deadline_s: Optional[float] = None,
        retry_on: _RetryOn = DEFAULT_RETRY_ON,
        retry_if: Optional[Callable[[BaseException], bool]] = None,
        journal=None,
        registry=None,
        seed: int = 0,
        sleep: Callable[[float], None] = time.sleep,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.name = name
        self.max_attempts = int(max_attempts)
        self.base_delay_s = float(base_delay_s)
        self.multiplier = float(multiplier)
        self.max_delay_s = float(max_delay_s)
        self.jitter = float(jitter)
        self.deadline_s = deadline_s
        self.retry_on = retry_on
        self.retry_if = retry_if
        self.journal = journal
        self._registry = registry
        self._rng = random.Random(seed)
        self._sleep = sleep
        self._clock = clock

    # -- classification / schedule (pure; shared by all three shapes) -------

    def classify(self, exc: BaseException) -> bool:
        """Is this exception retryable under the policy?"""
        if isinstance(exc, (KeyboardInterrupt, SystemExit)):
            return False  # never eat an operator interrupt or a crash fault
        if isinstance(exc, self.retry_on):
            return True
        return bool(self.retry_if is not None and self.retry_if(exc))

    def delay(self, attempt: int) -> float:
        """Backoff before retry number `attempt` (1-based), jittered."""
        d = self.base_delay_s * self.multiplier ** max(0, attempt - 1)
        d = min(d, self.max_delay_s)
        if self.jitter:
            d *= 1.0 + self.jitter * (2.0 * self._rng.random() - 1.0)
        return max(0.0, d)

    def should_retry(self, attempt: int, exc: BaseException) -> bool:
        """Budget + classification in one check: `attempt` failures so far."""
        return attempt < self.max_attempts and self.classify(exc)

    def backoff(self, attempt: int) -> float:
        """Sleep the schedule's delay for retry `attempt`; returns it."""
        d = self.delay(attempt)
        if d > 0:
            self._sleep(d)
        return d

    # -- event plumbing ------------------------------------------------------

    def _counter(self, which: str):
        reg = self._registry
        if reg is None:
            from deep_vision_tpu.obs.registry import get_registry

            reg = get_registry()
        return reg.counter(f"retry_{which}_total",
                           f"RetryPolicy {which}", labels={"policy": self.name})

    def note(self, attempt: int, exc: BaseException, outcome: str,
             delay_s: float = 0.0) -> None:
        """Emit one typed `retry` journal event + the matching counter.

        outcome: 'retrying' (will try again), 'gave_up' (budget/classifier
        stopped it), 'recovered' (a later attempt succeeded).
        """
        which = {"retrying": "attempts", "gave_up": "giveups",
                 "recovered": "recoveries"}[outcome]
        try:
            self._counter(which).inc()
        except Exception:
            pass  # metrics must never turn a retry into a crash
        if self.journal is not None:
            self.journal.write(
                "retry", name=self.name, attempt=int(attempt),
                error=f"{type(exc).__name__}: {exc}"[:500],
                outcome=outcome, delay_s=round(float(delay_s), 3),
            )

    # -- drivers -------------------------------------------------------------

    def call(self, fn: Callable, *args, **kwargs):
        """Run fn(*args, **kwargs) under the policy; the terminal exception
        (non-retryable, or budget/deadline exhausted) re-raises unchanged."""
        start = self._clock()
        attempt = 0
        while True:
            try:
                result = fn(*args, **kwargs)
            except BaseException as e:  # noqa: BLE001 - classified below
                attempt += 1
                if not self.should_retry(attempt, e):
                    self.note(attempt, e, "gave_up")
                    raise
                d = self.delay(attempt)
                if (self.deadline_s is not None
                        and self._clock() - start + d > self.deadline_s):
                    self.note(attempt, e, "gave_up")
                    raise
                self.note(attempt, e, "retrying", delay_s=d)
                if d > 0:
                    self._sleep(d)
                continue
            if attempt:
                self.note(attempt, _Recovered(), "recovered")
            return result

    def __call__(self, fn: Callable) -> Callable:
        """Decorator form: `@policy` wraps fn in call()."""
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            return self.call(fn, *args, **kwargs)

        wrapped.retry_policy = self
        return wrapped

    def attempts(self) -> Iterator["_Attempt"]:
        """Attempt-loop form: yields context managers until one succeeds.

        The with-block's exception is swallowed while the policy admits a
        retry, re-raised otherwise; a block that exits cleanly ends the loop.
        """
        start = self._clock()
        attempt = 0
        while True:
            a = _Attempt()
            yield a
            if a.succeeded:
                if attempt:
                    self.note(attempt, _Recovered(), "recovered")
                return
            exc = a.exc
            attempt += 1
            if not self.should_retry(attempt, exc):
                self.note(attempt, exc, "gave_up")
                raise exc
            d = self.delay(attempt)
            if (self.deadline_s is not None
                    and self._clock() - start + d > self.deadline_s):
                self.note(attempt, exc, "gave_up")
                raise exc
            self.note(attempt, exc, "retrying", delay_s=d)
            if d > 0:
                self._sleep(d)


class _Recovered(Exception):
    """Placeholder 'exception' for the recovered event (no live error)."""

    def __str__(self):
        return "recovered"


class _Attempt:
    """One try of an attempts() loop; captures the body's exception."""

    def __init__(self):
        self.exc: Optional[BaseException] = None
        self.succeeded = False

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.succeeded = True
            return False
        self.exc = exc
        return True  # swallowed; attempts() decides whether to re-raise
