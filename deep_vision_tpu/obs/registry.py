"""Metrics registry: counters, gauges, log-scale histograms.

Dependency-free by design (like core/tensorboard.py): no prometheus_client,
no jax at import time. Metrics are plain host-side objects safe to touch
from data-loader threads; exporters render the whole registry as
Prometheus text exposition format or as one JSONL snapshot line, and both
writers are process-0-only so a multi-host run produces one file, not N.
"""
from __future__ import annotations

import json
import math
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

# A step's metric named `COUNT_PREFIX + name` is a count the model made in
# the step: the trainer sums it into the counter `<name>_total` and logs it
# as `name`.
COUNT_PREFIX = "count/"


def is_primary_host() -> bool:
    """True when this process should own file writers (process 0).

    Lazy jax import: the registry is also used from spawned data workers
    where importing jax would drag in a backend.
    """
    try:
        import jax

        return jax.process_index() == 0
    except Exception:
        return True


def process_suffix() -> str:
    """'.pN' when this process is part of a multi-process run, else ''.

    The multi-host observability contract (journal/trace/flight): with
    more than one `jax.process_count()` every host writes its OWN file at
    `<path>.p<index>` — a follower's telemetry must survive the follower,
    and a shared file would interleave hosts mid-line. Single-process runs
    keep the plain path, so nothing changes for the common case. Lazy jax
    import, like is_primary_host: data workers must not drag in a backend.
    """
    try:
        import jax

        if jax.process_count() > 1:
            return f".p{jax.process_index()}"
    except Exception:
        pass
    return ""


def _fmt_labels(labels: Optional[dict]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    # finiteness first: int(NaN) raises, and a NaN gauge at export time
    # must render (Prometheus accepts the NaN token), not crash the export
    if not math.isfinite(v):
        if v != v:
            return "NaN"
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def default_log_buckets(lo: float = 1e-3, hi: float = 1e5,
                        per_decade: int = 3) -> List[float]:
    """Log-spaced bucket upper bounds covering [lo, hi]."""
    n = int(round(math.log10(hi / lo) * per_decade))
    return [lo * 10 ** (i / per_decade) for i in range(n + 1)]


class Counter:
    """Monotonically increasing count (Prometheus counter semantics)."""

    kind = "counter"

    def __init__(self, name: str, help: str = "", labels: Optional[dict] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease (inc {n})")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def to_prometheus(self) -> List[str]:
        return [f"{self.name}{_fmt_labels(self.labels)} {_fmt_value(self._value)}"]

    def snapshot(self):
        return self._value


class Gauge:
    """Point-in-time value (may go up or down)."""

    kind = "gauge"

    def __init__(self, name: str, help: str = "", labels: Optional[dict] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value

    def to_prometheus(self) -> List[str]:
        return [f"{self.name}{_fmt_labels(self.labels)} {_fmt_value(self._value)}"]

    def snapshot(self):
        return self._value


class Histogram:
    """Cumulative-bucket histogram with log-scale default bounds.

    Step times, data waits, and request latencies span 4+ decades across
    models and hosts — linear buckets would waste resolution at one end;
    the default is 3 buckets per decade from 1e-3 to 1e5 (ms scale).
    """

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Optional[Iterable[float]] = None,
                 labels: Optional[dict] = None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        bounds = sorted(buckets) if buckets else default_log_buckets()
        self.bounds: List[float] = list(bounds)
        self._counts = [0] * (len(self.bounds) + 1)  # +1: the +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        # linear scan: bucket lists are ~25 long and observe() is host-side
        # once per step/request, far off any hot path
        i = 0
        while i < len(self.bounds) and v > self.bounds[i]:
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile estimate (upper bound of the bucket
        holding the q-th observation)."""
        if not self._count:
            return 0.0
        target = q * self._count
        seen = 0
        for i, c in enumerate(self._counts):
            seen += c
            if seen >= target:
                return self.bounds[i] if i < len(self.bounds) else math.inf
        return math.inf

    def to_prometheus(self) -> List[str]:
        lines = []
        cumulative = 0
        for bound, c in zip(self.bounds, self._counts):
            cumulative += c
            lb = dict(self.labels, le=_fmt_value(bound))
            lines.append(f"{self.name}_bucket{_fmt_labels(lb)} {cumulative}")
        lb = dict(self.labels, le="+Inf")
        lines.append(f"{self.name}_bucket{_fmt_labels(lb)} {self._count}")
        lines.append(
            f"{self.name}_sum{_fmt_labels(self.labels)} {_fmt_value(self._sum)}"
        )
        lines.append(
            f"{self.name}_count{_fmt_labels(self.labels)} {self._count}"
        )
        return lines

    def snapshot(self):
        # quantiles above the top bucket are +Inf, which json.dumps would
        # emit as the non-standard `Infinity` token; None keeps the JSONL
        # strict-parser clean (jq, JSON.parse)
        def finite(v):
            return v if math.isfinite(v) else None

        return {
            "count": self._count,
            "sum": self._sum,
            "mean": self.mean,
            "p50": finite(self.quantile(0.5)),
            "p99": finite(self.quantile(0.99)),
        }


class Registry:
    """Named metric store with get-or-create accessors and exporters."""

    def __init__(self):
        self._metrics: Dict[Tuple[str, Tuple], object] = {}
        # the registry-level get-or-create lock is locksmith-named; the
        # per-metric leaf locks (Counter/Gauge/Histogram) stay raw
        # threading.Locks on purpose — they guard single arithmetic ops on
        # the hottest paths, never nest, and carry no ordering information
        from deep_vision_tpu.obs import locksmith

        self._lock = locksmith.lock("obs.registry")

    def _get_or_create(self, cls, name: str, help: str,
                       labels: Optional[dict], **kw):
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, help=help, labels=labels, **kw)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {cls.kind}"
                )
            return m

    def counter(self, name: str, help: str = "",
                labels: Optional[dict] = None) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Optional[dict] = None) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  buckets: Optional[Iterable[float]] = None,
                  labels: Optional[dict] = None) -> Histogram:
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def metrics(self) -> List[object]:
        with self._lock:
            return list(self._metrics.values())

    # -- exporters ---------------------------------------------------------

    def to_prometheus(self) -> str:
        """Prometheus text exposition format: one HELP/TYPE block per
        metric family with ALL its label variants contiguous under it —
        the spec forbids a family's lines being interleaved with another's
        (creation order would do that, e.g. latency{task=a}, requests,
        latency{task=b})."""
        families: Dict[str, List[object]] = {}
        for m in self.metrics():
            families.setdefault(m.name, []).append(m)
        lines: List[str] = []
        for name, members in families.items():
            head = members[0]
            if head.help:
                lines.append(f"# HELP {name} {head.help}")
            lines.append(f"# TYPE {name} {head.kind}")
            for m in members:
                lines.extend(m.to_prometheus())
        return "\n".join(lines) + ("\n" if lines else "")

    def snapshot(self) -> dict:
        out: dict = {}
        for m in self.metrics():
            key = m.name + _fmt_labels(m.labels)
            out[key] = m.snapshot()
        return out

    def write_prometheus(self, path: str) -> bool:
        """Atomic-ish whole-file write; process-0-only. Returns written."""
        if not is_primary_host():
            return False
        import os

        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_prometheus())
        os.replace(tmp, path)
        return True

    def append_jsonl_snapshot(self, path: str, **extra) -> bool:
        """Append one snapshot line (timestamped); process-0-only."""
        if not is_primary_host():
            return False
        import os

        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        row = {"ts": time.time(), "metrics": self.snapshot()}
        row.update(extra)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        return True


_DEFAULT = Registry()


def get_registry() -> Registry:
    """The process-wide default registry (trainer, data, inference all
    report here unless handed an explicit one)."""
    return _DEFAULT
