"""Elastic, accelerator-layer resilience: survive the fleet, not just the step.

PR 4 made storage and data I/O unreliable-by-design; one layer down, the
accelerator fails in its own ways: a backend connection drops mid-run, a
backend stops answering without raising, a libtpu client/terminal version
skew kills the first dispatch minutes into a compile.

This module is the shared substrate for treating those as *expected
inputs*:

- `classify_backend_error`: one classification for every consumer —
  `connection_lost` / `timeout` (retryable: rebuild the client and
  replay), `version_skew` (NOT retryable: a skew does not heal mid-run —
  fail fast, that is `tools/preflight.py`'s job to catch before minutes
  are burned), `unknown` (a program bug wearing a RuntimeError: never
  retried, it propagates).
- `BackendSupervisor`: the rebuild-replay choreography in one object: a
  single `RetryPolicy` holds the backoff jitter RNG (one draw per
  backoff), failures journal typed `backend_lost` events and recoveries
  `backend_recovered`, with flight recorder breadcrumbs on both. The
  Trainer drives it.
- cross-mesh sharding metadata (`sharding_meta` / `replace_on_mesh`):
  serializable leaf-level PartitionSpecs saved in the checkpoint sidecar
  so a run checkpointed on N hosts/devices restores onto M — specs are
  re-resolved against the *current* mesh, dropping axes the new topology
  cannot honor (axis absent, or dim no longer divisible) per dimension.
- `backend_alive`: the one budgeted liveness probe, threaded so that a
  backend that blocks without raising is still seen (by the join
  timeout); `tools/preflight.py` runs it.

jax-free at import (the resilience/ contract — spawned data workers
import this package): jax is imported inside the functions that need it.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from deep_vision_tpu.resilience.retry import RetryPolicy

# -- backend failure classification -------------------------------------------

#: classification kinds; `backend_lost` journal events carry one of these
#: and tools/check_journal.py --strict enforces the enum
KIND_CONNECTION = "connection_lost"
KIND_TIMEOUT = "timeout"
KIND_VERSION_SKEW = "version_skew"
KIND_UNKNOWN = "unknown"
BACKEND_LOST_KINDS = (KIND_CONNECTION, KIND_TIMEOUT, KIND_VERSION_SKEW,
                      KIND_UNKNOWN)
#: kinds a rebuild-and-replay can actually heal
RETRYABLE_KINDS = (KIND_CONNECTION, KIND_TIMEOUT)

#: message fingerprints, checked lowercased. Version skew FIRST: the
#: skew error ("FAILED_PRECONDITION: libtpu version mismatch: terminal
#: has ..., client AOT libtpu has ...") also mentions the word "client",
#: which must not fall through to a connection match.
_VERSION_PATTERNS = (
    "libtpu version mismatch",
    "version mismatch",
    "incompatible libtpu",
)
_TIMEOUT_PATTERNS = (
    "deadline_exceeded",
    "deadline exceeded",
    "timed out",
    "timeout",
    "heartbeat",
    "liveness probe still blocked",  # backend_alive's hung-backend verdict
)
_CONNECTION_PATTERNS = (
    "connection reset",
    "connection refused",
    "connection closed",
    "connection aborted",
    "backend connection",
    "body closed",
    "socket closed",
    "broken pipe",
    "unavailable",
)


def classify_backend_error(exc) -> str:
    """Classify an exception (or message string) from the accelerator layer.

    Returns one of `BACKEND_LOST_KINDS`. The exception TYPE gates the
    message match: jax wraps every backend/transport failure in
    RuntimeError (JaxRuntimeError/XlaRuntimeError subclass it), so only
    RuntimeErrors may classify as a lost backend. Everything else is
    `unknown` no matter what its message says — a ValueError mentioning
    'timeout' in a file name must not become retryable, and a raw
    OSError/ConnectionError is the STORAGE/data layer's weather (its own
    RetryPolicy already absorbed what it could; tearing down the backend
    over it would trade a read retry for a full restore-and-replay).
    """
    if isinstance(exc, BaseException):
        if not isinstance(exc, RuntimeError):
            return KIND_UNKNOWN
        msg = f"{type(exc).__name__}: {exc}"
    else:
        msg = str(exc)
    low = msg.lower()
    for pat in _VERSION_PATTERNS:
        if pat in low:
            return KIND_VERSION_SKEW
    for pat in _TIMEOUT_PATTERNS:
        if pat in low:
            return KIND_TIMEOUT
    for pat in _CONNECTION_PATTERNS:
        if pat in low:
            return KIND_CONNECTION
    return KIND_UNKNOWN


def backend_alive(budget_s: float, probe=None):
    """(ok, error, kind) — does a trivial device op complete within
    `budget_s`?

    The op runs in a worker thread: a backend that blocks without raising
    cannot be seen by a try/except — a join timeout can. The orphaned
    daemon thread stays blocked; callers report and return, so it never
    wedges teardown.

    `kind` classifies the failure from the EXCEPTION OBJECT the probe
    raised (a hang is `timeout`; None when healthy) —
    re-classifying the formatted message would lose the exception-type
    gate and let a probe bug mentioning 'timeout' impersonate a hung
    backend.
    """
    if probe is None:
        def probe():
            import jax
            import jax.numpy as jnp

            jax.devices()  # backend init is itself part of the handshake
            return float(jnp.ones((), jnp.float32).sum())
    out: Dict[str, Any] = {}

    def run():
        try:
            out["value"] = probe()
        except Exception as e:
            out["exc"] = e

    t = threading.Thread(target=run, daemon=True, name="backend-liveness")
    t.start()
    t.join(budget_s)
    if t.is_alive():
        err = (f"backend liveness probe still blocked after "
               f"{budget_s:.0f}s (backend hung?)")
        return False, err, KIND_TIMEOUT
    if "exc" in out:
        e = out["exc"]
        err = (f"backend liveness probe failed: "
               f"{type(e).__name__}: {e}")
        return False, err, classify_backend_error(e)
    return True, None, None


# -- the rebuild-replay supervisor --------------------------------------------

class BackendSupervisor:
    """Backend-loss detection + rebuild-replay bookkeeping, in one place.

    One supervisor serves one recovery surface (a Trainer.fit): it owns
    the `RetryPolicy` whose jitter RNG advances one draw per backoff,
    journals typed `backend_lost` / `backend_recovered` events, bumps `backend_lost_total{kind=}` /
    `backend_recoveries_total`, and leaves flight-recorder breadcrumbs so
    a degraded-result postmortem shows the recovery attempts that led
    there.

    The caller keeps its own control flow (what "rebuild" and "replay"
    mean is caller-specific — the Trainer re-jits, restores the last
    checkpoint, and replays the epoch); the supervisor decides *whether*
    another attempt is worth it and paces it:

        retrying = sup.on_failure(attempt, exc, step=...)
        if not retrying:
            raise
        sup.recover(attempt)           # breadcrumb + backoff + cache clear
        ... rebuild + replay ...
        sup.on_recovered(attempt, step=...)

    An `unknown` failure is never retried: it is a program bug and must
    propagate. `version_skew` is never retried either: it cannot heal
    mid-run, and burning the retry budget on it is exactly the minutes
    `tools/preflight.py` exists to save.
    """

    def __init__(self, max_retries: int = 5, policy: Optional[RetryPolicy] = None,
                 journal=None, registry=None, name: str = "backend",
                 clear_caches_after: int = 2):
        # max_attempts counts the first try too: max_retries retries on top
        self.policy = policy or RetryPolicy(
            name=name, max_attempts=int(max_retries) + 1, base_delay_s=2.0,
            multiplier=2.0, max_delay_s=15.0, jitter=0.25, journal=journal,
            registry=registry, retry_on=Exception,
        )
        self.name = name
        self.journal = journal if journal is not None else self.policy.journal
        if self.policy.journal is None:
            # one journal serves both event streams: the typed
            # backend_lost/backend_recovered rows AND the shared `retry`
            # rows the policy emits per attempt
            self.policy.journal = self.journal
        self._registry = registry
        self.clear_caches_after = int(clear_caches_after)

    # -- decisions ---------------------------------------------------------

    def classify(self, exc) -> str:
        return classify_backend_error(exc)

    def should_retry(self, attempt: int, exc) -> bool:
        """Budget + classification: is attempt `attempt`'s failure worth a
        rebuild-and-replay?"""
        if isinstance(exc, (KeyboardInterrupt, SystemExit)):
            return False
        kind = self.classify(exc)
        if kind not in RETRYABLE_KINDS:
            # version_skew will not heal (preflight's domain); unknown is
            # a program bug
            return False
        return self.policy.should_retry(attempt, exc)

    # -- event plumbing ----------------------------------------------------

    def _counter(self, name: str, help: str, labels=None):
        reg = self._registry
        if reg is None:
            from deep_vision_tpu.obs.registry import get_registry

            reg = get_registry()
        return reg.counter(name, help, labels=labels)

    def on_failure(self, attempt: int, exc, step: Optional[int] = None,
                   context: Optional[str] = None) -> bool:
        """Record one backend failure; returns whether to retry.

        Journals a typed `backend_lost` event (kind from the classifier),
        bumps `backend_lost_total{kind=}`, breadcrumbs the flight
        recorder, and emits the shared `retry` event so the existing
        retry dashboards see these attempts too.
        """
        kind = self.classify(exc)
        retrying = self.should_retry(attempt, exc)
        try:
            self._counter("backend_lost_total", "backend failures observed",
                          labels={"kind": kind}).inc()
        except Exception:
            pass
        err = f"{type(exc).__name__}: {exc}"[:500] if isinstance(
            exc, BaseException) else str(exc)[:500]
        if self.journal is not None:
            row = {"attempt": int(attempt), "error": err, "kind": kind,
                   "retrying": bool(retrying)}
            if step is not None:
                row["step"] = int(step)
            if context:
                row["context"] = str(context)
            try:
                self.journal.write("backend_lost", **row)
            except Exception:
                pass
        try:
            from deep_vision_tpu.obs import flight as _flight

            _flight.note("backend_lost", attempt=int(attempt), kind=kind,
                         error=err[:200])
        except Exception:
            pass
        if isinstance(exc, BaseException):
            self.policy.note(attempt, exc,
                             "retrying" if retrying else "gave_up")
        return retrying

    def recover(self, attempt: int) -> float:
        """Pace the next rebuild: breadcrumb, the policy's jittered backoff
        (ONE RNG, advancing per draw), and a jax cache clear on later
        attempts (a stale compiled-executable cache can pin a dead client).
        Returns the delay slept."""
        try:
            from deep_vision_tpu.obs import flight as _flight

            _flight.note("backend_recovery", attempt=int(attempt))
        except Exception:
            pass
        delay = self.policy.backoff(attempt)
        if attempt >= self.clear_caches_after:
            try:
                import jax

                jax.clear_caches()
            except Exception:
                pass
        return delay

    def on_recovered(self, attempt: int, step: Optional[int] = None) -> None:
        """The rebuilt backend made real progress again: journal the typed
        `backend_recovered` event and bump the recovery counter."""
        try:
            self._counter("backend_recoveries_total",
                          "successful backend rebuild-replays").inc()
        except Exception:
            pass
        if self.journal is not None:
            row = {"attempt": int(attempt)}
            if step is not None:
                row["step"] = int(step)
            try:
                self.journal.write("backend_recovered", **row)
            except Exception:
                pass
        try:
            from deep_vision_tpu.obs import flight as _flight

            _flight.note("backend_recovered", attempt=int(attempt))
        except Exception:
            pass


# -- cross-mesh sharding metadata ---------------------------------------------

#: reserved sidecar key the checkpoint layer stores the metadata under
SHARDING_META_KEY = "__sharding__"
SHARDING_META_FORMAT = 1


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), x) for p, x in flat]


def sharding_meta(tree) -> dict:
    """Serializable leaf-level sharding record for a pytree of jax.Arrays.

    {"format": 1, "mesh": {axis: size}, "device_count": N,
     "leaves": {keystr_path: [spec entries]}} — spec entries are None, an
    axis name, or a list of axis names (PartitionSpec tuples survive the
    JSON round trip as lists). Leaves without a NamedSharding (host
    numpy, scalars) are simply absent and restore replicated.
    """
    from jax.sharding import NamedSharding

    leaves: Dict[str, list] = {}
    mesh_shape: Optional[Dict[str, int]] = None
    device_count = 0
    for path, x in _leaf_paths(tree):
        s = getattr(x, "sharding", None)
        if not isinstance(s, NamedSharding):
            continue
        leaves[path] = [list(e) if isinstance(e, tuple) else e
                        for e in tuple(s.spec)]
        if mesh_shape is None:
            mesh_shape = {str(k): int(v) for k, v in s.mesh.shape.items()}
            device_count = int(s.mesh.devices.size)
    return {
        "format": SHARDING_META_FORMAT,
        "mesh": mesh_shape or {},
        "device_count": device_count,
        "leaves": leaves,
    }


def _resolve_spec(entries, shape, mesh) -> "Any":
    """A saved leaf spec, re-resolved against the CURRENT mesh.

    Per dimension: keep the recorded axis names only when every one
    exists on the new mesh AND their combined size still divides that
    dimension; otherwise that dimension replicates. A checkpoint from an
    8-device {'data': 4, 'model': 2} mesh restoring under a single
    device thus lands fully replicated — bit-identical values, honest
    placement — instead of crashing on a sharding the hardware no longer
    has.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    out = []
    dropped = 0
    ndim = len(shape)
    for dim in range(ndim):
        entry = entries[dim] if dim < len(entries) else None
        if entry is None:
            out.append(None)
            continue
        names = tuple(entry) if isinstance(entry, (list, tuple)) else (entry,)
        size = 1
        ok = True
        for n in names:
            if n not in mesh.shape:
                ok = False
                break
            size *= int(mesh.shape[n])
        if ok and size > 0 and shape[dim] % size == 0:
            out.append(names[0] if len(names) == 1 else names)
        else:
            out.append(None)
            dropped += 1
    while out and out[-1] is None:
        out.pop()  # canonical short form, like hand-written PartitionSpecs
    return NamedSharding(mesh, PartitionSpec(*out)), dropped


def abstract_template(tree, meta: Optional[dict], mesh):
    """`tree` as jax.ShapeDtypeStructs carrying the meta-resolved TARGET
    shardings for `mesh`.

    Handing this to the checkpoint reader (orbax StandardRestore accepts
    abstract arrays) makes a cross-mesh restore land every array ONCE,
    already placed — restoring onto a concrete replicated template and
    re-placing afterwards would pay double host-to-device traffic and
    peak memory on exactly the path a preemption/requeue window is
    racing. Leaves without recorded metadata restore replicated
    (`meta=None`: the whole tree, matching the legacy layout).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    leaves_meta = (meta or {}).get("leaves", {})
    replicated = NamedSharding(mesh, PartitionSpec())

    def make(path, x):
        shape = tuple(getattr(x, "shape", ()))
        entries = leaves_meta.get(path)
        sharding = (_resolve_spec(entries, shape, mesh)[0] if entries
                    else replicated)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [make(jax.tree_util.keystr(p), x) for p, x in flat])


def replace_on_mesh(tree, meta: Optional[dict], mesh):
    """Re-place every leaf of `tree` on `mesh` per the saved metadata.

    Returns (placed_tree, stats): leaves with a recorded spec go back to
    that layout (re-resolved for the current topology), everything else
    replicates. `meta=None` (a pre-metadata checkpoint) places the whole
    tree replicated — exactly what the trainer's legacy restore did.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    leaves_meta = (meta or {}).get("leaves", {})
    replicated = NamedSharding(mesh, PartitionSpec())
    stats = {"placed": 0, "resharded": 0, "dropped_dims": 0}

    def place(path, x):
        entries = leaves_meta.get(path)
        stats["placed"] += 1
        if entries:
            sharding, dropped = _resolve_spec(entries, getattr(x, "shape", ()),
                                              mesh)
            stats["dropped_dims"] += dropped
            if tuple(sharding.spec):
                stats["resharded"] += 1
            return jax.device_put(x, sharding)
        return jax.device_put(x, replicated)

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    placed = [place(jax.tree_util.keystr(p), x) for p, x in flat]
    return jax.tree_util.tree_unflatten(treedef, placed), stats
