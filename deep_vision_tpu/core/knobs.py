"""Central registry of `DVT_*` environment knobs.

Before this module, 14 knobs were scattered across 12 files, each with
its own parse idiom: DVT_NMS_IMPL raised on a typo (the convention worth
keeping — a triage knob that silently no-ops defeats its purpose),
DVT_LOCKSMITH_HOLD_MS fed `float()` raw (garbage = unhandled
ValueError deep in `arm_from_env`), DVT_TELEMETRY warned, and a kernel
switch treated ANY value — including the empty string — as truthy
unless it happened to be "0"/"false"/"off". This module is the
single source of truth the DV203 lint rule enforces: every `DVT_*` read
in the tree must go through a typed helper here, and every name a
helper is given must be declared in `KNOBS`.

Parse contract ("mistype raises", the DVT_NMS_IMPL precedent):

  - unset, or set to whitespace/empty -> the registered default;
  - a value that does not parse as the knob's kind -> `KnobError`
    (a ValueError), never a silent fallback;
  - a helper called with the wrong kind for a knob, or an unregistered
    name -> `KnobError` at the call site, so the registry cannot rot.

Stdlib-only by design: resilience/rendezvous.py and resilience/faults.py
read knobs before (or instead of) paying the jax import.

`python -m deep_vision_tpu.lint --knobs` prints `format_knob_table()`;
the README "Environment knobs" section mirrors it (tests assert the
README lists every registered name).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

__all__ = [
    "Knob",
    "KnobError",
    "KNOBS",
    "get_int",
    "get_float",
    "get_flag",
    "get_choice",
    "get_str",
    "knob_table",
    "format_knob_table",
]


class KnobError(ValueError):
    """A knob read failed loudly: unparseable value, unregistered name,
    or a typed helper applied to a knob of another kind."""


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    kind: str  # "int" | "float" | "flag" | "choice" | "str"
    default: object
    doc: str
    choices: Tuple[str, ...] = ()


def _k(name: str, kind: str, default, doc: str,
       choices: Tuple[str, ...] = ()) -> Knob:
    return Knob(name=name, kind=kind, default=default, doc=doc,
                choices=choices)


#: every `DVT_*` environment variable the tree reads, in one place.
#: DV203 (lint/distlint.py) fails any `os.environ` read of a `DVT_*`
#: name outside this module, and any helper call naming a knob that is
#: not declared here.
KNOBS: Dict[str, Knob] = {k.name: k for k in (
    _k("DVT_ALERT_BURN", "float", 2.0,
       "Burn-rate multiplier for obs/alerts.py rules: an error budget "
       "is 'burning' when the bad ratio exceeds budget * this in both "
       "the fast and slow windows."),
    _k("DVT_ALERT_ERROR_BUDGET", "float", 0.01,
       "Serving error budget (fraction of transport_request rows that "
       "may be 5xx/torn) the serve_error_burn rule guards."),
    _k("DVT_ALERT_FAST_S", "float", 5.0,
       "Fast window (seconds of event time) for burn-rate alert rules "
       "(obs/alerts.py) — the page-quickly half of the pair."),
    _k("DVT_ALERT_GOODPUT_FLOOR", "float", 0.0,
       "Goodput floor: mean goodput_frac over the slow window below "
       "this fires the goodput_floor alert; 0 disables the rule."),
    _k("DVT_ALERT_LATENCY_BUDGET_MS", "float", 0.0,
       "Serving latency budget (ms): ok-request p95 over the slow "
       "window above this fires serve_latency_budget; 0 disables."),
    _k("DVT_ALERT_RECOMPILE_BURST", "int", 8,
       "Recompile burst bound: more than this many new recompiles "
       "within the slow window fires recompile_burst; 0 disables."),
    _k("DVT_ALERT_SLOW_S", "float", 60.0,
       "Slow window (seconds of event time) for alert rules "
       "(obs/alerts.py) — the don't-page-on-a-blip half."),
    _k("DVT_ALERT_STARVATION_FRAC", "float", 0.0,
       "Data-starvation bound: fraction of steps in the slow window "
       "with data_wait_ms > dispatch_ms above this fires "
       "data_starvation; 0 disables the rule."),
    _k("DVT_COLLECTIVE_DEADLINE_S", "float", 600.0,
       "Deadline (seconds) for the raw-jax fallback collectives in "
       "parallel/multihost.py; a barrier blocked past this declares a "
       "lost peer instead of hanging forever."),
    _k("DVT_EXCACHE", "str", None,
       "Executable-cache directory (core/excache.py) used when "
       "--executable-cache is absent; empty/unset disables the cache."),
    _k("DVT_FAULT_SEED", "int", 0,
       "RNG seed for the resilience/faults.py injector; exported with "
       "the spec so spawned data-loader workers draw the same faults."),
    _k("DVT_FAULT_SPEC", "str", None,
       "Fault-injection spec (resilience/faults.py), inherited by "
       "spawned worker processes at import time."),
    _k("DVT_FLASH_MIN_TOKENS", "int", 1024,
       "Flash-attention routing floor: sequences at least this many "
       "tokens route onto the Pallas kernel (ops/pallas/"
       "flash_attention.py); lower routes shorter sequences onto it."),
    _k("DVT_GOODPUT_INTERVAL_S", "float", 30.0,
       "Cadence (seconds) of the live GoodputMeter's goodput_interval "
       "journal events (obs/goodput.py)."),
    _k("DVT_HOST_SMOKE_DEBUG", "flag", False,
       "Arm faulthandler periodic stack dumps in tools/host_smoke.py "
       "worker processes (hang triage)."),
    _k("DVT_LOCKSMITH", "flag", False,
       "Arm the locksmith runtime lock-order sanitizer "
       "(obs/locksmith.py) — set in serve/chaos/data smoke children."),
    _k("DVT_LOCKSMITH_HOLD_MS", "float", 1000.0,
       "Locksmith hold-time outlier threshold in milliseconds; holds "
       "past this emit a typed lock_contention event."),
    _k("DVT_LOCKSMITH_WAIT_MS", "float", 1000.0,
       "Locksmith acquire-wait outlier threshold in milliseconds."),
    _k("DVT_NMS_IMPL", "choice", None,
       "Force the NMS selection backend (ops/nms.py); unset = auto "
       "(pallas when the backend compiles Pallas, lax elsewhere).",
       choices=("lax", "pallas")),
    _k("DVT_PREFLIGHT_BUDGET_S", "float", 60.0,
       "Per-probe time budget (seconds) for tools/preflight.py backend "
       "checks; raise it for a slow cold start."),
    _k("DVT_RDZV_GENERATION", "int", None,
       "Rendezvous generation to re-attach to (resilience/"
       "rendezvous.py) — set for re-exec'd host agents."),
    _k("DVT_TELEMETRY", "int", None,
       "Telemetry HTTP port used when --telemetry-port is absent; "
       "0 binds a free port."),
    _k("DVT_TRANSPORT_DEADLINE_MS", "float", 0.0,
       "Default request deadline (milliseconds) the serving front door "
       "(serve/transport.py) applies to requests that carry no "
       "X-DVT-Deadline-Ms header; 0 means no default deadline."),
    _k("DVT_TRANSPORT_RETRY_AFTER_MS", "float", 50.0,
       "Retry-After hint (milliseconds) the front door attaches to 429/"
       "503 shed responses; the loadgen socket client sleeps exactly "
       "this before retrying."),
)}

_UNSET = object()

_TRUE = ("1", "true", "on", "yes")
_FALSE = ("0", "false", "off", "no")


def _lookup(name: str, kind: str) -> Knob:
    knob = KNOBS.get(name)
    if knob is None:
        raise KnobError(
            f"{name} is not a registered knob — declare it in "
            "deep_vision_tpu/core/knobs.py KNOBS (DV203)")
    if knob.kind != kind:
        raise KnobError(
            f"{name} is registered as a {knob.kind!r} knob, not "
            f"{kind!r} — use get_{knob.kind}()")
    return knob


def _raw(name: str) -> Optional[str]:
    """The raw env value, with unset and empty/whitespace both mapping
    to None (= use the default) — `DVT_EXCACHE=""` must disable the
    cache, not name a cache directory called ''."""
    v = os.environ.get(name)
    if v is None or not v.strip():
        return None
    return v


def _default(knob: Knob, default):
    return knob.default if default is _UNSET else default


def get_int(name: str, default=_UNSET) -> Optional[int]:
    knob = _lookup(name, "int")
    v = _raw(name)
    if v is None:
        return _default(knob, default)
    try:
        return int(v)
    except ValueError:
        raise KnobError(
            f"{name}={v!r} is not an integer — {knob.doc}") from None


def get_float(name: str, default=_UNSET) -> Optional[float]:
    knob = _lookup(name, "float")
    v = _raw(name)
    if v is None:
        return _default(knob, default)
    try:
        return float(v)
    except ValueError:
        raise KnobError(
            f"{name}={v!r} is not a number — {knob.doc}") from None


def get_flag(name: str, default=_UNSET) -> Optional[bool]:
    knob = _lookup(name, "flag")
    v = _raw(name)
    if v is None:
        return _default(knob, default)
    low = v.strip().lower()
    if low in _TRUE:
        return True
    if low in _FALSE:
        return False
    raise KnobError(
        f"{name}={v!r} is not a flag value "
        f"({'/'.join(_TRUE)} or {'/'.join(_FALSE)}) — {knob.doc}")


def get_choice(name: str, default=_UNSET) -> Optional[str]:
    knob = _lookup(name, "choice")
    v = _raw(name)
    if v is None:
        return _default(knob, default)
    if v not in knob.choices:
        # NO normalization: 'LAX' / 'lax ' raising is the point — a
        # triage knob that silently runs the suspect default defeats it
        raise KnobError(
            f"{name}={v!r} is not one of {'|'.join(knob.choices)} — "
            f"{knob.doc}")
    return v


def get_str(name: str, default=_UNSET) -> Optional[str]:
    knob = _lookup(name, "str")
    v = _raw(name)
    if v is None:
        return _default(knob, default)
    return v


def knob_table() -> List[Knob]:
    return [KNOBS[name] for name in sorted(KNOBS)]


def format_knob_table() -> str:
    """The human-readable registry dump behind
    `python -m deep_vision_tpu.lint --knobs`."""
    rows = []
    for knob in knob_table():
        kind = knob.kind
        if knob.choices:
            kind = f"{kind}({'|'.join(knob.choices)})"
        default = "unset" if knob.default is None else repr(knob.default)
        rows.append((knob.name, kind, default, knob.doc))
    w_name = max(len(r[0]) for r in rows)
    w_kind = max(len(r[1]) for r in rows)
    w_def = max(len(r[2]) for r in rows)
    lines = [f"{'knob':<{w_name}}  {'kind':<{w_kind}}  "
             f"{'default':<{w_def}}  doc"]
    for name, kind, default, doc in rows:
        lines.append(f"{name:<{w_name}}  {kind:<{w_kind}}  "
                     f"{default:<{w_def}}  {doc}")
    return "\n".join(lines)
