"""Resilience primitives: retrying I/O + deterministic fault injection.

The layer that lets the trainer treat storage and transport as unreliable
by design (ROADMAP north star: survive production traffic, not just a
clean lab run):

- `retry`:  `RetryPolicy` — exponential backoff + jitter, deadline,
  retryable-exception classification; decorator / driver / attempt-loop
  forms; typed `retry` journal events and metrics counters. Shared by
  the Trainer's rebuild-replay loop, the checkpoint sidecar writer, and
  shard opens in the tolerant record reader.
- `elastic`: the accelerator-layer arc — backend-failure classification
  (connection loss / hung-backend timeout / libtpu version skew),
  `BackendSupervisor` rebuild-replay choreography with typed
  `backend_lost`/`backend_recovered` journal events, cross-mesh
  checkpoint sharding metadata (restore a run saved on N devices onto
  M), and the threaded `backend_alive` liveness probe
  `tools/preflight.py` runs.
- `rendezvous`: the multi-HOST half of the elastic arc — file-backed
  generation-numbered membership (heartbeat leases, deadline-bounded
  barriers/consensus, join-time version handshake), `HostSupervisor`
  journaling typed `host_lost`/`host_joined`/`world_resized` events,
  and the bounded device fence that turns a peer SIGKILLed
  mid-collective into a typed error instead of an indefinite hang.
- `faults`: `FaultInjector` — seeded, deterministic faults driven by a
  `--fault-spec` string, with named injection points at every I/O
  boundary that cost one None-check when disabled. The mechanism behind
  `make chaos-smoke` and the crash-consistency tests.

Consumers of the skipping/quarantine behaviors these enable live next to
their data: the bad-record budget + dead-letter writer in
`data/records.py`, checkpoint quarantine in `core/checkpoint.py`.

jax-free at import (like obs/registry) so spawned data workers can use
both without dragging in a backend.
"""
from deep_vision_tpu.resilience.elastic import (
    BACKEND_LOST_KINDS,
    BackendSupervisor,
    backend_alive,
    classify_backend_error,
    replace_on_mesh,
    sharding_meta,
)
from deep_vision_tpu.resilience.faults import (
    ENV_SEED,
    ENV_SPEC,
    FaultInjected,
    FaultInjector,
    FaultSpecError,
    fire,
    install,
    install_spec,
    installed,
    transform,
)
from deep_vision_tpu.resilience.rendezvous import (
    HostLostError,
    HostSupervisor,
    Rendezvous,
    RendezvousError,
    RendezvousRefused,
    RendezvousTimeout,
    WorldResized,
    WorldView,
)
from deep_vision_tpu.resilience.retry import DEFAULT_RETRY_ON, RetryPolicy

__all__ = [
    "HostLostError",
    "HostSupervisor",
    "Rendezvous",
    "RendezvousError",
    "RendezvousRefused",
    "RendezvousTimeout",
    "WorldResized",
    "WorldView",
    "BACKEND_LOST_KINDS",
    "BackendSupervisor",
    "DEFAULT_RETRY_ON",
    "ENV_SEED",
    "ENV_SPEC",
    "FaultInjected",
    "FaultInjector",
    "FaultSpecError",
    "RetryPolicy",
    "backend_alive",
    "classify_backend_error",
    "fire",
    "install",
    "install_spec",
    "installed",
    "replace_on_mesh",
    "sharding_meta",
    "transform",
]
