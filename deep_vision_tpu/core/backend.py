"""Backend registry: the ONE module allowed to compare platform strings.

ROADMAP item 4 (multi-backend PJRT seam), first concrete step. Before
this module, "platform" was an implicit axis enforced by convention:
eight call sites across ops/pallas/, ops/nms.py, parallel/ and models/
each hand-rolled `jax.default_backend() == "tpu"` to decide whether a
Pallas kernel compiles natively or must run interpreted, and which NMS
selection backend is the default. The DV201 lint rule
(lint/distlint.py) now fails any such comparison OUTSIDE this module;
routing decisions read a `BackendProfile` instead, so adding a new
PJRT platform is one table row here, not a grep across the tree. A
platform WITHOUT a row is an error naming it: routing an unknown device
like the CPU (interpreted Pallas, lax NMS) would run, and would hide the
device from every number measured on it.

Deliberately NOT wrapped: telemetry/fingerprint call sites that only
RECORD the platform string (obs/journal.py run manifests, excache
fingerprints, preflight detail lines) — recording is not routing, and
DV201 only fires on comparisons.

jax is imported lazily so stdlib-only consumers (lint, tools) can
import the module without paying the jax tax.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

__all__ = [
    "BackendProfile",
    "BACKENDS",
    "current_platform",
    "get_backend",
    "is_tpu",
    "local_tpu_chips",
    "backend_initialized",
    "pallas_interpret",
    "default_nms_impl",
]


@dataclasses.dataclass(frozen=True)
class BackendProfile:
    """What the stack needs to know about one PJRT platform to route
    work — capabilities, not a platform name to compare against."""

    name: str
    #: Mosaic compiles Pallas kernels natively; elsewhere they run
    #: under `interpret=True` (the CPU test path).
    pallas_compiled: bool
    #: default NMS selection backend (ops/nms.py `impl='auto'`).
    nms_impl: str


BACKENDS: Dict[str, BackendProfile] = {
    "tpu": BackendProfile(name="tpu", pallas_compiled=True,
                          nms_impl="pallas"),
    "cpu": BackendProfile(name="cpu", pallas_compiled=False,
                          nms_impl="lax"),
}


def current_platform() -> str:
    """The active PJRT platform name (`jax.default_backend()`)."""
    import jax

    return jax.default_backend()


def get_backend() -> BackendProfile:
    platform = current_platform()
    try:
        return BACKENDS[platform]
    except KeyError:
        raise RuntimeError(
            f"no BackendProfile for PJRT platform {platform!r} (known: "
            f"{sorted(BACKENDS)}); add a row to core/backend.py BACKENDS "
            "saying how Pallas and NMS route there") from None


def is_tpu() -> bool:
    return current_platform() == "tpu"


def local_tpu_chips() -> int:
    """TPU chips this process would get, counted WITHOUT initialising a
    backend (the PCI scan jax itself starts from), so a parent that must
    leave the chips to its children can ask. 0 when the platform selection
    (JAX_PLATFORMS / jax_platforms) leaves the TPU out."""
    import jax
    from jax._src import hardware_utils

    selected = jax.config.jax_platforms
    if selected and "tpu" not in selected.split(","):
        return 0
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def backend_initialized() -> bool:
    """Has this process already created a PJRT client? On a TPU host that
    means it holds the chips: a child that needs one then fails or hangs."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def pallas_interpret() -> bool:
    """Should Pallas kernels run under `interpret=True`? The default
    for every `interpret=None` kernel entry point."""
    return not get_backend().pallas_compiled


def default_nms_impl() -> str:
    return get_backend().nms_impl
