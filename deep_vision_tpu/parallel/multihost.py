"""Multi-host distributed runtime: initialization, global mesh, host sync.

The reference advertises but never ships multi-host training (`train_dist.py`
is referenced at ResNet/pytorch/README.md:15 and absent — SURVEY.md §2.9);
its real distributed story is single-host NCCL via MirroredStrategy
(YOLO/tensorflow/train.py:281). The TPU-native equivalent is radically
simpler: every host runs the SAME SPMD program, `jax.distributed.initialize`
wires the cluster, the mesh spans all hosts' devices, and XLA routes
collectives over ICI within a slice and DCN across slices. There is no
NCCL/MPI code to write — the comm backend IS the mesh + partitioner.

Elastic overlay (resilience/rendezvous.py): with a generation-numbered
world view installed (`install_world`), every topology read here —
`process_count` / `process_index` / `host_shard` / `per_host_batch_size`
— routes through the CURRENT generation instead of a `jax.process_count()`
frozen at init, and every barrier/agree (`sync_hosts` / `agree_flag` /
`PreemptionGuard.agreed`) becomes deadline-bounded and lease-checked: a
dead peer yields a typed `HostLostError` within the heartbeat deadline
instead of an indefinite collective hang. Without a rendezvous, the raw
jax collectives still get a deadline (`DVT_COLLECTIVE_DEADLINE_S`,
default 600s) via a worker-thread join — no barrier path in this module
can block unboundedly.
"""
from __future__ import annotations

import os
import threading
from typing import Optional, Tuple

import jax
import numpy as np

from deep_vision_tpu.core import knobs
from deep_vision_tpu.parallel.mesh import MeshSpec, create_mesh
from deep_vision_tpu.resilience.rendezvous import HostLostError, WorldView

#: ceiling for the raw-jax-collective fallback path (no rendezvous
#: installed): a barrier blocked past this is declared a lost peer. The
#: rendezvous path detects in ~a lease (seconds); this is the backstop.
DEFAULT_COLLECTIVE_DEADLINE_S = knobs.get_float(
    "DVT_COLLECTIVE_DEADLINE_S")

# -- the installable world view (resilience/rendezvous.py) --------------------

_WORLD: Optional[WorldView] = None
_RDZV = None  # the Rendezvous backing barriers/agree, when elastic


def install_world(view: WorldView, rendezvous=None) -> None:
    """Adopt a rendezvous generation as THE topology: reads route through
    it and, when `rendezvous` is given, barriers/agree run over its
    lease-checked file protocol instead of jax collectives (which cannot
    name a dead peer, only hang on it)."""
    global _WORLD, _RDZV
    _WORLD = view
    _RDZV = rendezvous


def installed_world() -> Optional[WorldView]:
    return _WORLD


def clear_world() -> None:
    global _WORLD, _RDZV
    _WORLD = None
    _RDZV = None


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Wire this host into the cluster (idempotent; no-op single-process).

    With no args, reads the standard env (JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID, or the TPU metadata server on Cloud
    TPU pods where initialize() autodetects everything).
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "0")) or None
    if process_id is None:
        pid = os.environ.get("JAX_PROCESS_ID")
        process_id = int(pid) if pid is not None else None
    if coordinator_address is None and num_processes in (None, 1):
        return  # single host, nothing to wire
    _enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def _enable_cpu_collectives() -> None:
    """Multi-process collectives on the CPU backend need the gloo
    transport (newer jax: a config flag; without it every cross-process
    psum dies with 'Multiprocess computations aren't implemented on the
    CPU backend'). Must run before the backend initializes; harmless
    no-op on TPU and on jax builds without the flag."""
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    try:
        # gloo shares one context across a process's in-flight
        # computations: async CPU dispatch can overlap two executions
        # and interleave their collectives on the same TCP pair, which
        # gloo answers with a fatal preamble-size EnforceNotMet
        # (observed flakily in the host smoke). Serialize dispatch —
        # this is the CPU test/simulation path, not a perf surface.
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    except Exception:
        pass


def initialize_from_world(view: WorldView) -> None:
    """`jax.distributed.initialize` parameterized by a rendezvous
    generation: the view's coordinator address, world size, and this
    host's dense rank. The re-entry half of an elastic resize — a
    re-exec'd survivor calls this with the g+1 view and lands in a
    fresh, correctly-sized distributed world."""
    if view.world_size == 1:
        return  # a world of one needs no coordinator
    if view.coordinator is None:
        raise ValueError(
            f"generation {view.generation} record carries no coordinator "
            "address — cannot initialize jax.distributed")
    _enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=view.coordinator,
        num_processes=view.world_size,
        process_id=view.rank,
    )


def global_mesh(data: int = -1, model: int = 1):
    """Mesh over every device in the cluster (all hosts).

    Device order from `jax.devices()` keeps each host's devices contiguous,
    so a (data, model) reshape puts the model axis inside a host whenever
    model <= devices-per-host — TP collectives ride ICI, only DP gradient
    reduction crosses DCN (the layout recipe from the scaling playbook).
    """
    return create_mesh(MeshSpec(data=data, model=model), devices=jax.devices())


def process_count() -> int:
    """World size: the installed rendezvous generation's when elastic,
    else jax's (frozen at init — the fixed-world assumption the elastic
    overlay exists to remove)."""
    if _WORLD is not None:
        return _WORLD.world_size
    return jax.process_count()


def process_index() -> int:
    """This host's dense rank in the current generation (elastic) or
    jax's process index (static)."""
    if _WORLD is not None:
        return _WORLD.rank
    return jax.process_index()


def is_primary() -> bool:
    """True on the host that should write checkpoints/logs (rank 0 of
    the current generation)."""
    return process_index() == 0


def host_shard() -> tuple[int, int]:
    """(shard_index, num_shards) for host-sharded input pipelines: each host
    reads files[shard_index::num_shards] (records.record_iterator contract).
    Generation-aware: after an N→M resize the assignment re-derives over
    the new host set — disjoint and covering at every world size
    (tests/test_rendezvous.py proves the property)."""
    return process_index(), process_count()


def _bounded_collective(fn, name: str, deadline_s: Optional[float]):
    """Run a jax collective with a deadline: the op blocks in C++ when a
    peer is dead (a hang, never an exception), so the
    only honest bound is a worker-thread join — on timeout the orphaned
    thread stays wedged and the caller gets the typed `HostLostError`
    the supervision layer turns into a re-rendezvous."""
    deadline_s = (DEFAULT_COLLECTIVE_DEADLINE_S
                  if deadline_s is None else float(deadline_s))
    out: dict = {}

    def run():
        try:
            out["value"] = fn()
        except BaseException as e:
            out["exc"] = e

    t = threading.Thread(target=run, daemon=True,
                         name=f"collective-{name}")
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        raise HostLostError(
            None, _WORLD.generation if _WORLD is not None else -1,
            detail=f"collective {name!r} blocked past its "
                   f"{deadline_s:.0f}s deadline (dead peer?)")
    if "exc" in out:
        raise out["exc"]
    return out.get("value")


def sync_hosts(name: str = "barrier",
               deadline_s: Optional[float] = None) -> None:
    """Cross-host barrier, deadline-bounded.

    Elastic (rendezvous installed): a lease-checked file barrier — a
    dead peer raises `HostLostError` within the heartbeat deadline, and
    no jax collective (which could wedge in C++) is involved at all.
    Static: the real all-device collective rendezvous, bounded by
    `deadline_s` (default `DVT_COLLECTIVE_DEADLINE_S`)."""
    if process_count() == 1:
        return
    if _RDZV is not None:
        _RDZV.barrier(name, timeout_s=(deadline_s if deadline_s is not None
                                       else DEFAULT_COLLECTIVE_DEADLINE_S))
        return

    def op():
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)

    _bounded_collective(op, name, deadline_s)


def agree_flag(local_flag: bool,
               deadline_s: Optional[float] = None) -> bool:
    """Global OR of a per-host boolean (True if ANY host raised it).

    The preemption-consensus primitive (train/trainer.py): SIGTERM lands on
    hosts at different instants; every host calls this at the same step
    boundary, the allgather rendezvouses them, and all act on the same
    answer — no host enters a checkpoint collective while another enters
    the next step's all-reduce. Single-process: returns the flag as-is.
    Deadline-bounded like `sync_hosts`: a dead peer is a typed
    `HostLostError`, never an indefinite hang."""
    if process_count() == 1:
        return bool(local_flag)
    if _RDZV is not None:
        return _RDZV.agree(
            "agree_flag", bool(local_flag),
            timeout_s=(deadline_s if deadline_s is not None
                       else DEFAULT_COLLECTIVE_DEADLINE_S))

    def op():
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([bool(local_flag)])
        )
        return bool(np.any(flags))

    return bool(_bounded_collective(op, "agree_flag", deadline_s))


class PreemptionGuard:
    """SIGTERM → a cross-host-consistent "stop now" signal.

    The context manager installs a SIGTERM handler (main thread only; the
    previous handler is restored on exit). `agreed()` is the ONLY correct
    way to act on the flag in multi-host runs: hosts receive SIGTERM at
    different instants, and a host acting on its local flag alone would
    enter a checkpoint collective while another enters the next step's
    all-reduce — distributed deadlock. `agreed(step=...)` polls a
    cross-host OR (`agree_flag`) when `step % poll_every == 0` — the
    optimizer step is globally consistent (it advances in the SPMD train
    step every host runs), so hosts rendezvous at the same boundary even if
    they make different numbers of agreed() calls overall (uneven data
    shards, an eval iterator ending early on one host). It also polls
    whenever `force=True` (epoch/eval boundaries). The agreed answer is
    sticky. Single-process: returns the local flag directly, no collectives.

    Callers that cannot supply a step may omit it, falling back to a local
    call counter — that cadence is only deadlock-free if EVERY host makes
    the same number of agreed() calls, which the caller must then guarantee
    (one call per jitted step, identical batch counts via drop_remainder
    sharded loading).

    `poll_every` trades detection latency for hot-loop sync: SIGTERM gives
    ~30s of grace, so polling every 10 steps costs nothing in practice
    while keeping the train loop free of a per-step host-blocking
    allgather.
    """

    def __init__(self, poll_every: int = 10):
        self.poll_every = max(1, int(poll_every))
        self.requested = False
        self._agreed = False
        self._calls = 0
        self._prev_handler = None

    def _on_sigterm(self, signum, frame):
        self.requested = True
        # the flight recorder's preemption bundle: SIGTERM gives ~30s of
        # grace, so dumping NOW (not at the eventual consensus boundary)
        # guarantees the postmortem exists even if the graceful path never
        # completes before the VM is reclaimed. The dump runs on a daemon
        # THREAD, never in signal context: the handler interrupts the main
        # thread wherever it is — possibly inside the journal's or
        # recorder's non-reentrant locks — and a dump here would re-acquire
        # them and self-deadlock the very protocol it serves (the import
        # below would similarly contend on the import lock). The thread
        # simply waits until the handler returns and the lock holder
        # resumes.
        try:
            import threading

            threading.Thread(target=self._preempt_dump,
                             name="flight-preempt-dump",
                             daemon=True).start()
        except Exception:
            pass  # a failed dump must not break the preemption protocol

    @staticmethod
    def _preempt_dump() -> None:
        try:
            from deep_vision_tpu.obs import flight

            flight.emergency_dump("preempt")
        except Exception:
            pass

    def __enter__(self):
        import signal
        import threading

        if threading.current_thread() is threading.main_thread():
            self._prev_handler = signal.signal(
                signal.SIGTERM, self._on_sigterm
            )
        return self

    def __exit__(self, *exc):
        import signal

        if self._prev_handler is not None:
            signal.signal(signal.SIGTERM, self._prev_handler)
            self._prev_handler = None
        return False

    def agreed(self, *, step: Optional[int] = None, force: bool = False) -> bool:
        if self._agreed:
            return True
        if process_count() == 1:  # generation-aware (a 2-host world that
            # shrank to 1 must stop holding consensus with a ghost)
            self._agreed = self.requested
            return self._agreed
        if step is not None:
            due = int(step) % self.poll_every == 0
        else:
            self._calls += 1
            due = self._calls % self.poll_every == 0
        if force or due:
            self._agreed = agree_flag(self.requested)
        return self._agreed


def aggregate_obs(journal_path: str, out_path: Optional[str] = None,
                  gap_ms: float = 25.0) -> Optional[str]:
    """Primary-host end-of-run merge of the per-host journals.

    Assumes the standard Cloud TPU pod layout where every host writes its
    `<journal_path>.pN` into the same shared run directory (GCS/NFS). All
    hosts rendezvous at a barrier (so every follower's file is complete),
    then process 0 merges them into `<journal_path>.merged` with
    cross-host straggler detection (obs/merge.py). Returns the merged
    path on the primary, None elsewhere and in single-process runs.
    """
    if process_count() == 1:
        return None
    sync_hosts("obs_merge")
    if not is_primary():
        return None
    import glob as _g

    paths = sorted(_g.glob(journal_path + ".p*"))
    if not paths:
        return None
    from deep_vision_tpu.obs.merge import merge_journal_files

    out = out_path or journal_path + ".merged"
    merge_journal_files(paths, out, gap_ms=gap_ms)
    return out


def per_host_batch_size(global_batch_size: int) -> int:
    """Rows this host must feed per step (global batch / host count); the
    global-batch contract mirrors `batch * num_replicas` at
    YOLO/tensorflow/train.py:282 but spans hosts. Generation-aware: a
    3→2 resize re-derives this from the new world (the global batch is
    the training contract; the per-host share is topology weather)."""
    n = process_count()
    if global_batch_size % n:
        raise ValueError(f"global batch {global_batch_size} not divisible by {n} hosts")
    return global_batch_size // n


def form_global_array(local_batch, mesh, ndim: Optional[int] = None):
    """Assemble per-host numpy rows into one globally-sharded jax.Array.

    Each host passes only ITS rows; `make_array_from_process_local_data`
    stitches them into the global batch laid out over the mesh's data axis —
    the multi-host device_put (single-host path stays `shard_batch`).
    """
    from deep_vision_tpu.parallel.mesh import data_sharding

    def _make(x):
        x = np.asarray(x)
        sharding = data_sharding(mesh, x.ndim)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree_util.tree_map(_make, local_batch)
