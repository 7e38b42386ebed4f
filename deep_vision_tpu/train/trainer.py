"""The single Trainer shared by every model in the zoo.

Replaces the reference's per-model copy-pasted loops (the 562-line
`run_epochs`/`train`/`validate` at ResNet/pytorch/train.py:310-538, the TF2
`Trainer` classes at YOLO/tensorflow/train.py:22-257 and
Hourglass/tensorflow/train.py:15-172, and Keras `model.fit` at
ResNet/tensorflow/train.py:283-297) with ONE jitted SPMD step over a device
mesh:

- `train_step`/`eval_step` are traced once (the pjit analog of the
  `@tf.function distributed_train_epoch` boundary at YOLO/tensorflow/train.py:126);
- the per-replica fan-out + `strategy.reduce(SUM)` pair
  (YOLO/tensorflow/train.py:131-151) disappears: batches are sharded over the
  mesh's 'data' axis and XLA inserts the gradient all-reduce;
- stateful host logic (plateau LR, best-val checkpointing,
  YOLO/tensorflow/train.py:56-68,243-247) stays outside jit and feeds the LR
  back in through `opt_state.hyperparams`.
"""
from __future__ import annotations

import sys
import time
from typing import Callable, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deep_vision_tpu.core.metrics import MetricLogger
from deep_vision_tpu.core.train_state import TrainState, create_train_state
from deep_vision_tpu.data.device_prefetch import DevicePrefetcher, PlacedBatch
from deep_vision_tpu.obs import perfwatch
from deep_vision_tpu.obs.alerts import AlertEngine, default_training_rules
from deep_vision_tpu.obs.goodput import GoodputMeter
from deep_vision_tpu.obs.registry import COUNT_PREFIX
from deep_vision_tpu.obs.stepclock import StallRule, StepClock, stall_split
from deep_vision_tpu.obs.trace import span, watch_gc
from deep_vision_tpu.parallel.mesh import (
    DATA_AXIS,
    assert_sharding_coverage,
    create_mesh,
    pad_batch_to,
    replicated,
    shard_batch,
    stacked_data_sharding,
)
from deep_vision_tpu.resilience.rendezvous import HostLostError, WorldResized

# one shared jitted sum: evaluate() calls it per masked multi-host batch,
# and a fresh jax.jit wrapper there would retrace every time
_global_sum = jax.jit(jnp.sum)


# The step program's report: its metrics and, under these two keys, the
# post-update step counter and the injected learning rate. The state that
# holds them is donated into the next dispatch; outputs that are not fed
# back survive it, so the loop can read a step after dispatching the next.
# Popped before anything is logged.
_STEP, _LR = "_step", "_lr"


def _metrics_of(report: dict) -> dict:
    return {k: v for k, v in report.items() if k not in (_STEP, _LR)}


def _injected_lr(opt_state):
    """`inject_hyperparams`' learning-rate leaf, or None without one."""
    try:
        return opt_state.hyperparams["learning_rate"]
    except (AttributeError, KeyError, TypeError):
        return None


class _InFlight(NamedTuple):
    """A dispatched step whose report the loop has not read yet."""
    rec: object    # its StepClock record, committed at the read
    report: dict   # device scalars; every leaf stacked (K,) for a superstep
    n: int         # examples in the dispatch
    k: int         # optimizer steps in the dispatch: 0 = a single step
    epoch: int


def _host_bytes(batch: dict) -> int:
    """Bytes of a batch's leaves, as handed to `device_put`."""
    return sum(int(getattr(v, "nbytes", 0)) for v in batch.values())


def _set_lr(opt_state, lr: float):
    """Set the injected learning_rate hyperparam to an absolute value, on
    the devices the old leaf lives on: a leaf placed anywhere else changes
    the jitted step's input layout and recompiles the whole step."""
    hp = dict(opt_state.hyperparams)
    old = hp["learning_rate"]
    hp["learning_rate"] = jax.device_put(
        np.asarray(lr, jnp.asarray(old).dtype), old.sharding)
    return opt_state._replace(hyperparams=hp)


class Trainer:
    """One model + optimizer + loss over a mesh.

    loss_fn(outputs, batch) -> (loss, metrics_dict). The model is applied to
    `batch[input_key]` with `train=True/False` and a 'dropout' rng.

    Step-time knobs (README "Making it fast"): `multistep=K` runs K
    optimizer steps per device dispatch as one lax.scan superstep
    (per-microstep metrics/NaN-guard preserved, step counters advance by
    K; incompatible with checkify/EMA); `device_prefetch=N` places the
    next N batches on the mesh from a producer thread so H2D transfer
    overlaps compute (data/device_prefetch.py).

    Sharding (README "Sharding"): `sharding_rules` attaches a
    declarative pattern -> PartitionSpec table (parallel/shardmap.py).
    The full state tree places per the table (coverage-audited at
    startup against the family's floor, journaled as a typed
    `sharding_resolved` event) and every batch path — single step,
    multistep superstep stack, device prefetcher — shards the batch dim
    over the table's declared batch axes.
    """

    def __init__(
        self,
        model,
        tx: optax.GradientTransformation,
        loss_fn: Callable,
        sample_input,
        eval_loss_fn: Optional[Callable] = None,
        mesh=None,
        rng: Optional[jax.Array] = None,
        input_key: str = "image",
        checkpoint_manager=None,
        plateau=None,  # ReduceLROnPlateau or None
        plateau_metric: str = "top1",
        logger: Optional[MetricLogger] = None,
        eval_logger: Optional[MetricLogger] = None,
        profile_dir: Optional[str] = None,
        profile_steps: tuple = (10, 20),
        checkify_errors: bool = False,
        ema_decay: Optional[float] = None,
        journal=None,  # obs.RunJournal or None
        registry=None,  # obs.Registry; default process-wide registry
        telemetry_sample_every: int = 16,
        lr_schedule=None,  # the optax schedule behind tx, for current_lr
        health=None,  # obs.HealthMonitor or None
        autoprof=None,  # obs.AutoProfiler; built from profile_dir if None
        multistep: int = 1,  # optimizer steps per dispatch (lax.scan)
        device_prefetch: int = 0,  # device-resident batch buffer depth
        backend_supervisor=None,  # resilience.BackendSupervisor or None
        data_loader=None,  # snapshot-capable DataLoader (data/snapshot.py)
        host_supervisor=None,  # resilience.rendezvous.HostSupervisor or None
        executable_cache=None,  # core.excache.ExecutableCache or None
        sharding_rules=None,  # parallel.shardmap.ShardingRules or None
        telemetry=None,  # obs.TelemetryServer: live /healthz + /statusz
    ):
        self.mesh = mesh if mesh is not None else create_mesh()
        self.model = model  # single source of truth for summaries/export
        self.loss_fn = loss_fn
        self.eval_loss_fn = eval_loss_fn or loss_fn
        self.input_key = input_key
        self.ckpt = checkpoint_manager
        self.plateau = plateau
        self.plateau_metric = plateau_metric
        # telemetry: step-time breakdown + recompile/HBM gauges into the
        # registry, per-step events into the journal (obs/ subsystem)
        self.journal = journal
        self.health = health
        # skip_step policy: the jitted step itself discards a poisoned
        # update via a finiteness select — host-side "skip" would need the
        # pre-step state, which donate_argnums already gave back to XLA
        self._skip_nonfinite = bool(health is not None
                                    and health.skip_nonfinite)
        self.clock = StepClock(
            registry=registry, journal=journal, name="train",
            sample_every=telemetry_sample_every,
        )
        # goodput plane (obs/goodput.py): a journal tap attributing every
        # wall-clock second to a typed bucket, with periodic
        # goodput_interval events and a terminal goodput_summary (flushed
        # by a journal closer); alert engine (obs/alerts.py) evaluates
        # the knob-tuned training budgets over the same stream
        # a dispatch over three times the recent median is journaled as a
        # `stall` with where the loop's thread spent it (`_note_stall`);
        # the interpreter's collections are among the causes
        self._stalls = StallRule()
        self._c_stalls = self.clock.registry.counter(
            "train_stalls_total",
            "dispatches whose wall exceeded three times the recent median")
        watch_gc()
        self.goodput = (GoodputMeter(journal=journal,
                                     registry=self.clock.registry)
                        if journal is not None else None)
        self.alerts = (AlertEngine(default_training_rules(),
                                   journal=journal,
                                   registry=self.clock.registry)
                       if journal is not None else None)
        if self.alerts is not None:
            journal.add_tap(self.alerts.observe)
        self._lr_schedule = lr_schedule
        self.logger = logger or MetricLogger(
            name="train", registry=self.clock.registry, journal=journal)
        # no journal on the val logger: evaluate() writes the typed 'eval'
        # event itself — a journal-wired val logger would duplicate every
        # summary as an 'epoch' event
        self.eval_logger = eval_logger or MetricLogger(
            name="val", print_every=0, registry=self.clock.registry)
        # profiler: the instrumentation the reference never had (SURVEY.md
        # §2.7 'tracing/profilers: NONE'). One AutoProfiler owns BOTH the
        # static [start, stop) window (profile_dir/profile_steps, viewed
        # with tensorboard-plugin-profile/xprof) and the anomaly-triggered
        # capture policy (obs/autoprof.py); it guards re-entry so a second
        # trigger while a trace is in flight can never double-start.
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps
        if autoprof is None and profile_dir is not None:
            from deep_vision_tpu.obs.autoprof import AutoProfiler

            autoprof = AutoProfiler(profile_dir, window=profile_steps,
                                    journal=journal, registry=registry)
        self.prof = autoprof
        if self.prof is not None:
            # drain the device pipeline into the trace before stop_trace
            self.prof.fence = lambda: jax.block_until_ready(
                self.state.params)
        self._pguard = None  # PreemptionGuard, live only inside fit
        self._in_flight: Optional[_InFlight] = None  # fit's loop, depth 1
        self._closed = False
        self.preempted = False  # latched by the SIGTERM escalation path
        # backend-loss recovery (resilience/elastic.py BackendSupervisor):
        # with one installed, fit() treats a classified backend failure
        # (dropped connection, hung-backend timeout) as an expected input —
        # rebuild the jitted step from host-side seeds + checkpoint, replay
        # from the last completed step. The host-side ingredients of that
        # rebuild are kept here; everything device-resident is derived.
        # input-pipeline checkpointing (data/snapshot.py): with a
        # snapshot-capable train DataLoader attached, every checkpoint's
        # host sidecar carries the loader's DataLoaderState and resume()
        # re-arms it — the batch stream continues byte-identically instead
        # of restarting from shard zero while the step counter says
        # otherwise. With --device-prefetch N, a MID-epoch snapshot counts
        # batches already handed to the prefetcher as consumed (up to N in
        # flight); epoch-boundary saves (the fit() cadence) are exact.
        self.data_loader = data_loader
        if data_loader is not None and hasattr(data_loader,
                                               "enable_snapshots"):
            # arm per-batch recording BEFORE the first epoch runs so
            # mid-epoch (preempt) saves capture an exact position
            data_loader.enable_snapshots()
        self.backend = backend_supervisor
        if self.backend is not None and self.backend.journal is None:
            self.backend.journal = journal
            if self.backend.policy.journal is None:
                self.backend.policy.journal = journal
        # host-membership supervision (resilience/rendezvous.py): with a
        # HostSupervisor installed, a peer host dying mid-run is an
        # EXPECTED input — the blocking device fetches below become
        # lease-checked bounded fences (a SIGKILLed peer leaves this
        # host's fetch wedged in C++ forever; only a side-channel lease
        # sweep can name it), and fit() turns the typed HostLostError
        # into host_lost/world_resized journal events + a re-rendezvous
        # at generation g+1, raised to the host agent as WorldResized.
        self.hosts = host_supervisor
        if self.hosts is not None:
            if self.hosts.journal is None:
                self.hosts.journal = journal
            if self.hosts.resume_step_fn is None and checkpoint_manager \
                    is not None:
                # what a post-resize resume will land on: the last step
                # the checkpoint layer holds (a directory read — safe
                # from the supervisor's watchdog thread)
                self.hosts.resume_step_fn = checkpoint_manager.latest_step
            if data_loader is not None:
                # an armed snapshot loader pins the OLD host-shard slice
                # in its fingerprint: the restore refuses the resize
                # (SnapshotMismatch) instead of journaling data_reshard.
                # A loader built WITHOUT a host_shard gets this world's
                # slice stamped here — otherwise the fingerprints match
                # across a resize and the refusal can never fire.
                self.hosts.reshardable = False
                view = getattr(self.hosts.rdzv, "view", None)
                if view is not None and \
                        getattr(data_loader, "host_shard", 0) is None:
                    try:
                        data_loader.pin_host_shard(view.shard())
                    except Exception:
                        pass  # already fingerprinted: identity is fixed
        self._tx = tx
        self._sample_input = sample_input
        # a row of integer ids (B, T) is T tokens: `train_tokens_total`
        self._tokens_per_row = (
            int(sample_input.shape[1])
            if getattr(sample_input, "ndim", 0) == 2
            and jnp.issubdtype(sample_input.dtype, jnp.integer) else 0)
        self._init_rng = rng

        # declarative sharding (parallel/shardmap.py): with a rules table
        # attached, the FULL state tree (params, optimizer moments, BN
        # stats) resolves against the table at startup —
        # `assert_sharding_coverage` audits the result against the
        # family's declared floor BEFORE any buffer is placed, and the
        # rule -> leaf resolution lands in the journal as a typed
        # `sharding_resolved` event. Batches (single, multistep stacks,
        # device-prefetched) follow the table's declared batch axes.
        # Without a table, the state replicates (plain data parallel) —
        # the pre-table behavior, unchanged.
        self.sharding_rules = sharding_rules
        self._state_shardings = None
        self._batch_axes = (DATA_AXIS,)
        with span("setup/init_state"):
            state = create_train_state(model, tx, sample_input, rng)
        if sharding_rules is not None:
            shardings, report = sharding_rules.resolve(state, self.mesh)
            # startup hard check FIRST: a stale table must fail before
            # any device placement, naming the leaves it lost
            assert_sharding_coverage(
                state, shardings, self.mesh,
                min_sharded=sharding_rules.floor_for(self.mesh))
            self._state_shardings = shardings
            self._batch_axes = tuple(sharding_rules.batch_axes)
            if journal is not None:
                from deep_vision_tpu.parallel.shardmap import (
                    resolution_event_fields,
                )

                journal.write("sharding_resolved",
                              **resolution_event_fields(report))
        # device boundary: state lives on the mesh from here on —
        # table-sharded when rules are attached, replicated otherwise
        self.state = self._place_state(state)
        # EMA evaluation weights (train/ema.py): updated after every step,
        # used by eval_step. Checkpointed in a SIBLING manager under
        # <ckpt_dir>/ema so the main checkpoint's on-disk structure is
        # identical with or without the flag — runs stay resumable either
        # way (the shadow just re-seeds from the restored params when no
        # EMA history exists).
        self.ema = None
        self._ema_ckpt = None
        if ema_decay is not None:
            from deep_vision_tpu.train.ema import EmaParams

            self.ema = EmaParams(self.state.params, decay=ema_decay)
            if self.ckpt is not None:
                import os as _os

                self._ema_ckpt = type(self.ckpt)(
                    _os.path.join(self.ckpt.directory, "ema"),
                    journal=journal,
                )
        # base LR for plateau scaling: scale is applied to this absolute value,
        # never compounded onto an already-scaled current LR
        try:
            self._base_lr = float(state.opt_state.hyperparams["learning_rate"])
        except (AttributeError, KeyError, TypeError):
            self._base_lr = None
        if self.plateau is not None:
            # a scheduled LR (inject_hyperparams re-evaluates it every step)
            # would silently overwrite the plateau's absolute writes — refuse
            # the combination here too, for trainers built without the config
            # registry's validation
            hp_states = getattr(state.opt_state, "hyperparams_states", None)
            if hp_states and "learning_rate" in hp_states:
                raise ValueError(
                    "plateau scaling requires a constant base learning rate: "
                    "the optimizer's learning_rate is a schedule, which is "
                    "re-evaluated inside the jitted step and would override "
                    "plateau writes — use one LR policy"
                )
            if self._base_lr is None:
                raise ValueError(
                    "plateau scaling requires opt_state.hyperparams"
                    "['learning_rate'] (build the optimizer via "
                    "train.optimizers.build_optimizer)"
                )

        # Sanitizer mode (SURVEY §2.7: the functional-runtime analog of race
        # detectors/ASAN the reference never had): jax.experimental.checkify
        # instruments every op in the jitted step with NaN / out-of-bounds /
        # div-by-zero checks; train_step then raises a located error instead
        # of silently propagating garbage. ~2x step cost — a debugging mode,
        # vs --debug-nans which re-runs ops eagerly only after a NaN fetch.
        self._checkify = checkify_errors
        # -- scan-multistep: K optimizer steps per dispatch ----------------
        # One lax.scan over a (K, B, ...) stacked batch amortizes the
        # per-dispatch host turnaround K-fold. The scan body
        # IS `_train_step_impl`, so per-microstep RNG (fold_in on the
        # advancing state.step), metrics, and the skip_step NaN-guard all
        # apply per microstep; the epoch tail (fewer than K batches left)
        # rides the single-step executable so neither ever recompiles.
        self.multistep = max(1, int(multistep))
        if self.multistep > 1:
            if checkify_errors:
                raise ValueError(
                    "multistep > 1 is incompatible with checkify: the "
                    "sanitizer needs the un-scanned per-step boundary to "
                    "locate the failing op — debug at multistep=1"
                )
            if ema_decay is not None:
                raise ValueError(
                    "multistep > 1 is incompatible with ema_decay: the EMA "
                    "shadow updates once per HOST dispatch, so K scanned "
                    "microsteps would decay it once instead of K times and "
                    "silently change eval — run EMA at multistep=1"
                )
        # persistent executable cache (core/excache.py): step executables
        # AOT-round-trip through the on-disk store, so a restarted
        # process, the backend-loss rebuild-replay, and a re-exec'd host
        # all load their supersteps instead of recompiling them — the
        # recovery-time-objective stops paying the XLA compiler.
        # Checkify is exempt (its jit carries the error plumbing and is
        # a debugging mode, not a cold path worth caching).
        self.excache = executable_cache
        self._build_jitted_steps()
        # device prefetch: pad/shard/device_put the NEXT batch(es) on a
        # producer thread so H2D transfer overlaps the current step's
        # compute (data/device_prefetch.py); depth 2 = double buffering
        self.device_prefetch = max(0, int(device_prefetch))
        self._prefetcher = None
        if self.device_prefetch > 0:
            self._prefetcher = DevicePrefetcher(
                place_one=self._place_one,
                depth=self.device_prefetch,
                group=self.multistep,
                place_group=(self._place_group
                             if self.multistep > 1 else None),
                registry=self.clock.registry,
            )
        # live telemetry plane (obs/telemetry.py): register host-side
        # status + readiness sources. The scraper thread must never touch
        # the device, so /statusz reads the plain-Python step mirror kept
        # by `_log_report`, not `int(self.state.step)` (a device
        # fetch that could fence against an in-flight dispatch).
        self._live_step: Optional[int] = None
        self._live_epoch: Optional[int] = None
        self._live_eps: Optional[float] = None
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.add_status("train", self._telemetry_status)
            # the perf plane's live face (obs/perfwatch): rolling
            # step-time quantiles off this trainer's StepClock histogram
            # (host-side bucket math, no device fetch), recompile count,
            # last perf-gate verdict / trace digest
            perfwatch.set_quantile_source(self._step_time_quantiles)
            telemetry.add_status("perf", perfwatch.telemetry_status)
            # the goodput plane's live face: bucket fractions + the
            # goodput_frac scalar (obs_poll's "gp NN%" column), and the
            # alert engine behind /alertz + the "alerts" health source
            if self.goodput is not None:
                telemetry.add_status("goodput",
                                     self.goodput.telemetry_status)
            if self.alerts is not None:
                telemetry.set_alerts(self.alerts)
            if self.health is not None:
                telemetry.add_health("train", self.health.healthz)
            if self.hosts is not None:
                telemetry.add_health("rendezvous", self._rendezvous_health)

    def _telemetry_status(self) -> dict:
        """Telemetry status source for /statusz: the last step/epoch and
        throughput the train loop published, plus the world generation.
        Host-side reads only — see the registration comment above."""
        out = {
            "step": self._live_step,
            "epoch": self._live_epoch,
            "examples_per_sec": (round(self._live_eps, 1)
                                 if self._live_eps else self._live_eps),
            "steps_seen": int(self.clock.steps_seen),
            "multistep": int(self.multistep),
        }
        if self.hosts is not None:
            out["generation"] = getattr(self.hosts.rdzv, "generation", None)
        return out

    def _step_time_quantiles(self) -> dict:
        """Rolling step-time p50/p95 for the /statusz perf source —
        bucket-resolution estimates from the StepClock histogram, so the
        scraper thread reads plain host numbers (None until steps land)."""
        h = self.clock._h_step
        if not h.count:
            return {}
        import math

        def finite(v):
            return round(v, 3) if math.isfinite(v) else None

        return {"step_time_ms_p50": finite(h.quantile(0.5)),
                "step_time_ms_p95": finite(h.quantile(0.95))}

    def _rendezvous_health(self):
        """Telemetry health source: this host's OWN lease freshness — a
        host whose heartbeat thread died is about to be declared lost by
        its peers, and /healthz should say so first."""
        rdzv = self.hosts.rdzv
        gap = rdzv.lease_gap(rdzv.host)
        ok = gap is not None and gap <= rdzv.lease_s
        return ok, {
            "host": rdzv.host,
            "generation": rdzv.generation,
            "lease_gap_s": round(gap, 3) if gap is not None else None,
            "lease_s": rdzv.lease_s,
        }

    def _place_state(self, state):
        """Place a host/abstract state onto the mesh: per the resolved
        sharding table when one is attached, fully replicated otherwise.
        Shared by init, the backend-loss rebuild, and the legacy-restore
        path of resume() so a recovered run lands on the SAME layout the
        original compiled against (a layout flip would recompile every
        step executable)."""
        if self._state_shardings is not None:
            return jax.device_put(state, self._state_shardings)
        return jax.device_put(state, replicated(self.mesh))

    # -- jitted steps ------------------------------------------------------
    def _build_jitted_steps(self) -> None:
        """(Re)create the jitted step callables. Called once at init and
        again by the backend-loss recovery path: after a client rebuild
        the old executables reference dead buffers, so the wrappers are
        remade from the pure impl methods (the impls close over nothing
        device-resident — everything flows through state/batch args)."""
        # With a sharding table attached, PIN the step executables' state
        # input AND output to the resolved layout: left unconstrained,
        # XLA may pick slightly different output shardings for the
        # single-step and superstep executables (e.g. a trimmed spec),
        # and alternating them — every epoch tail does — would recompile
        # on the layout flip. Pinning keeps the state in the audited
        # table layout for the whole run; batches stay unconstrained
        # (they arrive pre-placed on the declared batch axes).
        state_pin = {}
        if self._state_shardings is not None:
            state_pin = dict(in_shardings=(self._state_shardings, None),
                             out_shardings=(self._state_shardings, None))
        self._state_pin = state_pin  # reused by profile_step's AOT lowering
        if self._checkify:
            from jax.experimental import checkify

            checked = checkify.checkify(
                self._train_step_impl, errors=checkify.all_checks
            )
            # jaxlint: disable=DV003 -- checkify debug mode: keep the pre-step state un-donated so a thrown error can be inspected against the exact inputs that produced it
            self._train_step_err = jax.jit(checked)
            self._train_step = None
        else:
            self._train_step = jax.jit(
                self._train_step_impl, donate_argnums=0, **state_pin
            )
            self._train_step_err = None
        self._eval_step = jax.jit(self._eval_step_impl)
        self._train_multi = None
        if self.multistep > 1:
            self._train_multi = jax.jit(
                self._multistep_impl, donate_argnums=0, **state_pin
            )
        # AOT executables loaded/stored through self.excache, keyed by
        # (step kind -> batch signature). Reset with the jit wrappers:
        # after a backend rebuild the old executables pin dead buffers,
        # and the next dispatch re-lowers and re-loads from the
        # persistent cache (the disk read IS the recovery fast path).
        # The cache-path jits DO NOT DONATE: jax's executable serialize
        # round trip drops the donated-buffer bookkeeping, so a
        # deserialized donating step aliases the old state's buffers
        # while Python still thinks it owns them — measured as a
        # segfault on the second step (use-after-free). The trade is
        # transient 2x state memory during a cached step; flip the
        # cache off for models where that peak matters more than
        # cold-start.
        self._train_step_cache = self._train_multi_cache = None
        if self.excache is not None and not self._checkify:
            # jaxlint: disable=DV003 -- cache-path step: donation must not ride the executable serialize round trip (deserialized donating executables alias freed buffers)
            self._train_step_cache = jax.jit(self._train_step_impl,
                                             **state_pin)
            if self.multistep > 1:
                # jaxlint: disable=DV003 -- cache-path superstep: same serialize-round-trip donation hazard
                self._train_multi_cache = jax.jit(self._multistep_impl,
                                                  **state_pin)
        self._aot_steps: dict = {}

    def _mesh_context(self):
        """JAX's mesh context, entered wherever a step is traced or
        dispatched (it is part of jit's cache key, so always or never):
        code under the trace reads the mesh from it — the Pallas kernels
        to run per data-axis shard, since XLA cannot partition a Mosaic
        call itself (ops/pallas/partition.py)."""
        return jax.set_mesh(self.mesh)

    def profile_step(self, batch, kind: str = "train"):
        """Journal the XLA cost + collective inventory of the step
        executable for `batch`'s signature (typed perf_profile /
        perf_collective events; see obs/perfwatch).

        The excache path profiles automatically at its AOT build; this
        is the explicit probe for plain-jit trainers (smokes, scaling
        benches). It lowers the NON-donating variant of the step impl —
        same HLO modulo buffer aliasing — which costs one extra backend
        compile the first time per signature (jax's AOT cache absorbs
        repeats). `kind="multi"` profiles the superstep: `batch` must
        then be the (K, B, ...) stacked pytree the superstep consumes.
        Returns the profile dict, or None when extraction failed.
        """
        if kind == "multi":
            if self.multistep <= 1:
                raise ValueError("profile_step(kind='multi') on a "
                                 "multistep=1 trainer")
            impl = self._multistep_impl
        elif kind == "train":
            impl = self._train_step_impl
        else:
            raise ValueError(f"profile_step kind {kind!r} not in "
                             "('train', 'multi')")
        # jaxlint: disable=DV003 -- profiling probe: non-donating on purpose (the compiled artifact is inspected, not dispatched on the training hot path)
        jitted = jax.jit(impl, **self._state_pin)
        with self._mesh_context():
            compiled = jitted.lower(self.state, batch).compile()
        return perfwatch.profile_compiled(f"trainer/{kind}", compiled,
                                          journal=self.journal,
                                          registry=self.clock.registry)

    @staticmethod
    def _batch_sig(batch) -> tuple:
        """Cheap shape/dtype signature of a (possibly nested) batch —
        the AOT lookup key. Training batches are padded to a fixed
        canonical shape, so in steady state this is one dict walk."""
        return tuple(
            (k, tuple(v.shape), str(getattr(v, "dtype", type(v).__name__)))
            for k, v in sorted(batch.items()))

    def _cached_step(self, kind: str, jitted, cache_jitted, batch):
        """The executable for (kind, batch signature): loaded from the
        persistent cache on a cold start / post-rebuild, compiled-and-
        stored otherwise. Falls back to the plain (donating) jit wrapper
        when no cache is attached — ``cache_jitted`` is the
        donation-free variant of the same impl, the only shape safe to
        serialize (see _build_jitted_steps)."""
        if cache_jitted is None:
            return jitted
        by_sig = self._aot_steps.setdefault(kind, {})
        sig = self._batch_sig(batch)
        compiled = by_sig.get(sig)
        if compiled is None:
            lowered = cache_jitted.lower(self.state, batch)
            compiled, _source = self.excache.get_or_compile(
                lowered, name=f"trainer/{kind}")
            by_sig[sig] = compiled
            # perf attribution (obs/perfwatch): the AOT/cache path is the
            # one trainer site that holds a compiled executable, so its
            # XLA cost + collective inventory journal here — once per
            # (kind, batch signature), at the build it already paid for
            perfwatch.profile_compiled(f"trainer/{kind}", compiled,
                                       journal=self.journal,
                                       registry=self.clock.registry)
        return compiled

    # (the scopes name every op of a step in the program's HLO metadata)
    @jax.named_scope("train_step")
    def _train_step_impl(self, state: TrainState, batch):
        step_rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            variables = {"params": params}
            mutable = False
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
                mutable = ["batch_stats"]
            out = state.apply_fn(
                variables,
                batch[self.input_key],
                train=True,
                rngs={"dropout": step_rng},
                mutable=mutable,
            )
            outputs, new_model_state = out if mutable else (out, {})
            loss, metrics = self.loss_fn(outputs, batch)
            return loss, (metrics, new_model_state.get("batch_stats", {}))

        grads, (metrics, new_bs) = jax.grad(loss_fn, has_aux=True)(state.params)
        new_state = state.apply_gradients(grads)
        if state.batch_stats:
            new_state = new_state.replace(batch_stats=new_bs)
        metrics["grad_norm"] = optax.global_norm(grads)
        if self._skip_nonfinite:
            # health skip_step policy: one poisoned batch must not destroy
            # the weights — keep the whole pre-step state (params, opt
            # moments, step counter, batch_stats) when loss or grads went
            # non-finite. A select inside jit, so no extra host sync and
            # no reliance on the donated input buffers.
            ok = jnp.isfinite(metrics["grad_norm"])
            if "loss" in metrics:
                ok = ok & jnp.isfinite(metrics["loss"])
            new_state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new_state, state
            )
            metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
        metrics[_STEP] = new_state.step
        lr = _injected_lr(new_state.opt_state)
        if lr is not None:
            metrics[_LR] = lr
        return new_state, metrics

    @jax.named_scope("eval_step")
    def _eval_step_impl(self, state: TrainState, batch):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        outputs = state.apply_fn(variables, batch[self.input_key], train=False)
        _, metrics = self.eval_loss_fn(outputs, batch)
        return metrics

    def _multistep_impl(self, state: TrainState, batches):
        """K optimizer steps over a (K, B, ...) stacked batch, one dispatch.

        The scan body is the exact single-step impl: state.step advances
        inside apply_gradients, so per-microstep RNG derivation
        (fold_in(rng, step)) and the skip_step finiteness select match K
        separate dispatches bit for bit. Returns (state, report) with
        every leaf of the report stacked (K,) — the per-microstep record the
        host loop un-stacks for loggers/health."""
        return jax.lax.scan(
            lambda s, b: self._train_step_impl(s, b), state, batches
        )

    # -- host API ----------------------------------------------------------
    def _pad_and_mask(self, batch):
        """Pad the final partial batch up to the data-axis multiple and attach
        a '_mask' row-validity array consumed by mask-aware losses/metrics
        (TPU static shapes; the reference just let torch/TF handle ragged
        last batches, ResNet/pytorch/train.py:431-485)."""
        if isinstance(batch[self.input_key], jax.Array) and len(
                batch[self.input_key].sharding.device_set) > 1:
            # multi-host: the batch is already a globally-sharded array
            # (form_global_array) — this host holds only its shards, so
            # padding must happen BEFORE assembly; callers feed full batches
            return dict(batch)
        n_data = int(np.prod([self.mesh.shape[a]
                              for a in self._batch_axes]))
        batch, n_valid = pad_batch_to(dict(batch), n_data)
        n_total = np.asarray(batch[self.input_key]).shape[0]
        if "_mask" not in batch:
            mask = np.zeros((n_total,), np.float32)
            mask[:n_valid] = 1.0
            batch["_mask"] = mask
        return batch

    # -- batch placement (device prefetch + multistep stacking) ------------
    @staticmethod
    def _pad_rows_to(batch: dict, n: int) -> dict:
        """Zero-pad every leaf's leading dim to `n` rows; the '_mask'
        zeros added with them keep the rows out of every masked mean."""
        out = {}
        for k, v in batch.items():
            v = np.asarray(v)
            if v.shape[0] < n:
                pad = [(0, n - v.shape[0])] + [(0, 0)] * (v.ndim - 1)
                v = np.pad(v, pad)
            out[k] = v
        return out

    def _place_one(self, batch) -> PlacedBatch:
        """Host batch -> padded/masked/sharded on the mesh (the work
        train_step otherwise does on the critical path)."""
        n = int(np.shape(batch[self.input_key])[0])
        placed = shard_batch(self.mesh, self._pad_and_mask(batch),
                             axes=self._batch_axes)
        return PlacedBatch(placed, n, 1)

    def _place_group(self, batches) -> PlacedBatch:
        """K host batches -> one (K, B, ...) stacked superstep batch."""
        n = sum(int(np.shape(b[self.input_key])[0]) for b in batches)
        return PlacedBatch(self._stack_batches(batches), n, len(batches))

    def _stack_batches(self, batches):
        """Pad/mask each batch, stack leaves along a new scan axis, and
        place with the (replicated-K, sharded-B) layout.

        A partial final batch inside the group (drop_remainder=False) is
        additionally zero-padded up to the group's common batch size with
        its '_mask' extended accordingly — mask-aware losses/metrics ignore
        the extra rows exactly as they ignore the data-axis padding at
        multistep=1, and np.stack sees uniform shapes."""
        padded = [self._pad_and_mask(b) for b in batches]
        sizes = [np.asarray(p[self.input_key]).shape[0] for p in padded]
        n_max = max(sizes)
        if min(sizes) != n_max:
            padded = [p if n == n_max else self._pad_rows_to(p, n_max)
                      for p, n in zip(padded, sizes)]

        def _stack(*xs):
            return np.stack([np.asarray(x) for x in xs])

        stacked = jax.tree_util.tree_map(_stack, *padded)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, stacked_data_sharding(self.mesh, x.ndim,
                                         axes=self._batch_axes)),
            stacked,
        )

    @property
    def _profiling(self) -> bool:
        """True while a profiler capture is in flight (static or auto)."""
        return self.prof is not None and self.prof.capturing

    def _profiler_hook(self):
        if self.prof is None:
            return
        # a pending static window anchors to the true optimizer step (e.g.
        # after a resume). Reading it waits for whatever holds the state,
        # so only with no step in flight: the first dispatch of an epoch,
        # a caller driving train_step by hand. In between, the profiler's
        # own counter runs on, recalibrated by observe_step.
        anchor = self.prof.needs_step_index and self._in_flight is None
        self.prof.on_step_start(int(self.state.step) if anchor else None)

    def _stop_trace(self, step: Optional[int] = None) -> None:
        """Close an in-flight profiler capture (idempotent); journaled as
        a `profile_capture` event with outcome=closed_early."""
        if self.prof is not None:
            self.prof.interrupt()

    def train_step(self, batch) -> dict:
        """One optimizer step on `batch`; -> its metrics (device scalars)."""
        return _metrics_of(self._dispatch_step(batch))

    def _dispatch_step(self, batch) -> dict:
        """Place `batch`, enqueue the step program; -> its report."""
        self._profiler_hook()
        i = self.clock.steps_seen  # the host's dispatch index (fit's loop)
        if isinstance(batch, PlacedBatch):
            batch = batch.data  # device prefetcher already padded + placed
        else:
            with span("train/place", step=i) as sp:
                batch = self._pad_and_mask(batch)
                sp.set(bytes=_host_bytes(batch))
                batch = shard_batch(self.mesh, batch, axes=self._batch_axes)
        with span("train/dispatch", step=i), self._mesh_context():
            if self._checkify:
                err, (new_state, metrics) = self._train_step_err(self.state,
                                                                 batch)
                err.throw()  # located NaN/OOB/div0 inside the step, if any
                self.state = new_state
            else:
                step_fn = self._cached_step("train_step", self._train_step,
                                            self._train_step_cache, batch)
                self.state, metrics = step_fn(self.state, batch)
        if self.ema is not None:
            self.ema.update(self.state.params)
        return metrics

    def train_superstep(self, batches) -> list:
        """K optimizer steps in ONE dispatch (requires multistep > 1).

        `batches`: a list of K host batch dicts, or a PlacedBatch the
        device prefetcher stacked ahead of time. Returns K per-microstep
        metric dicts (device scalars — fetch once, not per key)."""
        metrics = _metrics_of(self._dispatch_superstep(batches))
        return [jax.tree_util.tree_map(lambda v, i=i: v[i], metrics)
                for i in range(self.multistep)]

    def _dispatch_superstep(self, batches) -> dict:
        """Stack and place K batches, enqueue the scan; -> its report,
        every leaf stacked (K,)."""
        if self._train_multi is None:
            raise ValueError("train_superstep needs Trainer(multistep=K>1)")
        self._profiler_hook()
        i = self.clock.steps_seen
        if isinstance(batches, PlacedBatch):
            k, stacked = batches.group, batches.data
        else:
            with span("train/place", step=i) as sp:
                k, stacked = len(batches), self._stack_batches(batches)
                sp.set(bytes=_host_bytes(stacked))
        if k != self.multistep:
            raise ValueError(
                f"superstep got {k} batches, configured multistep is "
                f"{self.multistep} (the epoch tail must use train_step)"
            )
        with span("train/dispatch", step=i), self._mesh_context():
            multi_fn = self._cached_step("superstep", self._train_multi,
                                         self._train_multi_cache, stacked)
            self.state, metrics = multi_fn(self.state, stacked)
        return metrics

    def eval_step(self, batch) -> dict:
        batch = shard_batch(self.mesh, self._pad_and_mask(batch),
                            axes=self._batch_axes)
        state = self.state
        if self.ema is not None:
            state = state.replace(params=self.ema.params)
        with self._mesh_context():
            return self._eval_step(state, batch)

    def lr_at(self, step: int) -> float:
        """LR for a step the caller already fetched (`current_lr`)."""
        lr = _injected_lr(self.state.opt_state)
        if lr is not None:
            return float(lr)
        return self._scheduled_lr(step)

    def _scheduled_lr(self, step: int) -> float:
        """Optimizer built without inject_hyperparams: evaluate the schedule
        at the given step instead of logging NaN forever."""
        if self._lr_schedule is not None:
            if callable(self._lr_schedule):
                return float(self._lr_schedule(step))
            return float(self._lr_schedule)
        return float("nan")

    @property
    def current_lr(self) -> float:
        return self.lr_at(int(self.state.step))

    def close(self) -> None:
        """Release run-scoped resources: stop an in-flight profiler trace
        (the start_trace leak when training ends before profile_steps[1]),
        flush TensorBoard writers, and drain async checkpoint saves.
        Idempotent; called from train_cli.py and, via journal.add_closer,
        from the journal's atexit hook on abnormal exits."""
        if self._closed:
            return
        self._closed = True
        if self.health is not None:
            self.health.stop()  # disarm the watchdog before teardown
        if self.prof is not None:
            # terminal: stops an in-flight (auto-)capture without leaking
            # the process-wide profiler latch
            self.prof.close()
        for lg in (self.logger, self.eval_logger):
            tb = getattr(lg, "tb", None)
            if tb is not None:
                try:
                    tb.flush()
                except Exception:
                    pass
        if self.ckpt is not None:
            self.ckpt.wait()
        if self._ema_ckpt is not None:
            self._ema_ckpt.wait()
        if self.goodput is not None:
            # terminal goodput_summary (idempotent — the journal closer
            # covers runs that never reach Trainer.close)
            self.goodput.close()

    def evaluate(self, eval_data: Iterable, epoch: int = 0) -> dict:
        with span("eval", epoch=epoch):
            return self._evaluate(eval_data, epoch)

    def _evaluate(self, eval_data: Iterable, epoch: int = 0) -> dict:
        self.eval_logger.start_epoch()
        step = 0
        for batch in eval_data:
            # eval batches are forward progress too: a long val pass must
            # not trip the hang watchdog
            if self.health is not None:
                self.health.beat()
            # consensus (not the local flag): in multi-host runs every host
            # must leave the eval collectives at the same batch boundary.
            # Keyed on the eval-batch index, which is host-identical because
            # the SPMD eval_step itself already requires every host to make
            # the same sequence of calls.
            if self._pguard is not None and self._pguard.agreed(step=step):
                break  # caller re-checks with force=True and checkpoints
            # metrics are masked MEANS over valid rows; weight the epoch
            # aggregate by VALID rows. Multi-host callers pre-pad the final
            # global batch (see _pad_and_mask) and ship '_mask' with it —
            # counting padded rows here would skew every epoch average the
            # padding's share.
            if "_mask" in batch:
                m = batch["_mask"]
                if isinstance(m, jax.Array) and not m.is_fully_addressable:
                    # multi-host global array: shards live on other hosts;
                    # reduce under SPMD, fetch the replicated scalar
                    n = int(_global_sum(m))
                else:
                    n = int(np.sum(np.asarray(m)))
            else:
                n = np.shape(batch[self.input_key])[0]
            metrics = self.eval_step(batch)
            self.eval_logger.log_step(step, metrics, batch_size=n, epoch=epoch)
            step += 1
        summary = self.eval_logger.end_epoch(epoch)
        if self.journal is not None:
            self.journal.write("eval", epoch=epoch, summary=summary)
        return summary

    def fit(
        self,
        train_data_fn: Callable[[], Iterable],
        eval_data_fn: Optional[Callable[[], Iterable]] = None,
        epochs: int = 1,
        start_epoch: int = 0,
        eval_first: bool = False,  # epoch-0 sanity pass (ResNet/pytorch/train.py:390)
        save_every: int = 1,
        handle_preemption: bool = True,
        preemption_poll_every: int = 10,
    ):
        """Epoch driver. With `handle_preemption` (default), SIGTERM — what a
        TPU VM gets ~30s before a maintenance event or spot reclaim — is
        caught, the current step finishes, a checkpoint + host sidecar are
        written synchronously, and fit returns early; `resume()` continues
        the run. The elastic-recovery story the reference lacked entirely
        (SURVEY §2.7: 'recovery = manual resume from checkpoint'). Installed
        only on the main thread (signal module requirement)."""
        from deep_vision_tpu.parallel.multihost import PreemptionGuard

        self._pguard = (
            PreemptionGuard(poll_every=preemption_poll_every)
            if handle_preemption else None
        )
        self._closed = False  # fit may be re-entered after a close()
        self.preempted = False  # re-armed per fit: the latch reports THIS run
        self._resizing = False  # latched by _handle_host_loss: gates the
        # finally-block device waits below
        if self.health is not None:
            self.health.start_watchdog()  # no-op without a timeout
        import contextlib

        ctx = self._pguard if self._pguard is not None else contextlib.nullcontext()
        try:
            with ctx:
                if eval_first and eval_data_fn is not None:
                    self.evaluate(eval_data_fn(), epoch=start_epoch)
                epoch = start_epoch
                attempt = 0  # backend rebuild-replay attempts so far
                while epoch < epochs:
                    try:
                        with span("train/epoch", epoch=epoch):
                            status, summary = self._run_epoch(train_data_fn,
                                                              epoch)
                        if status == "preempted":
                            return self.state
                        if self._post_epoch(summary, eval_data_fn, epoch,
                                            save_every) == "preempted":
                            return self.state
                        if attempt and self.backend is not None:
                            # a full epoch on the rebuilt backend = real
                            # progress: the outage is over
                            self.backend.on_recovered(
                                attempt, step=int(self.state.step))
                            attempt = 0
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except HostLostError as e:
                        # a peer HOST died (lease expired at a bounded
                        # fence / rendezvous barrier): journal, re-
                        # rendezvous at g+1, hand the new world to the
                        # host agent — never the backend path, which
                        # would rebuild-and-replay into the same dead
                        # collective
                        self._handle_host_loss(e)
                    except Exception as e:
                        # a SIGKILLed peer often surfaces as a transport
                        # error (gloo/ICI 'connection closed') MILLI-
                        # seconds before its lease expires: give the
                        # lease ledger one period to name a corpse
                        # before treating this as a backend/program
                        # failure
                        if self.hosts is not None:
                            lost = self.hosts.confirm_loss(e)
                            if lost is not None:
                                self._handle_host_loss(lost)
                        # backend-loss detection + rebuild-replay
                        # (resilience/elastic.BackendSupervisor): only
                        # failures the supervisor classifies as a
                        # lost backend are retried — program bugs, NaN
                        # aborts, and version skew propagate unchanged
                        attempt += 1
                        if self.backend is None or not self.backend.on_failure(
                                attempt, e, step=None, context="train/fit"):
                            raise
                        self.backend.recover(attempt)
                        epoch = self._rebuild_after_backend_loss(start_epoch)
                        continue
                    epoch += 1
        finally:
            self._pguard = None
            self._stop_trace()  # stop gate never reached (short run)
            # NOT while a world resize is propagating: an async save's
            # device fetch may be wedged in the very collective that
            # just died, and wait() has no deadline — the re-exec'd
            # process re-reads whatever the last COMPLETED save left
            if not self._resizing:
                if self.ckpt is not None:
                    self.ckpt.wait()
                if self._ema_ckpt is not None:
                    self._ema_ckpt.wait()
        return self.state

    def _save_checkpoint(self, epoch: int, val_summary=None) -> bool:
        t0 = time.perf_counter()
        with span("checkpoint/save", epoch=epoch,
                  step=int(self.state.step)):
            host_state = {
                "epoch": epoch,
                "train_logger": self.logger.state_dict(),
                "val_logger": self.eval_logger.state_dict(),
            }
            if self.plateau is not None:
                host_state["plateau"] = self.plateau.state_dict()
            if self.data_loader is not None:
                # the input pipeline is a checkpoint citizen: its state
                # rides the same crc32c sidecar as the plateau/loggers
                host_state["data_state"] = self.data_loader.state_dict()
            saved = self.ckpt.save(
                int(self.state.step), self.state, host_state=host_state,
                metrics=val_summary,
            )
            if self._ema_ckpt is not None:
                self._ema_ckpt.save_tree(
                    int(self.state.step), dict(self.ema.params),
                    host_state=self.ema.state_dict(),
                )
        if self.journal is not None:
            # save_ms is the goodput plane's checkpoint feed: offline
            # attribution (obs/goodput.py) carves exactly this much of
            # the gap before this row into the checkpoint bucket
            self.journal.write("checkpoint", step=int(self.state.step),
                               epoch=epoch, saved=bool(saved),
                               save_ms=round(
                                   (time.perf_counter() - t0) * 1e3, 3))
        return bool(saved)

    def _rebuild_after_backend_loss(self, fallback_epoch: int) -> int:
        """Rebuild the device-side world from host-side seeds + checkpoint
        after a lost backend; returns the epoch to replay from.

        Everything device-resident is reconstructed: the compiled-
        executable caches are dropped (they pin the dead client), the
        jitted wrappers are remade, a fresh TrainState is re-initialized
        from the SAME host seeds (bit-equivalent to the original init),
        and — when a checkpoint manager holds a valid step — `resume()`
        replays from the last completed checkpoint (riding the quarantine
        fallback chain and the cross-mesh re-placement). Without a
        checkpoint the honest floor is a from-scratch replay, journaled
        as such."""
        try:
            jax.clear_caches()
        except Exception:
            pass
        state = create_train_state(self.model, self._tx, self._sample_input,
                                   self._init_rng)
        self.state = self._place_state(state)
        if self.ema is not None:
            from deep_vision_tpu.train.ema import EmaParams

            self.ema = EmaParams(self.state.params, decay=self.ema.decay,
                                 warmup=self.ema.warmup)
        self._build_jitted_steps()
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            return self.resume()  # journals 'resumed'; restores EMA/loggers
        if self.journal is not None:
            self.journal.write(
                "note", note="backend rebuilt without a checkpoint: "
                             "replaying from scratch",
                epoch=int(fallback_epoch))
        return fallback_epoch

    def _host_fetch(self, fn):
        """Blocking device fetch, lease-checked when a HostSupervisor is
        installed: a peer SIGKILLed mid-collective wedges this host's
        fetch in C++ with no exception — the bounded fence polls the
        rendezvous lease ledger between waits and raises the typed
        HostLostError the fit loop supervises. Without a supervisor,
        the plain fetch (single-host runs pay nothing)."""
        if self.hosts is None:
            return fn()
        return self.hosts.bounded_fetch(fn)

    def _handle_host_loss(self, err: HostLostError):
        """The elastic ladder for host churn: typed `host_lost` event →
        re-rendezvous at generation g+1 with the survivors → typed
        `world_resized{from,to,generation,resume_step}` → hand the new
        world to the host agent as WorldResized.

        Why raise instead of rebuilding in place: this rank may be (and
        after a mid-collective SIGKILL, IS) wedged inside a dead gloo/
        ICI op; `jax.distributed` cannot re-initialize in-process and
        its coordination client terminates the process when it notices
        the corpse (rendezvous.py module docstring). The host agent
        re-execs into the new generation — same process slot, same
        append-mode journal — and `resume()` continues from
        `resume_step` via the PR 10 cross-mesh restore. No new
        checkpoint is attempted here: a save would fetch device state
        through the very collective that just died.
        """
        if self.hosts is None:
            raise err
        self._resizing = True  # fit's finally must not block on device
        # waits that may ride the dead collective
        self._stop_trace()
        # the exactly-once funnel: journals host_lost + world_resized
        # (+ data_reshard when the input re-derives), resizes at g+1. If
        # the supervisor's watchdog won the race, this parks until its
        # reexec replaces the process.
        view = self.hosts.handle_loss(err)
        # the step handle_loss journaled, not a fresh latest_step() read:
        # the postmortem timeline and the actual resume must agree
        raise WorldResized(view, resume_step=self.hosts.last_resume_step)

    def _preempt_save(self, epoch: int) -> None:
        """The SIGTERM escalation ladder's final rung: checkpoint-now-and-
        requeue. The flight recorder already dumped its `preempt` bundle
        from the signal hook; here (at the cross-host-agreed step
        boundary, on the main thread) the state is checkpointed
        synchronously through the atomic crc32c sidecar path, journaled as
        a typed `preempt_checkpoint` event, and the run is marked for the
        scheduler's requeue exit code (obs.flight.REQUEUE_EXIT_CODE) —
        honest about the outcome either way (the VM dies shortly; the
        operator must know whether the step made it to disk)."""
        from deep_vision_tpu.obs import flight as _flight

        # first the step in flight: the checkpoint's step, the last journal
        # row and the data position then agree
        self._flush()
        step = int(self.state.step)
        self.preempted = True
        if self.ckpt is None:
            print(f"preempted at step {step}: NO checkpoint manager, "
                  "state not saved; exiting fit", flush=True)
            if self.journal is not None:
                self.journal.write("preempt_checkpoint", step=step,
                                   epoch=int(epoch), saved=False,
                                   reason="no checkpoint manager")
            _flight.request_requeue()
            return
        saved = self._save_checkpoint(epoch)
        self.ckpt.wait()
        if self._ema_ckpt is not None:
            self._ema_ckpt.wait()
        if saved:
            print(f"preempted at step {step}: checkpoint written, "
                  "exiting fit", flush=True)
        else:
            print(f"preempted at step {step}: checkpoint manager DECLINED "
                  f"the save (latest on disk: {self.ckpt.latest_step()}); "
                  "exiting fit", flush=True)
        if self.journal is not None:
            self.journal.write("preempt_checkpoint", step=step,
                               epoch=int(epoch), saved=bool(saved),
                               dir=self.ckpt.directory)
        _flight.request_requeue()

    def _grouped(self, data):
        """Coalesce host batches into lists of `multistep` for the scan
        superstep; the short epoch tail flows through as single batches so
        the stacked executable never sees a ragged shape (no recompile)."""
        pending = []
        for batch in data:
            pending.append(batch)
            if len(pending) == self.multistep:
                yield pending
                pending = []
        for batch in pending:
            yield batch

    def _run_epoch(self, train_data_fn, epoch):
        """One epoch of steps; returns ("preempted"|None, logger summary).

        Three data paths share this loop: plain host batches, device-
        prefetched PlacedBatches (H2D already off the critical path), and
        multistep groups (one dispatch = K optimizer steps) — the latter
        two composed by the prefetcher itself when both are on. The
        grouping/prefetch stage sits INSIDE clock.iter_data so data_wait
        honestly covers the whole wait for a dispatch's worth of input.

        The loop keeps one step in flight: it dispatches step N, then reads
        and logs step N-1's report, and takes batch N+1 while the device
        runs step N. The epoch ends, by its last batch or by an exception,
        with the step in flight read and logged."""
        self.logger.start_epoch()
        data = train_data_fn()
        if self._prefetcher is not None:
            data = self._prefetcher(data)
        elif self.multistep > 1:
            data = self._grouped(data)
        try:
            for item in self.clock.iter_data(data):
                if self._step_and_log(item, epoch) == "preempted":
                    # no end_epoch: a partial-epoch summary would pollute
                    # the history/TensorBoard rows the re-run epoch writes
                    # again
                    return "preempted", None
            last, self._in_flight = self._in_flight, None
            if last is not None and self._close_step(last) == "preempted":
                return "preempted", None
        except BaseException:
            # the step in flight did run: its row belongs in the journal.
            # What reading it may raise in turn (a second non-finite step,
            # the same lost host) gives way to the exception on its way out
            try:
                self._flush()
            except Exception:
                pass
            raise
        return None, self.logger.end_epoch(epoch)

    def _step_and_log(self, item, epoch):
        """Dispatch one item of the feed — a batch, or a multistep group
        (one scan dispatch = K optimizer steps); either may be a
        PlacedBatch — then read and log the dispatch before it."""
        group = isinstance(item, list) or (
            isinstance(item, PlacedBatch) and item.group > 1)
        if isinstance(item, PlacedBatch):
            n = item.n
        elif group:
            n = sum(int(np.shape(b[self.input_key])[0]) for b in item)
        else:
            n = np.shape(item[self.input_key])[0]
        i = self.clock.steps_seen + 1  # this dispatch, known before any fetch
        args = {"multistep": self.multistep} if group else {}
        with jax.profiler.StepTraceAnnotation("train", step_num=i), \
                span("train/step", step=i, epoch=epoch, **args):
            # dispatch_ms is enqueue-only (the starvation signal compares
            # data_wait against it); the record commits when it is read
            with self.clock.step(batch_size=n, auto_commit=False,
                                 tokens=n * self._tokens_per_row) as rec:
                report = (self._dispatch_superstep(item) if group
                          else self._dispatch_step(item))
            late = self._in_flight
            self._in_flight = _InFlight(rec, report, n,
                                        self.multistep if group else 0,
                                        epoch)
            return self._close_step(late) if late is not None else None

    def _close_step(self, flight: _InFlight):
        """Read and log a dispatched step, then poll for preemption."""
        try:
            opt_step = self._read_and_log(flight)
        except BaseException:
            # the run ends on what this step's report showed (a health
            # abort) or on the fetch itself: the step dispatched after it
            # goes with the state, unread
            self._in_flight = None
            raise
        # poll keyed to the optimizer step — globally consistent across
        # hosts, immune to unequal agreed() call counts elsewhere
        if self._pguard is not None and self._pguard.agreed(step=opt_step):
            # epoch-1: this epoch is incomplete, resume re-runs it
            self._preempt_save(flight.epoch - 1)
            return "preempted"
        return None

    def _flush(self) -> None:
        """Read and log the step in flight, if any. After it the loop's
        state is whole: journal, loggers, health guard and clock have
        every step that was dispatched."""
        flight, self._in_flight = self._in_flight, None
        if flight is not None:
            self._read_and_log(flight)

    def _read_and_log(self, flight: _InFlight) -> int:
        """Fetch a dispatched step's report and feed every sink with it;
        -> the optimizer step after that dispatch."""
        rec = flight.rec

        def read():
            rec.await_report(flight.report)
            return jax.device_get(flight.report)

        # the one blocking fetch of a dispatch. Lease-checked
        # (_host_fetch): in a multi-host world a dead peer wedges it
        # forever otherwise
        with span("train/fetch", step=rec.index, n=1):
            host = self._host_fetch(read)
        self.clock.note_host_fetches(1)
        with span("train/log", step=rec.index) as sp:
            opt_step = self._log_report(flight, host, sp)
            sp.set(opt_step=opt_step)
        return opt_step

    def _log_report(self, flight: _InFlight, host: dict, log_span) -> int:
        """Every sink fed after a dispatch: clock (registry + journal),
        the stall rule, anomaly triggers, loggers, health guard. A
        superstep's K microsteps are recovered from the scanned stack and
        logged and health-checked exactly as K single steps would have
        been. `log_span`: the `train/log` span this runs inside."""
        rec, k, epoch = flight.rec, flight.k, flight.epoch
        steps = [int(s) for s in np.atleast_1d(host.pop(_STEP))]
        opt_step = steps[-1]
        if _LR in host:
            lrs = [float(v) for v in np.atleast_1d(host.pop(_LR))]
        elif k and callable(self._lr_schedule):
            # update t uses schedule(t-1)
            lrs = [self._scheduled_lr(s - 1) for s in steps]
        else:
            lrs = [self._scheduled_lr(opt_step)] * len(steps)
        for key in [k for k in host if k.startswith(COUNT_PREFIX)]:
            name = key[len(COUNT_PREFIX):]
            host[name] = host.pop(key)
            self.clock.registry.counter(
                name + "_total", "a count the model made, summed over "
                "steps").inc(float(np.sum(host[name])))
        rows = [{name: float(np.atleast_1d(v)[i]) for name, v in host.items()}
                for i in range(len(steps))]
        last, lr = rows[-1], lrs[-1]
        # journal: ONE step event per dispatch (the thing that actually
        # happened), a superstep's stamped multistep=K; loggers below keep
        # per-microstep series so histories stay comparable across K
        rec.commit(step=opt_step,
                   metrics={"loss": last["loss"], "lr": lr}
                   if "loss" in last else {"lr": lr},
                   extra={"multistep": k} if k else None)
        if self._stalls.observe(rec.step_time_ms):
            self._note_stall(rec, opt_step, log_span)
        # publish the host-side mirror the telemetry scraper reads (plain
        # attribute writes: benign to race, never a device fetch)
        self._live_step, self._live_epoch = opt_step, epoch
        self._live_eps = rec.examples_per_sec
        # anomaly triggers see the committed record (step-time/data-wait
        # z-scores, recompile bursts, HBM high-water jumps) and arm a
        # capture that the NEXT dispatch's _profiler_hook starts
        if self.prof is not None:
            self.prof.observe_step(opt_step, rec.fields())
        n_each = max(1, flight.n // len(rows))
        for step_i, lr_i, mf in zip(steps, lrs, rows):
            loss_f = mf.get("loss")
            grad_norm_f = mf.get("grad_norm")
            skipped = (self._skip_nonfinite and mf.get("skipped", 0.0) > 0)
            if skipped:
                # the discarded update's loss/grads are garbage: keep them
                # out of the epoch means and TB series — the health event
                # and skipped counter (below) carry the record instead
                mf = {kk: v for kk, v in mf.items()
                      if v == v and abs(v) != float("inf")}
            # (train_learning_rate gauge: MetricLogger's NaN-guarded write)
            # data_wait amortizes over the K microsteps the one gather fed;
            # examples_per_sec is the dispatch's wall rate (same for all K)
            self.logger.log_step(
                step_i, mf, batch_size=n_each, epoch=epoch, lr=lr_i,
                data_wait_ms=rec.data_wait_ms / len(rows),
                examples_per_sec=rec.examples_per_sec,
            )
            # health guard AFTER the step/log writes: an abort's journal
            # then reads step -> health(non_finite) -> crash, in order
            if self.health is not None:
                self.health.check_step(step_i, loss=loss_f,
                                       grad_norm=grad_norm_f,
                                       skipped=skipped)
        return opt_step

    def _note_stall(self, rec, opt_step: int, log_span) -> None:
        """A dispatch took over three times the median of the last 64:
        journal it with where the loop's thread spent its wall."""
        split = stall_split(rec, open_spans=(log_span,))
        cause = max(split, key=split.get)
        self._c_stalls.inc()
        fields = dict(step=opt_step, dispatch=rec.index,
                      step_time_ms=round(rec.step_time_ms, 3),
                      median_ms=round(self._stalls.median_ms, 3),
                      cause=cause, split_ms=split)
        if self.journal is not None:
            self.journal.write("stall", **fields)
        print(f"[stall] step {opt_step} took {rec.step_time_ms:.1f} ms "
              f"(median {self._stalls.median_ms:.1f}): " + ", ".join(
                  f"{k} {v:.1f}" for k, v in split.items() if v),
              file=sys.stderr, flush=True)

    def _post_epoch(self, summary, eval_data_fn, epoch, save_every):
        # failure detection the reference has none of (SURVEY §5): a
        # diverged run must stop loudly, not burn the remaining epochs.
        # Checked at epoch granularity so the hot loop stays sync-free.
        loss_avg = summary.get("loss")
        if loss_avg is not None and not np.isfinite(loss_avg):
            relax = (self.health is not None
                     and getattr(self.health, "policy_explicit", True)
                     and not self.health.skip_nonfinite
                     and self.health.policy != "abort")
            if relax:
                # explicit warn policy: the health layer already journaled
                # every non-finite step; a poisoned epoch mean is reported,
                # not fatal — 'warn continues' is the policy's contract. A
                # defaulted policy (watchdog-only monitor) keeps the
                # pre-existing fatal behavior below.
                self.health.check_summary(epoch, {"loss": loss_avg})
            else:
                # leave postmortem artifacts intact: flush the in-flight
                # async checkpoint and close any open profiler trace first
                if self.ckpt is not None:
                    self.ckpt.wait()
                self._stop_trace()
                if self.journal is not None:
                    self.journal.write(
                        "note", note=f"diverged at epoch {epoch}: "
                                     f"mean loss {loss_avg}")
                if self.health is not None:
                    # abort policy (or a skip_step run whose mean still
                    # went non-finite): typed health event, then raise
                    self.health.check_summary(epoch, {"loss": loss_avg})
                raise FloatingPointError(
                    f"training diverged: epoch {epoch} mean loss is "
                    f"{loss_avg} (re-run with train.py --debug-nans to "
                    "locate the first non-finite op)"
                )

        # honor a SIGTERM that landed after the last step (or during eval,
        # which bails early): the epoch's training IS complete, save as such
        if self._pguard is not None and self._pguard.agreed(force=True):
            self._preempt_save(epoch)
            return "preempted"
        val_summary = {}
        if eval_data_fn is not None:
            val_summary = self.evaluate(eval_data_fn(), epoch=epoch)
        if self._pguard is not None and self._pguard.agreed(force=True):
            self._preempt_save(epoch)
            return "preempted"

        if (
            self.plateau is not None
            and self.plateau_metric in val_summary
            and self._base_lr is not None
        ):
            scale = self.plateau.step(val_summary[self.plateau_metric])
            self.state = self.state.replace(
                opt_state=_set_lr(self.state.opt_state, self._base_lr * scale)
            )

        if self.ckpt is not None and (epoch + 1) % save_every == 0:
            self._save_checkpoint(epoch, val_summary)

    def resume(self, step: Optional[int] = None) -> int:
        """Restore state + host loggers/plateau; returns next epoch to run.

        Rides CheckpointManager's fallback chain: with `step=None` a
        corrupt/incomplete latest step is quarantined (typed
        `ckpt_quarantine` journal event) and the newest valid one restores
        instead — resume() survives a save the crash tore in half. When
        NOTHING valid remains, returns 0: restarting from scratch is the
        honest floor of the degradation ladder, and the journal records
        why.

        Cross-mesh: the restore is handed THIS trainer's mesh, so a
        checkpoint written on a different topology (8 devices, say) lands
        re-placed against the current one (4, or 1) per the sharding
        metadata the save recorded — a preempted run resumes on whatever
        slice the scheduler gives back."""
        assert self.ckpt is not None, "no CheckpointManager configured"
        t0 = time.perf_counter()
        with span("checkpoint/restore", step=step if step is not None
                  else -1):
            self.state, host_state = self.ckpt.restore(self.state, step,
                                                       mesh=self.mesh)
        if self.journal is not None:
            # restore_ms: the goodput plane's restore feed — the gap
            # before this note lands in the checkpoint bucket
            self.journal.write(
                "note", note="resumed", step=int(self.state.step),
                host_state_found=host_state is not None,
                restore_ms=round((time.perf_counter() - t0) * 1e3, 3))
        if not getattr(self.ckpt, "last_restore_placed", False):
            # legacy manager (or nothing restored): re-place on this
            # trainer's mesh — per the sharding table when one is
            # attached, the old blanket replicate otherwise
            self.state = self._place_state(self.state)
        if self.ema is not None:
            restored_ema, ema_host = (None, None)
            if self._ema_ckpt is not None:
                # pin the EMA restore to the step the MAIN restore landed
                # on: after a quarantine fallback the EMA dir's latest can
                # be newer than the restored params, and a mixed-step
                # (params, shadow) pair would silently change eval
                ema_step = step if step is not None else int(self.state.step)
                try:
                    restored_ema, ema_host = self._ema_ckpt.restore_tree(
                        dict(self.ema.params), ema_step
                    )
                except Exception:
                    restored_ema, ema_host = (None, None)
            if restored_ema is not None:
                self.ema.params = restored_ema
                self.ema.load_state_dict(ema_host or {})
            else:
                # checkpoint predates --ema-decay, or the EMA shadow for
                # the restored step is itself missing/corrupt: seed from
                # the restored weights rather than the fresh init
                from deep_vision_tpu.train.ema import EmaParams

                self.ema = EmaParams(self.state.params, decay=self.ema.decay,
                                     warmup=self.ema.warmup)
        if not host_state:
            self._resume_data_state(None)
            return 0
        self.logger.load_state_dict(host_state.get("train_logger", {}))
        self.eval_logger.load_state_dict(host_state.get("val_logger", {}))
        if self.plateau is not None and "plateau" in host_state:
            self.plateau.load_state_dict(host_state["plateau"])
        self._resume_data_state(host_state.get("data_state"))
        return int(host_state.get("epoch", -1)) + 1

    def _resume_data_state(self, data_state) -> None:
        """Re-arm the input pipeline from the sidecar's DataLoaderState
        and journal the typed `data_resume` verdict: 'restored' = the
        loader will replay its exact position (byte-identical stream),
        'fresh' = the checkpoint predates --data-snapshot (or carried no
        loader state) and the stream restarts at epoch 0 — honest, and
        visible in obs_report instead of silent. A SnapshotMismatch
        (dataset changed on disk) propagates: resuming on a shifted
        stream is corruption, not degradation."""
        if self.data_loader is None:
            return
        if data_state:
            info = self.data_loader.load_state_dict(data_state)
            if self.journal is not None:
                self.journal.write(
                    "data_resume", verdict="restored",
                    epoch=int(info["epoch"]), batches=int(info["batches"]),
                    shard=info.get("shard"), record=info.get("record"))
        elif self.journal is not None:
            self.journal.write("data_resume", verdict="fresh",
                               epoch=0, batches=0)
