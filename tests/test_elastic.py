"""Elastic, preemption-native training (resilience/elastic.py + the
cross-mesh checkpoint path + the Trainer's SIGTERM escalation and
backend rebuild-replay).

The failure modes under test are the accelerator layer's own: a dropped
backend connection, a backend that hangs without raising, and a libtpu
client/terminal version skew. Cross-mesh restore
is proven the way the issue specifies: save under an 8-device CPU mesh
(conftest forces --xla_force_host_platform_device_count=8), restore
under meshes over 4 and 1 of those devices, assert bit-identical leaves
and correct re-placement.
"""
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.resilience import elastic
from deep_vision_tpu.resilience.elastic import (
    BACKEND_LOST_KINDS,
    BackendSupervisor,
    classify_backend_error,
)
from deep_vision_tpu.resilience.retry import RetryPolicy

# the string a libtpu client/terminal skew dies on, minutes into its compile
_R01_SKEW = (
    'FAILED_PRECONDITION: libtpu version mismatch: terminal has "TFRT TPU '
    'v5 lite ... cl/831091709", client AOT libtpu has "... cl/854318611". '
    "Client and terminal must use the same libtpu build"
)


def _no_sleep_policy(**kw) -> RetryPolicy:
    kw.setdefault("name", "test.backend")
    kw.setdefault("max_attempts", 4)
    kw.setdefault("base_delay_s", 0.0)
    kw.setdefault("jitter", 0.0)
    kw.setdefault("retry_on", Exception)
    return RetryPolicy(**kw)


class _Journal:
    def __init__(self):
        self.rows = []

    def write(self, event, **fields):
        self.rows.append({"event": event, **fields})

    def step(self, step, **fields):  # StepClock's per-step funnel
        self.rows.append({"event": "step", "step": step, **fields})

    def add_tap(self, fn):  # observer hooks (GoodputMeter, AlertEngine):
        pass                # inert here — these tests assert row trails

    def add_closer(self, fn):
        pass


# -- classification -----------------------------------------------------------

class TestClassification:
    def test_version_skew_from_the_r01_artifact(self):
        assert classify_backend_error(
            jax.errors.JaxRuntimeError(_R01_SKEW)) == "version_skew"
        assert classify_backend_error(_R01_SKEW) == "version_skew"

    def test_connection_loss_signatures(self):
        # the messages PJRT really raises, plus the usual transport endings
        for msg in ("UNAVAILABLE: connection reset by peer",
                    "INTERNAL: stream body closed",
                    "socket closed: UNAVAILABLE",
                    "the backend connection was dropped",
                    "Broken pipe"):
            assert classify_backend_error(
                RuntimeError(msg)) == "connection_lost", msg

    def test_timeout_signatures(self):
        for msg in ("DEADLINE_EXCEEDED: collective timed out",
                    "heartbeat missed",
                    "backend liveness probe still blocked after 180s "
                    "(backend hung?)"):
            assert classify_backend_error(msg) == "timeout", msg

    def test_non_transport_exceptions_stay_unknown(self):
        # a message can LOOK transient; the exception type gates it
        assert classify_backend_error(
            ValueError("shape mismatch in timeout_config.py")) == "unknown"
        assert classify_backend_error(
            FloatingPointError("diverged")) == "unknown"
        assert classify_backend_error(KeyboardInterrupt()) == "unknown"
        assert classify_backend_error(RuntimeError("boring bug")) == "unknown"
        # raw OSError/ConnectionError is the STORAGE layer's weather (its
        # own RetryPolicy owns it): it must NOT trigger a backend teardown
        assert classify_backend_error(
            ConnectionResetError("Connection reset by peer")) == "unknown"
        assert classify_backend_error(
            TimeoutError("read timed out")) == "unknown"

    def test_kinds_enum_matches_check_journal(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "check_journal", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools", "check_journal.py"))
        cj = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cj)
        assert set(BACKEND_LOST_KINDS) == cj.BACKEND_LOST_KINDS


def test_backend_alive_detects_block_error_and_health():
    import time

    # the probe the preflight runs: a blocked backend is caught by the
    # join timeout, an erroring one by its exception
    ok, err, kind = elastic.backend_alive(0.2, probe=lambda: time.sleep(60))
    assert not ok and "blocked" in err and kind == "timeout"
    ok, err, kind = elastic.backend_alive(5.0, probe=lambda: 1 / 0)
    assert not ok and "ZeroDivisionError" in err and kind == "unknown"
    ok, err, kind = elastic.backend_alive(5.0, probe=lambda: 1.0)
    assert ok and err is None and kind is None


# -- supervisor ---------------------------------------------------------------

class TestBackendSupervisor:
    def test_retryable_kinds_and_budget(self):
        sup = BackendSupervisor(policy=_no_sleep_policy(max_attempts=3))
        e = RuntimeError("socket closed: UNAVAILABLE")
        assert sup.should_retry(1, e) and sup.should_retry(2, e)
        assert not sup.should_retry(3, e)  # budget: 2 retries + first try

    def test_version_skew_never_retried(self):
        sup = BackendSupervisor(policy=_no_sleep_policy())
        assert not sup.should_retry(1, RuntimeError(_R01_SKEW))

    def test_unknown_failure_never_retried(self):
        bug = RuntimeError("a plain bug")
        assert not BackendSupervisor(
            policy=_no_sleep_policy()).should_retry(1, bug)

    def test_journals_typed_events(self):
        j = _Journal()
        sup = BackendSupervisor(policy=_no_sleep_policy(), journal=j)
        retrying = sup.on_failure(
            1, RuntimeError("DEADLINE_EXCEEDED: no answer in 30s"), step=42,
            context="train/fit")
        assert retrying
        sup.on_recovered(1, step=43)
        lost = [r for r in j.rows if r["event"] == "backend_lost"]
        rec = [r for r in j.rows if r["event"] == "backend_recovered"]
        assert len(lost) == 1 and lost[0]["kind"] == "timeout"
        assert lost[0]["attempt"] == 1 and lost[0]["retrying"] is True
        assert lost[0]["step"] == 42 and lost[0]["context"] == "train/fit"
        assert len(rec) == 1 and rec[0]["attempt"] == 1
        # the shared retry event rides along for the existing dashboards
        assert any(r["event"] == "retry" and r["outcome"] == "retrying"
                   for r in j.rows)

    def test_backoff_jitter_rng_advances_per_draw(self):
        # the _ACTIVE_POLICY regression this design removes: a re-seeded
        # policy would re-draw the SAME "jittered" delay every retry
        slept = []
        sup = BackendSupervisor(policy=_no_sleep_policy(
            base_delay_s=1.0, jitter=0.5, multiplier=1.0,
            sleep=slept.append), clear_caches_after=99)
        sup.recover(1)
        sup.recover(1)
        assert len(slept) == 2 and slept[0] != slept[1]


# -- cross-mesh sharding metadata --------------------------------------------

def _tp_tree(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return {
        "w": jax.device_put(
            jnp.arange(32 * 16, dtype=jnp.float32).reshape(32, 16),
            NamedSharding(mesh, P(None, "model"))),
        "b": jax.device_put(jnp.arange(16, dtype=jnp.float32),
                            NamedSharding(mesh, P())),
    }


class TestShardingMeta:
    def test_meta_is_json_serializable_and_complete(self, mesh4x2):
        meta = elastic.sharding_meta(_tp_tree(mesh4x2))
        meta2 = json.loads(json.dumps(meta))  # the sidecar round trip
        assert meta2["mesh"] == {"data": 4, "model": 2}
        assert meta2["device_count"] == 8
        assert len(meta2["leaves"]) == 2
        w = [v for k, v in meta2["leaves"].items() if "'w'" in k][0]
        assert w == [None, "model"]

    def test_replace_preserves_spec_on_a_compatible_smaller_mesh(
            self, mesh4x2):
        from deep_vision_tpu.parallel.mesh import create_mesh

        tree = _tp_tree(mesh4x2)
        meta = json.loads(json.dumps(elastic.sharding_meta(tree)))
        mesh22 = create_mesh(devices=jax.devices()[:4], data=2, model=2)
        placed, stats = elastic.replace_on_mesh(
            jax.tree_util.tree_map(np.asarray, tree), meta, mesh22)
        assert "model" in str(placed["w"].sharding.spec)
        assert len(placed["w"].sharding.device_set) == 4
        assert stats["resharded"] == 1
        np.testing.assert_array_equal(np.asarray(placed["w"]),
                                      np.asarray(tree["w"]))

    def test_replace_drops_axes_the_new_mesh_cannot_honor(self, mesh4x2):
        from jax.sharding import Mesh

        tree = _tp_tree(mesh4x2)
        meta = json.loads(json.dumps(elastic.sharding_meta(tree)))
        # a mesh with NO model axis at all: the spec entry must drop
        data_only = Mesh(np.asarray(jax.devices()[:2]), ("data",))
        placed, stats = elastic.replace_on_mesh(
            jax.tree_util.tree_map(np.asarray, tree), meta, data_only)
        assert tuple(placed["w"].sharding.spec) == ()
        assert stats["dropped_dims"] == 1

    def test_replace_drops_indivisible_dims(self, mesh4x2):
        from jax.sharding import Mesh

        tree = _tp_tree(mesh4x2)
        meta = json.loads(json.dumps(elastic.sharding_meta(tree)))
        # model axis of 3 does not divide the 16-wide dim: replicate it
        mesh3 = Mesh(np.asarray(jax.devices()[:3]).reshape(1, 3),
                     ("data", "model"))
        placed, _ = elastic.replace_on_mesh(
            jax.tree_util.tree_map(np.asarray, tree), meta, mesh3)
        assert tuple(placed["w"].sharding.spec) == ()

    def test_none_meta_places_replicated(self, mesh8):
        placed, stats = elastic.replace_on_mesh(
            {"w": np.ones((4, 4), np.float32)}, None, mesh8)
        assert len(placed["w"].sharding.device_set) == 8
        assert tuple(placed["w"].sharding.spec) == ()
        assert stats["resharded"] == 0


# -- cross-mesh checkpoint restore (the tentpole proof) -----------------------

def _tiny_state(mesh):
    from deep_vision_tpu.core.train_state import create_train_state
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.parallel.mesh import replicated
    from deep_vision_tpu.train.optimizers import build_optimizer

    state = create_train_state(
        get_model("lenet5", num_classes=10),
        build_optimizer("sgd", learning_rate=0.1),
        jnp.ones((2, 32, 32, 1), jnp.float32))
    return jax.device_put(state, replicated(mesh))


class TestCrossMeshRestore:
    @pytest.mark.slow
    def test_save_on_8_restore_on_4_and_1(self, mesh8, tmp_path):
        """The issue's proof: checkpoint under 8 devices, restore under 4
        and 1 — bit-identical leaves, re-placed on the current mesh."""
        from deep_vision_tpu.core import CheckpointManager
        from deep_vision_tpu.parallel.mesh import create_mesh

        state = _tiny_state(mesh8).replace(step=jnp.asarray(9, jnp.int32))
        cm = CheckpointManager(str(tmp_path))
        assert cm.save(9, state, host_state={"epoch": 4})
        cm.close()
        want = jax.tree_util.tree_leaves(
            jax.device_get({"p": state.params, "o": state.opt_state}))
        for nd in (4, 1):
            mesh = create_mesh(devices=jax.devices()[:nd])
            cm2 = CheckpointManager(str(tmp_path))
            restored, host = cm2.restore(_tiny_state(mesh), mesh=mesh)
            cm2.close()
            assert host == {"epoch": 4}  # sharding meta stripped
            assert cm2.last_restore_placed
            assert int(restored.step) == 9
            got = jax.tree_util.tree_leaves(
                jax.device_get({"p": restored.params,
                                "o": restored.opt_state}))
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            placements = {len(x.sharding.device_set)
                          for x in jax.tree_util.tree_leaves(
                              restored.params)}
            assert placements == {nd}

    def test_tree_roundtrip_keeps_tp_layout_across_meshes(self, mesh4x2,
                                                          tmp_path):
        from deep_vision_tpu.core import CheckpointManager
        from deep_vision_tpu.parallel.mesh import create_mesh, replicated

        tree = _tp_tree(mesh4x2)
        cm = CheckpointManager(str(tmp_path))
        assert cm.save_tree(1, tree)  # no host_state: sidecar still written
        cm.close()
        assert os.path.exists(os.path.join(str(tmp_path),
                                           "host_state_1.json"))
        mesh22 = create_mesh(devices=jax.devices()[:4], data=2, model=2)
        cm2 = CheckpointManager(str(tmp_path))
        template = {k: jax.device_put(jnp.zeros_like(v), replicated(mesh22))
                    for k, v in tree.items()}
        out, host = cm2.restore_tree(template, mesh=mesh22)
        cm2.close()
        assert host == {}  # only the reserved key was in the sidecar
        assert "model" in str(out["w"].sharding.spec)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))

    def test_legacy_restore_without_mesh_unchanged(self, mesh8, tmp_path):
        from deep_vision_tpu.core import CheckpointManager

        state = _tiny_state(mesh8)
        cm = CheckpointManager(str(tmp_path))
        assert cm.save(1, state, host_state={"epoch": 0})
        cm.wait()
        restored, host = cm.restore(_tiny_state(mesh8))
        cm.close()
        assert host == {"epoch": 0}
        assert not cm.last_restore_placed
        assert int(restored.step) == 0  # saved at a fresh step


# -- preflight ----------------------------------------------------------------

class TestPreflight:
    def test_mesh_shape_pass_and_fail(self):
        from deep_vision_tpu.tools import preflight as pf

        assert pf.check_mesh_shape(8, data=4, model=2).ok
        assert not pf.check_mesh_shape(8, data=4, model=3).ok
        r = pf.check_mesh_shape(6, expect_devices=8)
        assert not r.ok and "degraded" in r.detail

    def test_client_versions_pass_and_skew(self):
        from deep_vision_tpu.tools import preflight as pf

        assert pf.check_client_versions("0.4.37", "0.4.36").ok  # patch drift
        r = pf.check_client_versions("0.5.0", "0.4.30")
        assert not r.ok and r.kind == "version_skew"

    def test_ckpt_dir_pass_and_fail(self, tmp_path):
        from deep_vision_tpu.tools import preflight as pf

        assert pf.check_ckpt_dir(str(tmp_path / "ok")).ok
        # leftover probe files are cleaned up
        assert not [p for p in os.listdir(str(tmp_path / "ok"))
                    if p.startswith(".preflight")]
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("file where a dir must go")
        assert not pf.check_ckpt_dir(str(blocker)).ok

    def test_backend_probe_classifies_the_r01_skew(self):
        from deep_vision_tpu.tools import preflight as pf

        def skewed_probe():
            raise jax.errors.JaxRuntimeError(_R01_SKEW)

        r = pf.check_backend(budget_s=10.0, probe=skewed_probe)
        assert not r.ok and r.kind == "version_skew"

    def test_backend_probe_reports_a_hung_backend_as_timeout(self):
        import time

        from deep_vision_tpu.tools import preflight as pf

        r = pf.check_backend(budget_s=0.2, probe=lambda: time.sleep(60))
        assert not r.ok and r.kind == "timeout"

    def test_run_preflight_passes_on_cpu(self, tmp_path):
        from deep_vision_tpu.tools import preflight as pf

        j = _Journal()
        ok, results = pf.run_preflight(ckpt_dir=str(tmp_path / "ck"),
                                       budget_s=60.0, journal=j)
        assert ok, [(r.name, r.detail) for r in results if not r.ok]
        assert [r.name for r in results] == [
            "client_versions", "backend", "mesh_shape",
            "sharding_tables", "ckpt_dir"]
        assert any(r["event"] == "note" and r.get("note") == "preflight"
                   for r in j.rows)

    def test_failed_backend_skips_downstream_checks(self):
        from deep_vision_tpu.tools import preflight as pf

        def dead():
            raise RuntimeError("socket closed: UNAVAILABLE")

        ok, results = pf.run_preflight(probe=dead, budget_s=10.0,
                                       shard_tables=False)
        assert not ok
        assert [r.name for r in results] == ["client_versions", "backend"]

    def test_cli_pass_and_fail(self, tmp_path, capsys):
        from deep_vision_tpu.tools import preflight as pf

        assert pf.main(["--ckpt-dir", str(tmp_path / "ck"), "--json"]) == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        doc = json.loads(line)
        assert doc["ok"] and len(doc["checks"]) == 5
        assert "sharding_tables" in [c["name"] for c in doc["checks"]]
        assert pf.main(["--expect-devices", "999",
                        "--no-shard-check"]) == 1


# -- SIGTERM escalation: checkpoint-now-and-requeue ---------------------------

def _synthetic_batches(n=3, bs=16):
    rng = np.random.RandomState(0)
    return [{"image": rng.rand(bs, 32, 32, 1).astype(np.float32),
             "label": rng.randint(0, 10, (bs,)).astype(np.int32)}
            for _ in range(n)]


def _make_trainer(mesh, tmp_path, journal=None, **kw):
    from deep_vision_tpu.core import CheckpointManager
    from deep_vision_tpu.losses.classification import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    return Trainer(
        get_model("lenet5", num_classes=10),
        build_optimizer("adam", 1e-3),
        classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)),
        mesh=mesh,
        checkpoint_manager=CheckpointManager(str(tmp_path)),
        journal=journal,
        **kw,
    )


class TestPreemptEscalation:
    def test_sigterm_checkpoints_journals_and_requests_requeue(
            self, mesh8, tmp_path):
        from deep_vision_tpu.obs import flight

        flight.clear_requeue()
        j = _Journal()
        trainer = _make_trainer(mesh8, tmp_path, journal=j)
        data = _synthetic_batches()

        def preempting():
            for i, b in enumerate(data):
                if i == 1:
                    os.kill(os.getpid(), signal.SIGTERM)
                yield b

        try:
            trainer.fit(preempting, epochs=3)
            assert trainer.preempted
            assert flight.requeue_requested()
            pc = [r for r in j.rows if r["event"] == "preempt_checkpoint"]
            assert len(pc) == 1
            assert pc[0]["saved"] is True
            assert pc[0]["step"] == int(trainer.state.step)
            assert pc[0]["dir"] == trainer.ckpt.directory
            # ordering: the checkpoint event precedes preempt_checkpoint
            events = [r["event"] for r in j.rows]
            assert events.index("checkpoint") < events.index(
                "preempt_checkpoint")
        finally:
            flight.clear_requeue()
            trainer.close()

    def test_requeue_latch_set_even_without_checkpoint_manager(
            self, mesh8):
        from deep_vision_tpu.losses.classification import (
            classification_loss_fn,
        )
        from deep_vision_tpu.models import get_model
        from deep_vision_tpu.obs import flight
        from deep_vision_tpu.train import Trainer, build_optimizer

        flight.clear_requeue()
        j = _Journal()
        trainer = Trainer(
            get_model("lenet5", num_classes=10),
            build_optimizer("adam", 1e-3), classification_loss_fn,
            sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8, journal=j)
        data = _synthetic_batches()

        def preempting():
            for i, b in enumerate(data):
                if i == 1:
                    os.kill(os.getpid(), signal.SIGTERM)
                yield b

        try:
            trainer.fit(preempting, epochs=2)
            assert flight.requeue_requested()
            pc = [r for r in j.rows if r["event"] == "preempt_checkpoint"]
            assert len(pc) == 1 and pc[0]["saved"] is False
        finally:
            flight.clear_requeue()
            trainer.close()


# -- Trainer backend-loss rebuild-replay --------------------------------------

class TestTrainerRebuildReplay:
    @pytest.mark.slow
    def test_backend_loss_mid_run_resumes_from_checkpoint(self, mesh8,
                                                          tmp_path):
        """Epoch 0 checkpoints; the first step of epoch 1 dies with a
        connection-loss signature. The supervisor must rebuild the jitted
        step, restore the epoch-0 checkpoint, replay, and finish — with
        typed backend_lost/backend_recovered events bracketing it."""
        j = _Journal()
        sup = BackendSupervisor(policy=_no_sleep_policy(), journal=j)
        trainer = _make_trainer(mesh8, tmp_path, journal=j,
                                backend_supervisor=sup)
        data = _synthetic_batches(n=3)
        steps_per_epoch = len(data)

        orig = trainer._train_step
        fired = {"n": 0}

        def flaky(state, batch):
            # the wrapper dies ONCE, at the first step of epoch 1; the
            # recovery path re-creates _train_step so the sabotage is gone
            # exactly the way a rebuilt client replaces a dead one
            fired["n"] += 1
            if fired["n"] == steps_per_epoch + 1:
                raise RuntimeError("UNAVAILABLE: connection reset by peer")
            return orig(state, batch)

        trainer._train_step = flaky
        try:
            trainer.fit(lambda: data, epochs=2)
            assert int(trainer.state.step) == 2 * steps_per_epoch
            lost = [r for r in j.rows if r["event"] == "backend_lost"]
            rec = [r for r in j.rows if r["event"] == "backend_recovered"]
            assert len(lost) == 1 and lost[0]["kind"] == "connection_lost"
            assert len(rec) == 1 and rec[0]["step"] == 2 * steps_per_epoch
            assert any(r["event"] == "note" and r.get("note") == "resumed"
                       for r in j.rows)
            # the rebuilt step is a REAL jitted callable, not the sabotage
            assert trainer._train_step is not flaky
        finally:
            trainer.close()

    def test_unclassified_and_skew_failures_propagate(self, mesh8,
                                                      tmp_path):
        sup = BackendSupervisor(policy=_no_sleep_policy())
        trainer = _make_trainer(mesh8, tmp_path, backend_supervisor=sup)
        data = _synthetic_batches(n=2)

        def bug(state, batch):
            raise RuntimeError(_R01_SKEW)

        trainer._train_step = bug
        try:
            with pytest.raises(RuntimeError, match="libtpu"):
                trainer.fit(lambda: data, epochs=1)
        finally:
            trainer.close()

    def test_no_supervisor_keeps_failfast_behavior(self, mesh8, tmp_path):
        trainer = _make_trainer(mesh8, tmp_path)
        data = _synthetic_batches(n=2)

        def dead(state, batch):
            raise RuntimeError("socket closed: UNAVAILABLE")

        trainer._train_step = dead
        try:
            with pytest.raises(RuntimeError, match="socket closed"):
                trainer.fit(lambda: data, epochs=1)
        finally:
            trainer.close()


# -- sharding-coverage hard check ---------------------------------------------

class TestShardingCoverage:
    def test_counts_and_gauges(self, mesh4x2):
        from deep_vision_tpu.obs.registry import Registry
        from deep_vision_tpu.parallel.mesh import (
            assert_sharding_coverage,
            infer_tp_sharding,
        )

        tree = {"big": jnp.ones((64, 64), jnp.float32),
                "bias": jnp.ones((8,), jnp.float32),
                "step": jnp.asarray(1, jnp.int32)}
        sh = infer_tp_sharding(tree, mesh4x2, min_size=64)
        reg = Registry()
        stats = assert_sharding_coverage(tree, sh, mesh4x2, min_sharded=1,
                                         registry=reg)
        # replicated_paths names the leaves that fell back to
        # replication (the floor-failure message uses them — the 108->34
        # incident was undebuggable from bare counts)
        assert stats == {"float_leaves": 2, "sharded": 1, "replicated": 1,
                         "replicated_paths": ["['bias']"],
                         "unmatched": []}
        assert reg.gauge("parallel_sharded_leaves").value == 1
        assert reg.gauge("parallel_float_leaves").value == 2

    def test_regression_below_floor_fails_loudly(self, mesh4x2):
        from deep_vision_tpu.parallel.mesh import (
            ShardingCoverageError,
            assert_sharding_coverage,
            infer_tp_sharding,
        )

        tree = {"big": jnp.ones((64, 64), jnp.float32)}
        sh = infer_tp_sharding(tree, mesh4x2, min_size=10**9)  # all repl.
        with pytest.raises(ShardingCoverageError, match="regressed"):
            assert_sharding_coverage(tree, sh, mesh4x2, min_sharded=1,
                                     registry=None)

    def test_unmatched_float_leaf_fails_with_its_path(self, mesh4x2):
        from deep_vision_tpu.parallel.mesh import (
            ShardingCoverageError,
            assert_sharding_coverage,
            infer_tp_sharding,
        )

        tree = {"a": jnp.ones((4, 4), jnp.float32),
                "b": jnp.ones((4, 4), jnp.float32)}
        sh = dict(infer_tp_sharding(tree, mesh4x2))
        del sh["b"]  # a rule that stopped matching
        with pytest.raises(ShardingCoverageError, match="'b'"):
            assert_sharding_coverage(tree, sh, mesh4x2)


# -- requeue latch ------------------------------------------------------------

def test_requeue_latch_roundtrip():
    from deep_vision_tpu.obs import flight

    flight.clear_requeue()
    assert not flight.requeue_requested()
    flight.request_requeue()
    assert flight.requeue_requested()
    flight.clear_requeue()
    assert not flight.requeue_requested()
    assert flight.REQUEUE_EXIT_CODE == 75  # EX_TEMPFAIL, the requeue code
