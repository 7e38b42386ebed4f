"""Resilience subsystem tests: retry policy, fault injection, bounded
bad-record degradation, checksummed/quarantining checkpoints, and the
crash-consistency e2e (SIGKILL mid-checkpoint-save, resume recovers).

Kept deterministic: every fault comes from resilience.faults (seeded) or
from bytes this file flips itself; retries run with injected sleep.
"""
import json
import os
import signal
import struct
import subprocess
import sys

import numpy as np
import pytest

from deep_vision_tpu.resilience import (
    FaultInjected,
    FaultInjector,
    FaultSpecError,
    RetryPolicy,
    faults,
)


@pytest.fixture(autouse=True)
def _clean_faults():
    """No test may leak an installed injector (module-global) or the
    worker-inheritance env vars into its neighbors."""
    yield
    faults.install(None)
    os.environ.pop(faults.ENV_SPEC, None)
    os.environ.pop(faults.ENV_SEED, None)


class _Journal:
    """Collects journal rows; stands in for obs.RunJournal."""

    def __init__(self):
        self.rows = []

    def write(self, event, **fields):
        self.rows.append({"event": event, **fields})


# -- RetryPolicy -------------------------------------------------------------

class TestRetryPolicy:
    def _policy(self, **kw):
        kw.setdefault("jitter", 0)
        kw.setdefault("base_delay_s", 0.01)
        sleeps = []
        p = RetryPolicy(sleep=sleeps.append, **kw)
        return p, sleeps

    def test_recovers_after_transient_failures(self):
        p, sleeps = self._policy(max_attempts=5)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise IOError("transient")
            return "ok"

        assert p.call(flaky) == "ok"
        assert len(calls) == 3
        assert len(sleeps) == 2

    def test_gives_up_and_reraises_unchanged(self):
        p, _ = self._policy(max_attempts=3)
        boom = IOError("still down")

        def always():
            raise boom

        with pytest.raises(IOError) as ei:
            p.call(always)
        assert ei.value is boom

    def test_non_retryable_class_fails_fast(self):
        p, sleeps = self._policy(max_attempts=5)
        calls = []

        def bug():
            calls.append(1)
            raise ValueError("a real bug, not weather")

        with pytest.raises(ValueError):
            p.call(bug)
        assert len(calls) == 1 and sleeps == []

    def test_keyboard_interrupt_never_retried(self):
        p, _ = self._policy(max_attempts=5, retry_on=BaseException)
        with pytest.raises(KeyboardInterrupt):
            p.call(lambda: (_ for _ in ()).throw(KeyboardInterrupt()))

    def test_retry_if_predicate_extends_classification(self):
        p, _ = self._policy(
            max_attempts=3,
            retry_if=lambda e: "UNAVAILABLE" in str(e))
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise RuntimeError("UNAVAILABLE: connection reset")
            return 7

        assert p.call(flaky) == 7

    def test_deadline_stops_before_sleeping_past_it(self):
        clock = [0.0]
        p = RetryPolicy(max_attempts=100, base_delay_s=10.0, jitter=0,
                        deadline_s=5.0, sleep=lambda d: None,
                        clock=lambda: clock[0])
        calls = []

        def always():
            calls.append(1)
            raise IOError("down")

        with pytest.raises(IOError):
            p.call(always)
        assert len(calls) == 1  # first delay (10s) would cross the 5s budget

    def test_backoff_schedule_exponential_and_capped(self):
        p, _ = self._policy(max_attempts=9, base_delay_s=1.0, multiplier=2.0,
                            max_delay_s=5.0)
        assert [p.delay(a) for a in (1, 2, 3, 4)] == [1.0, 2.0, 4.0, 5.0]

    def test_jitter_deterministic_per_seed(self):
        a = RetryPolicy(base_delay_s=1.0, jitter=0.5, seed=3)
        b = RetryPolicy(base_delay_s=1.0, jitter=0.5, seed=3)
        assert [a.delay(1) for _ in range(4)] == [b.delay(1) for _ in range(4)]

    def test_decorator_form(self):
        p, _ = self._policy(max_attempts=3)
        calls = []

        @p
        def flaky(x):
            calls.append(x)
            if len(calls) < 2:
                raise OSError("blip")
            return x * 2

        assert flaky(21) == 42
        assert flaky.retry_policy is p

    def test_attempts_loop_form(self):
        p, _ = self._policy(max_attempts=4)
        tries = []
        for attempt in p.attempts():
            with attempt:
                tries.append(1)
                if len(tries) < 3:
                    raise IOError("blip")
        assert len(tries) == 3

    def test_attempts_loop_reraises_on_budget(self):
        p, _ = self._policy(max_attempts=2)
        with pytest.raises(IOError):
            for attempt in p.attempts():
                with attempt:
                    raise IOError("down")

    def test_journal_events_typed(self):
        j = _Journal()
        p = RetryPolicy(name="t", max_attempts=3, base_delay_s=0, jitter=0,
                        journal=j, sleep=lambda d: None)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise IOError("blip")

        p.call(flaky)
        outcomes = [(r["event"], r["outcome"]) for r in j.rows]
        assert outcomes == [("retry", "retrying"), ("retry", "recovered")]
        assert j.rows[0]["name"] == "t" and j.rows[0]["attempt"] == 1

        j.rows.clear()
        with pytest.raises(ValueError):
            p.call(lambda: (_ for _ in ()).throw(ValueError("bug")))
        assert [(r["event"], r["outcome"]) for r in j.rows] == \
            [("retry", "gave_up")]


# -- FaultInjector -----------------------------------------------------------

class TestFaultInjector:
    def test_parse_rejects_unknown_point_kind_and_shape(self):
        for bad in ("nope.read:io_error", "data.read:frobnicate",
                    "data.read", "data.read:io_error@zero",
                    "data.read:io_error@-1"):
            with pytest.raises(FaultSpecError):
                FaultInjector.parse(bad)

    def test_nth_hit_fires_exactly_once(self):
        inj = FaultInjector.parse("data.read:io_error@3")
        faults.install(inj)
        hits = []
        for i in range(6):
            try:
                faults.fire("data.read")
                hits.append("ok")
            except FaultInjected:
                hits.append("boom")
        assert hits == ["ok", "ok", "boom", "ok", "ok", "ok"]

    def test_probability_sequence_reproducible_per_seed(self):
        def seq(seed):
            inj = FaultInjector.parse("data.read:io_error@0.3", seed=seed)
            out = []
            for _ in range(50):
                try:
                    inj.fire("data.read")
                    out.append(0)
                except FaultInjected:
                    out.append(1)
            return out

        assert seq(11) == seq(11)
        assert seq(11) != seq(12)
        assert sum(seq(11)) > 0

    def test_injected_error_is_an_ioerror(self):
        # handlers for the genuine article (retry/budget code catching
        # IOError/OSError) must treat injected faults identically
        assert issubclass(FaultInjected, IOError)

    def test_points_are_scoped(self):
        faults.install(FaultInjector.parse("ckpt.save:io_error@1"))
        faults.fire("data.read")  # different point: no fault
        with pytest.raises(FaultInjected):
            faults.fire("ckpt.save")

    def test_corrupt_transform_mangles_bytes(self):
        inj = FaultInjector.parse("ckpt.sidecar:corrupt@1")
        data = b"x" * 64
        mangled = inj.transform("ckpt.sidecar", data)
        assert mangled != data
        assert inj.transform("ckpt.sidecar", data) == data  # once only

    def test_disabled_hooks_are_noops(self):
        assert faults.installed() is None
        faults.fire("data.read")
        assert faults.transform("ckpt.sidecar", b"abc") == b"abc"

    def test_install_spec_exports_and_clears_env(self):
        faults.install_spec("data.read:io_error@2", seed=9)
        assert os.environ[faults.ENV_SPEC] == "data.read:io_error@2"
        assert os.environ[faults.ENV_SEED] == "9"
        faults.install_spec(None)
        assert faults.ENV_SPEC not in os.environ
        assert faults.installed() is None

    def test_fired_fault_journals_and_skips_journal_flush_point(self):
        j = _Journal()
        inj = FaultInjector.parse(
            "data.read:io_error@1;journal.flush:io_error@1", journal=j)
        faults.install(inj)
        with pytest.raises(FaultInjected):
            faults.fire("data.read")
        with pytest.raises(FaultInjected):
            faults.fire("journal.flush")
        points = [r["point"] for r in j.rows if r["event"] == "fault"]
        assert points == ["data.read"]  # journal.flush must not self-journal


# -- bad-record budget + tolerant reader -------------------------------------

class TestBadRecordBudget:
    def test_parse_count_vs_fraction(self):
        from deep_vision_tpu.data.records import BadRecordBudget

        assert BadRecordBudget.parse("5").max_count == 5
        assert BadRecordBudget.parse("0.25").max_fraction == 0.25
        with pytest.raises(ValueError):
            BadRecordBudget.parse("0")

    def test_count_budget_allows_n_then_aborts(self, tmp_path):
        from deep_vision_tpu.data.records import (
            BadRecordBudget,
            BadRecordBudgetExceeded,
        )

        b = BadRecordBudget(max_count=2,
                            dead_letter_path=str(tmp_path / "dl.jsonl"))
        b.record_bad("f", 0, "r1")
        b.record_bad("f", 10, "r2")
        with pytest.raises(BadRecordBudgetExceeded):
            b.record_bad("f", 20, "r3")
        rows = [json.loads(x) for x in
                (tmp_path / "dl.jsonl").read_text().splitlines()]
        assert [r["offset"] for r in rows] == [0, 10, 20]
        assert all(r["path"] == "f" and r["reason"] for r in rows)

    def test_fraction_budget_waits_for_min_seen(self):
        from deep_vision_tpu.data.records import (
            BadRecordBudget,
            BadRecordBudgetExceeded,
        )

        b = BadRecordBudget(max_fraction=0.1, min_seen=10)
        b.record_bad("f", 0, "early")   # 1/1 bad, but below min_seen
        b.record_ok(7)                  # seen = 8
        b.record_bad("f", 1, "second")  # seen = 9: still below min_seen
        with pytest.raises(BadRecordBudgetExceeded):
            b.record_bad("f", 2, "third")  # seen = 10: 3/10 > 0.1

    def test_journal_dropped_on_pickle(self):
        import pickle

        from deep_vision_tpu.data.records import BadRecordBudget

        b = BadRecordBudget(max_count=5, journal=_Journal())
        b2 = pickle.loads(pickle.dumps(b))
        assert b2.journal is None and b2.max_count == 5
        b2.record_bad("f", 0, "works without a journal")


def _write_shard(path, payloads):
    from deep_vision_tpu.data.records import write_records

    write_records(str(path), payloads)
    return str(path)


def _record_offsets(path):
    """[(offset, length)] per record, walking the clean framing."""
    out = []
    with open(path, "rb") as f:
        while True:
            off = f.tell()
            header = f.read(8)
            if not header:
                return out
            (length,) = struct.unpack("<Q", header)
            f.read(4)
            f.read(length)
            f.read(4)
            out.append((off, length))


class TestTolerantReader:
    def _flip(self, path, byte_at):
        with open(path, "r+b") as f:
            f.seek(byte_at)
            b = f.read(1)
            f.seek(byte_at)
            f.write(bytes([b[0] ^ 0xFF]))

    def test_clean_file_yields_offsets_and_payloads(self, tmp_path):
        from deep_vision_tpu.data.records import (
            BadRecordBudget,
            read_records_tolerant,
        )

        payloads = [b"aa", b"bbbb", b"cccccc"]
        p = _write_shard(tmp_path / "s", payloads)
        budget = BadRecordBudget(max_count=10)
        got = list(read_records_tolerant(p, budget))
        assert [d for _, d in got] == payloads
        assert [o for o, _ in got] == [o for o, _ in _record_offsets(p)]
        assert budget.bad == 0 and budget.ok == 3

    def test_data_corruption_skips_exactly_that_record(self, tmp_path):
        from deep_vision_tpu.data.records import (
            BadRecordBudget,
            read_records_tolerant,
        )

        payloads = [b"record-%d" % i for i in range(5)]
        p = _write_shard(tmp_path / "s", payloads)
        off, _ = _record_offsets(p)[2]
        self._flip(p, off + 12 + 3)  # a data byte of record 2
        budget = BadRecordBudget(max_count=10,
                                 dead_letter_path=str(tmp_path / "dl.jsonl"))
        got = [d for _, d in read_records_tolerant(p, budget)]
        assert got == [payloads[0], payloads[1], payloads[3], payloads[4]]
        row = json.loads((tmp_path / "dl.jsonl").read_text().splitlines()[0])
        assert row["offset"] == off and "corrupt record data" in row["reason"]

    def test_header_corruption_dead_letters_shard_remainder(self, tmp_path):
        from deep_vision_tpu.data.records import (
            BadRecordBudget,
            read_records_tolerant,
        )

        payloads = [b"record-%d" % i for i in range(5)]
        p = _write_shard(tmp_path / "s", payloads)
        off, _ = _record_offsets(p)[2]
        self._flip(p, off + 2)  # a length byte: framing is gone
        budget = BadRecordBudget(max_count=10)
        got = [d for _, d in read_records_tolerant(p, budget)]
        assert got == payloads[:2]  # remainder skipped as ONE budget event
        assert budget.bad == 1

    def test_truncated_tail_tolerated(self, tmp_path):
        from deep_vision_tpu.data.records import (
            BadRecordBudget,
            read_records_tolerant,
        )

        payloads = [b"one", b"two", b"three"]
        p = _write_shard(tmp_path / "s", payloads)
        size = os.path.getsize(p)
        with open(p, "r+b") as f:
            f.truncate(size - 5)
        budget = BadRecordBudget(max_count=10)
        got = [d for _, d in read_records_tolerant(p, budget)]
        assert got == payloads[:2]
        assert budget.bad == 1

    def test_strict_reader_still_raises(self, tmp_path):
        from deep_vision_tpu.data.records import read_records

        p = _write_shard(tmp_path / "s", [b"payload-zero", b"payload-one"])
        off, _ = _record_offsets(p)[1]
        self._flip(p, off + 12 + 2)
        with pytest.raises(IOError):
            list(read_records(p))

    def test_injected_read_fault_burns_budget_not_run(self, tmp_path):
        from deep_vision_tpu.data.records import (
            BadRecordBudget,
            read_records_tolerant,
        )

        payloads = [b"r%d" % i for i in range(6)]
        p = _write_shard(tmp_path / "s", payloads)
        faults.install(FaultInjector.parse("data.read:io_error@2"))
        budget = BadRecordBudget(max_count=10)
        got = [d for _, d in read_records_tolerant(p, budget)]
        assert len(got) == 5 and budget.bad == 1

    def test_record_dataset_budget_covers_decode_failures(self, tmp_path):
        from deep_vision_tpu.data.datasets import RecordDataset
        from deep_vision_tpu.data.example_codec import encode_example
        from deep_vision_tpu.data.records import BadRecordBudget

        good = encode_example({"label": [1]})
        p = tmp_path / "train-0"
        _write_shard(p, [good, b"not-an-example-proto", good])
        budget = BadRecordBudget(max_count=5)
        ds = RecordDataset(str(tmp_path / "train-*"),
                           schema=lambda f: {"label": f["label"][0]},
                           bad_record_budget=budget)
        assert [s["label"] for s in ds] == [1, 1]
        assert budget.bad == 1


# -- journal flush degradation ------------------------------------------------

class TestJournalDegradation:
    def test_flush_fault_drops_line_not_run(self, tmp_path):
        from deep_vision_tpu.obs.journal import RunJournal, read_journal

        faults.install(FaultInjector.parse("journal.flush:io_error@2"))
        j = RunJournal(str(tmp_path / "j.jsonl"), kind="test")
        j.write("note", note="first")
        j.write("note", note="second")  # injected flush failure: dropped
        j.write("note", note="third")
        j.close("clean_exit")
        faults.install(None)
        notes = [e["note"] for e in read_journal(str(tmp_path / "j.jsonl"))
                 if e["event"] == "note"]
        assert notes == ["first", "third"]
        assert j.dropped_lines == 1


# -- checkpoint hardening -----------------------------------------------------

def _tree(v):
    return {"w": np.full((4,), v, np.float32), "b": np.full((2,), -v,
                                                            np.float32)}


class TestCheckpointResilience:
    def _manager(self, tmp_path, journal=None, **kw):
        from deep_vision_tpu.core.checkpoint import CheckpointManager

        return CheckpointManager(str(tmp_path / "ckpt"), journal=journal,
                                 **kw)

    def test_sidecar_roundtrip_checksummed(self, tmp_path):
        cm = self._manager(tmp_path)
        cm._write_sidecar(3, {"epoch": 3, "lr": 0.1})
        doc = json.load(open(cm._sidecar_path(3)))
        assert doc["__sidecar_format__"] == 1 and "crc32c" in doc
        host, err = cm._read_sidecar(3)
        assert err is None and host == {"epoch": 3, "lr": 0.1}
        assert not [p for p in os.listdir(cm.directory) if ".tmp." in p]

    def test_sidecar_rot_detected_by_checksum(self, tmp_path):
        cm = self._manager(tmp_path)
        cm._write_sidecar(3, {"epoch": 3})
        path = cm._sidecar_path(3)
        data = bytearray(open(path, "rb").read())
        i = data.index(b'"epoch"') + 2  # flip a payload byte, keep JSON-ish
        data[i] ^= 0x01
        open(path, "wb").write(bytes(data))
        host, err = cm._read_sidecar(3)
        assert host is None and err is not None

    def test_legacy_plain_json_sidecar_accepted(self, tmp_path):
        cm = self._manager(tmp_path)
        with open(cm._sidecar_path(7), "w") as f:
            json.dump({"epoch": 7}, f)  # pre-checksum format
        host, err = cm._read_sidecar(7)
        assert err is None and host == {"epoch": 7}

    def test_half_written_sidecar_is_an_error_not_a_crash(self, tmp_path):
        cm = self._manager(tmp_path)
        with open(cm._sidecar_path(2), "w") as f:
            f.write('{"__sidecar_format__": 1, "crc32c": 12, "payl')  # torn
        host, err = cm._read_sidecar(2)
        assert host is None and "unreadable" in err

    def test_sidecar_write_retries_transient_io_error(self, tmp_path):
        from deep_vision_tpu.core.checkpoint import CheckpointManager

        j = _Journal()
        cm = CheckpointManager(
            str(tmp_path / "ckpt"), journal=j,
            retry=RetryPolicy(name="ckpt.sidecar", max_attempts=3,
                              journal=j, jitter=0, sleep=lambda d: None))
        faults.install(FaultInjector.parse("ckpt.sidecar:io_error@1"))
        cm._write_sidecar(1, {"epoch": 1})
        faults.install(None)
        assert cm._read_sidecar(1) == ({"epoch": 1}, None)
        outcomes = [r["outcome"] for r in j.rows if r["event"] == "retry"]
        assert outcomes == ["retrying", "recovered"]

    def test_corrupt_fault_caught_by_checksum(self, tmp_path):
        cm = self._manager(tmp_path)
        faults.install(FaultInjector.parse("ckpt.sidecar:corrupt@1"))
        cm._write_sidecar(1, {"epoch": 1})
        faults.install(None)
        host, err = cm._read_sidecar(1)
        assert host is None and err is not None

    @pytest.mark.slow
    def test_restore_tree_quarantines_corrupt_latest_and_falls_back(
            self, tmp_path):
        j = _Journal()
        cm = self._manager(tmp_path, journal=j)
        for step in (1, 2, 3):
            assert cm.save_tree(step, _tree(step), host_state={"step": step})
        cm._mgr.wait_until_finished()
        # rot the newest sidecar on disk
        with open(cm._sidecar_path(3), "r+b") as f:
            f.seek(os.path.getsize(cm._sidecar_path(3)) // 2)
            f.write(b"\x00\x00")
        tree, host = cm.restore_tree(_tree(0))
        assert host == {"step": 2}
        np.testing.assert_array_equal(tree["w"], _tree(2)["w"])
        q = [r for r in j.rows if r["event"] == "ckpt_quarantine"]
        assert len(q) == 1 and q[0]["step"] == 3
        qdir = os.path.join(cm.directory, "quarantine")
        assert os.path.isdir(qdir) and len(os.listdir(qdir)) >= 1
        # the quarantined step must stay forgotten for the NEXT restore too
        tree2, host2 = cm.restore_tree(_tree(0))
        assert host2 == {"step": 2}

    @pytest.mark.slow
    def test_missing_sidecar_with_siblings_quarantined(self, tmp_path):
        j = _Journal()
        cm = self._manager(tmp_path, journal=j)
        for step in (1, 2):
            cm.save_tree(step, _tree(step), host_state={"step": step})
        cm._mgr.wait_until_finished()
        os.remove(cm._sidecar_path(2))  # the died-before-sidecar signature
        tree, host = cm.restore_tree(_tree(0))
        assert host == {"step": 1}
        assert any(r["event"] == "ckpt_quarantine" and r["step"] == 2
                   for r in j.rows)

    @pytest.mark.slow
    def test_explicit_step_corrupt_raises_not_falls_back(self, tmp_path):
        from deep_vision_tpu.core.checkpoint import CheckpointCorruptError

        cm = self._manager(tmp_path)
        for step in (1, 2):
            cm.save_tree(step, _tree(step), host_state={"step": step})
        cm._mgr.wait_until_finished()
        with open(cm._sidecar_path(2), "r+b") as f:
            f.seek(10)
            f.write(b"\xff")
        with pytest.raises(CheckpointCorruptError):
            cm.restore_tree(_tree(0), step=2)

    @pytest.mark.slow
    def test_nothing_valid_left_returns_none(self, tmp_path):
        cm = self._manager(tmp_path)
        assert cm.restore_tree(_tree(0)) == (None, None)

    @pytest.mark.slow
    def test_sidecar_gc_follows_max_to_keep(self, tmp_path):
        cm = self._manager(tmp_path, max_to_keep=2)
        for step in (1, 2, 3, 4):
            cm.save_tree(step, _tree(step), host_state={"step": step})
        cm._mgr.wait_until_finished()
        cm.save_tree(5, _tree(5), host_state={"step": 5})
        cm._mgr.wait_until_finished()
        kept = set(cm._sidecar_steps())
        assert kept == set(cm._mgr.all_steps())


# -- crash consistency e2e ----------------------------------------------------

_SAVER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from deep_vision_tpu.core.checkpoint import CheckpointManager

cm = CheckpointManager(sys.argv[1])
for step in (1, 2, 3):
    if step == 3:
        cm._mgr.wait_until_finished()  # 1 and 2 fully committed
    cm.save_tree(step, {"w": np.full((4,), float(step), np.float32)},
                 host_state={"step": step})
cm._mgr.wait_until_finished()
print("UNREACHABLE: the injected crash never fired")
"""


class TestCrashConsistencyE2E:
    @pytest.mark.slow
    def test_sigkill_mid_save_then_restore_recovers(self, tmp_path):
        """SIGKILL a saver inside the sidecar torn-write window; restore
        must land on the newest fully-committed step."""
        ckpt_dir = str(tmp_path / "ckpt")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env[faults.ENV_SPEC] = "ckpt.sidecar:crash_after_write@3"
        env["PYTHONPATH"] = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run([sys.executable, "-c", _SAVER, ckpt_dir],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == -signal.SIGKILL, proc.stdout + proc.stderr
        assert "UNREACHABLE" not in proc.stdout

        from deep_vision_tpu.core.checkpoint import CheckpointManager

        j = _Journal()
        cm = CheckpointManager(ckpt_dir, journal=j)
        tree, host = cm.restore_tree({"w": np.zeros((4,), np.float32)})
        assert host == {"step": 2}
        np.testing.assert_array_equal(tree["w"], np.full((4,), 2.0))

    @pytest.mark.slow
    def test_cli_run_sigkilled_mid_save_resumes(self, tmp_path):
        """The satellite e2e: a tiny CPU train run is SIGKILLed mid-
        checkpoint-save; `Trainer.resume()` recovers to the newest valid
        step and the rerun completes cleanly."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ckpt_dir = str(tmp_path / "ckpt")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
        env.pop(faults.ENV_SPEC, None)
        base = [sys.executable, os.path.join(root, "train.py"), "-m",
                "lenet5", "--fake-data", "--fake-batches", "2",
                "--epochs", "3", "--ckpt-dir", ckpt_dir]
        crashed = subprocess.run(
            base + ["--fault-spec", "ckpt.sidecar:crash_after_write@3",
                    "--journal", str(tmp_path / "j1.jsonl")],
            env=env, cwd=root, capture_output=True, text=True, timeout=560)
        assert crashed.returncode == -signal.SIGKILL, (
            crashed.stdout + crashed.stderr)

        resumed = subprocess.run(
            base + ["-c", ckpt_dir, "--journal", str(tmp_path / "j2.jsonl")],
            env=env, cwd=root, capture_output=True, text=True, timeout=560)
        assert resumed.returncode == 0, resumed.stdout + resumed.stderr
        # 2 fake batches/epoch; epoch 3's save was torn, so the newest
        # valid step is end-of-epoch-2 = 4
        assert "resumed from step 4" in resumed.stdout
        from tools.check_journal import check_journal

        assert check_journal(str(tmp_path / "j2.jsonl"), strict=True) == []


# -- dead data-worker resubmission -------------------------------------------

class _KillableDataset:
    """Round-robin-splittable dataset; worker `kill_wid`'s process SIGKILLs
    itself at local index `kill_at`. One-shot mode drops a sentinel file
    first so the replacement worker survives; `always` kills every
    incarnation (the restart-budget case)."""

    def __init__(self, n, sentinel, kill_wid=0, kill_at=3, always=False):
        self.items = list(range(n))
        self.sentinel = sentinel
        self.kill_wid = kill_wid
        self.kill_at = kill_at
        self.always = always
        self.wid = None

    def split(self, i, n):
        out = _KillableDataset.__new__(_KillableDataset)
        out.__dict__.update(self.__dict__)
        out.items = self.items[i::n]
        out.wid = i
        return out

    def __iter__(self):
        for j, v in enumerate(self.items):
            if (self.wid == self.kill_wid and j == self.kill_at
                    and (self.always or not os.path.exists(self.sentinel))):
                if not self.always:
                    open(self.sentinel, "w").close()
                os.kill(os.getpid(), signal.SIGKILL)
            yield {"x": np.array([v])}


class TestDeadWorkerResubmission:
    @pytest.mark.slow
    def test_dead_worker_restarted_no_loss_no_duplicates(self, tmp_path):
        from deep_vision_tpu.data import DataLoader

        ds = _KillableDataset(16, str(tmp_path / "sentinel"))
        dl = DataLoader(ds, batch_size=4, num_procs=2, worker_poll_s=0.5)
        got = sorted(int(v) for batch in dl for v in batch["x"].ravel())
        assert got == list(range(16))

    @pytest.mark.slow
    def test_restart_budget_spent_raises(self, tmp_path):
        from deep_vision_tpu.data import DataLoader

        ds = _KillableDataset(16, str(tmp_path / "sentinel"), always=True)
        dl = DataLoader(ds, batch_size=4, num_procs=2, worker_poll_s=0.5,
                        worker_restarts=1)
        with pytest.raises(RuntimeError, match="restart budget"):
            for _ in dl:
                pass


# -- check_journal schema coverage -------------------------------------------

class TestCheckJournalResilienceEvents:
    def _journal(self, tmp_path, rows):
        path = tmp_path / "j.jsonl"
        base = {"ts": 1.0, "run_id": "r1"}
        with open(path, "w") as f:
            f.write(json.dumps({"event": "run_manifest", "kind": "t",
                                "argv": [], **base}) + "\n")
            for r in rows:
                f.write(json.dumps({**base, **r}) + "\n")
            f.write(json.dumps({"event": "exit", "status": "clean_exit",
                                **base}) + "\n")
        return str(path)

    def test_strict_accepts_all_resilience_events(self, tmp_path):
        from tools.check_journal import check_journal

        path = self._journal(tmp_path, [
            {"event": "retry", "name": "ckpt.sidecar", "attempt": 1,
             "error": "IOError: blip", "outcome": "retrying",
             "delay_s": 0.05},
            {"event": "fault", "point": "data.read", "kind": "io_error"},
            {"event": "data_skip", "path": "train-0", "offset": 128,
             "reason": "corrupt record data"},
            {"event": "ckpt_quarantine", "step": 3,
             "reason": "sidecar checksum mismatch", "moved_to": []},
        ])
        assert check_journal(path, strict=True) == []

    def test_strict_rejects_missing_fields_and_bad_outcome(self, tmp_path):
        from tools.check_journal import check_journal

        path = self._journal(tmp_path, [
            {"event": "retry", "name": "x", "attempt": 1,
             "error": "e", "outcome": "exploded"},
            {"event": "data_skip", "path": "train-0", "reason": "r"},
            {"event": "ckpt_quarantine", "reason": "r"},
        ])
        errs = check_journal(path, strict=True)
        assert len(errs) == 3
        assert any("outcome" in e for e in errs)
        assert any("offset" in e for e in errs)
        assert any("step" in e for e in errs)
