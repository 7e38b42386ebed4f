"""bench.py: a failed window is retried, and anything short of a complete
healthy measurement exits non-zero.

These tests drive `_timed_windows` / `main` / `cli` with an injected flaky
step. They pin the retry-rebuild-replay path, that the contract line is
still printed for a degraded run, and the exit codes: 0 healthy, 1 degraded
line or failed phase, 2 no TPU (and then no line at all).
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import bench  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_bench_process_state(monkeypatch):
    """The emit-once latch is process-lifetime state in the real CLI; each
    test is its own 'process'."""
    monkeypatch.setattr(bench, "_EMITTED", None)
    # unit tests drive injected steps, not a real backend: the probe must
    # not spend wall time compiling a trivial op per test
    monkeypatch.setattr(bench, "_backend_alive", lambda *a, **k: (True, None))
    monkeypatch.setattr(bench, "_cold_start_fields", lambda: {})


def _instant_retries(monkeypatch):
    """Zero-delay retry schedule: the BackendSupervisor bench builds via
    _retry_policy() must not sleep real backoff in unit tests (budget
    still honors a monkeypatched MAX_RETRIES at call time)."""
    monkeypatch.setattr(bench, "_retry_policy", lambda: bench.RetryPolicy(
        name="bench.window", max_attempts=bench.MAX_RETRIES + 1,
        base_delay_s=0.0, jitter=0.0, retry_on=Exception))


def _pretend_tpu(monkeypatch):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(bench.jax, "devices", lambda *a: [dev])


class _FlakyStep:
    """Raises on the Nth call, healthy otherwise."""

    def __init__(self, fail_on_call=None):
        self.calls = 0
        self.fail_on_call = fail_on_call

    def __call__(self, state, batch):
        self.calls += 1
        if self.calls == self.fail_on_call:
            raise RuntimeError("UNAVAILABLE: connection reset by peer")
        return state, np.float32(0.5)

    def lower(self, *a, **kw):  # cost-analysis path: pretend unsupported
        raise NotImplementedError


def _fake_build_factory(fail_plan):
    """fail_plan: list of fail_on_call values, one per build_bench call."""
    builds = []

    def fake_build(batch_per_chip, multistep):
        step = _FlakyStep(
            fail_plan[len(builds)] if len(builds) < len(fail_plan) else None
        )
        builds.append(step)
        batch = {"image": np.zeros((batch_per_chip, 4))}
        fake_dev = types.SimpleNamespace(device_kind="TPU v5 lite")
        return step, None, batch, batch_per_chip, 1, [fake_dev]

    return fake_build, builds


def _build_once_then_die(batch_per_chip, multistep, _calls):
    """Build #1: warmup + window 0 ok, window 1 dies mid-way; every rebuild
    dies too -> retry exhaustion with ONE good window."""
    _calls["n"] += 1
    if _calls["n"] > 1:
        raise RuntimeError("UNAVAILABLE: backend still down")
    step = _FlakyStep(fail_on_call=bench.WARMUP_STEPS + bench.TIMED_STEPS + 5)
    batch = {"image": np.zeros((batch_per_chip, 4))}
    fake_dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    return step, None, batch, batch_per_chip, 1, [fake_dev]


def test_transient_failure_mid_window_rebuilds_and_completes(monkeypatch):
    # build #1's step dies mid-window-1 (warmup + window 0 ok); build #2 is
    # healthy — all WINDOWS must still complete
    fake_build, builds = _fake_build_factory(
        [bench.WARMUP_STEPS + bench.TIMED_STEPS + 5, None]
    )
    monkeypatch.setattr(bench, "build_bench", fake_build)
    _instant_retries(monkeypatch)
    (dts, step, state, batch, bs, n_chips, devs, errors) = (
        bench._timed_windows(8, 1)
    )
    assert len(dts) == bench.WINDOWS
    assert len(builds) == 2
    assert len(errors) == 1 and "connection reset" in errors[0]
    # pre-failure windows must NOT feed the median — every window replays
    # on the rebuilt (healthy) step
    assert builds[1].calls == bench.WARMUP_STEPS + (
        bench.WINDOWS * bench.TIMED_STEPS
    )


def test_retry_exhaustion_keeps_completed_windows_but_is_degraded(
        monkeypatch, capsys):
    """Budget exhaustion after some windows completed still reports the
    measured number — on a line the CLI exits 1 for."""
    calls = {"n": 0}
    monkeypatch.setattr(bench, "build_bench",
                        lambda b, m: _build_once_then_die(b, m, calls))
    _instant_retries(monkeypatch)
    monkeypatch.setattr(bench, "_device_step_ms", lambda *a, **kw: None)
    monkeypatch.setattr(bench, "MAX_RETRIES", 2)
    args = types.SimpleNamespace(batch=8, multistep=1)
    bench.main(args)
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["value"] > 0  # window 0's measurement survived
    assert payload["windows_completed"] == 1
    assert payload["errors"]
    assert bench.degraded(payload)


def test_main_emits_json_even_when_everything_fails(monkeypatch, capsys):
    def always_broken(batch_per_chip, multistep):
        raise RuntimeError("UNAVAILABLE: backend down")

    monkeypatch.setattr(bench, "build_bench", always_broken)
    _instant_retries(monkeypatch)
    monkeypatch.setattr(bench, "MAX_RETRIES", 2)
    args = types.SimpleNamespace(batch=8, multistep=1)
    bench.main(args)
    out = capsys.readouterr().out.strip().splitlines()
    payload = json.loads(out[-1])  # the JSON line is the last line
    assert payload["metric"] == "resnet50_train_images_per_sec_per_chip"
    assert payload["value"] == 0.0
    assert payload["errors"]
    assert bench.degraded(payload)


def test_backend_alive_detects_block_error_and_health():
    import time

    from deep_vision_tpu.resilience.elastic import backend_alive

    # the one probe bench and preflight share: a blocked backend is caught
    # by the join timeout, an erroring one by its exception
    ok, err = backend_alive(0.2, probe=lambda: time.sleep(60))
    assert not ok and "blocked" in err
    ok, err = backend_alive(5.0, probe=lambda: 1 / 0)
    assert not ok and "ZeroDivisionError" in err
    ok, err = backend_alive(5.0, probe=lambda: 1.0)
    assert ok and err is None


def test_cli_dead_backend_prints_degraded_line_and_exits_1(
        monkeypatch, capsys):
    monkeypatch.setattr(
        bench, "_backend_alive",
        lambda *a, **k: (False, "backend liveness probe still blocked"),
    )

    def must_not_run(*a, **k):
        raise AssertionError("build_bench must not run against a dead backend")

    monkeypatch.setattr(bench, "build_bench", must_not_run)
    assert bench.cli(["--batch", "128"]) == 1
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["value"] == 0.0
    assert "blocked" in payload["errors"][0]


def test_emit_is_once_per_process(capsys):
    assert bench._emit({"metric": "m", "value": 1})
    assert not bench._emit({"metric": "m", "value": 2})
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["value"] == 1


def test_degraded_verdicts():
    healthy = {"value": 10.0, "windows_completed": bench.WINDOWS}
    assert not bench.degraded(healthy)
    assert bench.degraded({**healthy, "errors": ["retried once"]})
    assert bench.degraded({**healthy, "windows_completed": 1})
    assert bench.degraded({**healthy, "value": 0.0})
    assert bench.degraded({"metric": "dispatch_sweep", "rows": []})
    assert not bench.degraded({"metric": "dispatch_sweep", "rows": [{}]})


def test_cli_without_a_tpu_exits_2_and_prints_no_result():
    """The real CLI on the CPU: no rate may appear under a device metric's
    name, so there is no line at all."""
    repo = os.path.dirname(os.path.abspath(bench.__file__))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--batch", "8"], cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_cli_degraded_run_exits_1(monkeypatch, capsys):
    _pretend_tpu(monkeypatch)
    calls = {"n": 0}
    monkeypatch.setattr(bench, "build_bench",
                        lambda b, m: _build_once_then_die(b, m, calls))
    _instant_retries(monkeypatch)
    monkeypatch.setattr(bench, "_device_step_ms", lambda *a, **kw: None)
    monkeypatch.setattr(bench, "MAX_RETRIES", 1)
    assert bench.cli(["--batch", "8"]) == 1
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["value"] > 0 and payload["errors"]


def test_cli_happy_path_exits_0_with_wall_rate_and_mfu(monkeypatch, capsys):
    _pretend_tpu(monkeypatch)
    fake_build, _ = _fake_build_factory([None])
    monkeypatch.setattr(bench, "build_bench", fake_build)
    monkeypatch.setattr(bench, "_device_step_ms", lambda *a, **kw: None)
    assert bench.cli(["--batch", "8"]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["value"] > 0
    assert payload["unit"] == "images/sec/chip"
    assert payload["windows_completed"] == bench.WINDOWS
    # wall semantics: vs_baseline is wall / target
    assert payload["vs_baseline"] == round(
        payload["value"] / bench.TARGET_PER_CHIP, 3
    )
    # analytic fallback path: flops reported even without cost analysis
    assert payload["flops_source"] == "analytic"
    assert payload["mfu_wall_pct"] > 0


def test_unknown_device_kind_has_no_peak():
    """One peaks table (core/backend.py), read by bench and the roofline
    tool; a device that is not in it is an error, not a default."""
    from deep_vision_tpu.core.backend import device_peaks
    from deep_vision_tpu.tools import roofline

    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="TPU v9 hypothetical"):
        bench._peak_flops("TPU v9 hypothetical")
    peaks = device_peaks(roofline.REFERENCE_DEVICE_KIND)
    assert roofline.PEAK_BF16_TFLOPS == peaks.bf16_flops / 1e12
    assert roofline.PEAK_HBM_GBS == peaks.hbm_bytes_per_s / 1e9
