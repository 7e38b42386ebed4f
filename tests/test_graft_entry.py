"""The driver's entry points, exercised the way the driver calls them.

`dryrun_multichip` is hermetic: it forces the virtual host-CPU platform
before any backend touch and never probes the default backend. These
tests pin
that contract: a fresh process with NO helpful env vars — and even with a
hostile stale device-count flag — must complete the dry run on the virtual
CPU mesh.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # jit-heavy: full DP x TP step compile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env_extra, n=4):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "__graft_entry__.py", str(n)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )


def test_dryrun_hermetic_with_no_env():
    proc = _run({})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
    # all four parallelism flavors actually ran on the DP x TP mesh
    assert "tp_sharded_leaves=" in proc.stdout
    assert "ring_attn_err=" in proc.stdout and "ep_err=" in proc.stdout


def test_dryrun_overrides_stale_device_count_flag():
    """A leftover smaller --xla_force_host_platform_device_count must be
    replaced, not trusted (it would bring up a too-small backend)."""
    proc = _run({"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "mesh={'data': 2, 'model': 2}" in proc.stdout
