"""Environment preflight: fail in seconds, not minutes.

    python -m deep_vision_tpu.tools.preflight [--ckpt-dir DIR]
        [--mesh-data N] [--mesh-model M] [--expect-devices N]
        [--expect-hosts N --rendezvous-dir DIR [--host-id ID]]
        [--budget SECONDS] [--json]

Accelerator-layer failures burn minutes before dying: a libtpu
client/terminal version skew kills the first dispatch only after the whole
compile, and a backend that does not answer holds the run until an
external timeout fires. This preflight front-loads those verdicts:

  client_versions   jax vs jaxlib (major, minor) agreement — the
                    client-side half of a version skew
  backend           a trivial device op must complete within --budget,
                    run on a probe THREAD (a backend that blocks without
                    raising is seen only by a join timeout). Any error
                    it raises is classified
                    (resilience.elastic.classify_backend_error): a skew's
                    FAILED_PRECONDITION surfaces here as `version_skew`
                    in seconds, before any real compile.
                    Pass detail reports N x device_kind + the platform
                    version string — the terminal half of the handshake.
  mesh_shape        the requested (data, model) layout resolves over the
                    live device count (and matches --expect-devices when
                    given): a multi-chip launch asking for {'data': 4,
                    'model': 2} on a degraded 6-chip slice fails here,
                    not in the partitioner.
  ckpt_dir          checkpoint-directory writability, probed with the
                    same tmp+fsync+rename shape the crc32c sidecar uses:
                    a read-only or mis-mounted volume fails before the
                    first epoch trains into an unsaveable run.
  excache           with --excache: the persistent executable cache dir
                    (core/excache.py) is probed end-to-end — writable
                    with the tmp+fsync+rename shape, a trivial compiled
                    executable AOT-round-trips (store -> load -> run,
                    proving this backend can serialize executables), and
                    a deliberately version-skewed entry is REFUSED (the
                    stale-entry detector works). A bad cache mount fails
                    here in seconds, not at the first warmup miss.
  rendezvous        with --expect-hosts: join the elastic rendezvous
                    (resilience/rendezvous.py) and run the join-time
                    client-version/platform-version exchange through
                    the coordinator. A version-skewed joiner — a stale
                    host that would burn minutes of everyone's compile
                    before dying — is refused HERE, in seconds, with kind `version_skew`,
                    never admitted into a generation; a world that
                    cannot assemble --expect-hosts compatible members
                    within the budget fails as `timeout` naming who
                    showed up.

Runnable standalone (`make preflight`; exit 0 pass / 1 fail, one line
per check) and as the first act of `train_cli` (--skip-preflight opts
out). All checks are pure functions over injectable inputs so the
pass/fail classification is unit-testable without breaking hardware.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import asdict, dataclass
from typing import Callable, List, Optional, Tuple

from deep_vision_tpu.core import knobs
from deep_vision_tpu.resilience.elastic import (
    KIND_VERSION_SKEW,
    backend_alive,
)

#: default probe budget: a healthy backend answers a trivial op in
#: milliseconds (CPU) to ~15 seconds (a cold TPU client); a hung one
#: never does. Env-overridable (DVT_PREFLIGHT_BUDGET_S).
DEFAULT_BUDGET_S = knobs.get_float("DVT_PREFLIGHT_BUDGET_S")


@dataclass
class CheckResult:
    name: str
    ok: bool
    detail: str
    kind: str = ""  # failure classification (elastic.BACKEND_LOST_KINDS)
    elapsed_ms: float = 0.0


# -- the checks (pure over injectable inputs) ---------------------------------

def check_client_versions(jax_version: Optional[str] = None,
                          jaxlib_version: Optional[str] = None) -> CheckResult:
    """jax and jaxlib must agree on (major, minor): the client-side half
    of a version skew (the installed pair drifting apart is the usual way
    one side of the libtpu handshake goes stale)."""
    if jax_version is None or jaxlib_version is None:
        import jax
        import jaxlib

        jax_version = jax_version or jax.__version__
        jaxlib_version = jaxlib_version or jaxlib.__version__
    detail = f"jax {jax_version}, jaxlib {jaxlib_version}"

    def mm(v: str) -> Tuple[str, ...]:
        return tuple(v.split(".")[:2])

    if mm(jax_version) != mm(jaxlib_version):
        return CheckResult("client_versions", False,
                           detail + " — (major, minor) disagree",
                           kind=KIND_VERSION_SKEW)
    return CheckResult("client_versions", True, detail)


def check_backend(budget_s: float = DEFAULT_BUDGET_S,
                  probe: Optional[Callable] = None) -> CheckResult:
    """The liveness + handshake probe: one trivial device op, threaded.

    A hang is reported as `timeout`; a raised exception is
    classified from the exception OBJECT (the type gate applies) — the
    libtpu client/terminal skew raises FAILED_PRECONDITION on the first
    dispatch and lands here as `version_skew` seconds into the run
    instead of minutes."""
    ok, err, kind = backend_alive(budget_s, probe=probe)
    if not ok:
        return CheckResult("backend", False, err, kind=kind)
    try:
        import jax

        devs = jax.devices()
        # the terminal half of the handshake: on TPU this is the libtpu
        # build string a skew error quotes
        version = str(getattr(getattr(devs[0], "client", None),
                              "platform_version", "") or "")
        detail = (f"{len(devs)} x {devs[0].device_kind} "
                  f"({devs[0].platform}"
                  + (f", {version.splitlines()[0]}" if version else "")
                  + ")")
    except Exception as e:  # probe passed but introspection is exotic
        detail = f"alive (introspection unavailable: {type(e).__name__})"
    return CheckResult("backend", True, detail)


def check_mesh_shape(n_devices: int, data: int = -1, model: int = 1,
                     expect_devices: Optional[int] = None) -> CheckResult:
    """Does the requested (data, model) layout resolve over `n_devices`?"""
    from deep_vision_tpu.parallel.mesh import MeshSpec

    if expect_devices is not None and n_devices != expect_devices:
        return CheckResult(
            "mesh_shape", False,
            f"expected {expect_devices} devices, found {n_devices} "
            "(degraded slice, or the wrong machine)")
    try:
        d, m = MeshSpec(data=data, model=model).resolve(n_devices)
    except ValueError as e:
        return CheckResult("mesh_shape", False, str(e))
    return CheckResult("mesh_shape", True,
                       f"{{'data': {d}, 'model': {m}}} over "
                       f"{n_devices} device(s)")


def check_ckpt_dir(path: str) -> CheckResult:
    """Writability probe with the sidecar's own durability shape
    (tmp + fsync + rename), cleaned up after itself."""
    probe = os.path.join(path, f".preflight-{os.getpid()}")
    tmp = probe + ".tmp"
    try:
        os.makedirs(path, exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(b"preflight")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, probe)
        with open(probe, "rb") as f:
            if f.read() != b"preflight":
                return CheckResult("ckpt_dir", False,
                                   f"{path}: read-back mismatch "
                                   "(corrupting filesystem?)")
    except OSError as e:
        return CheckResult("ckpt_dir", False,
                           f"{path}: {type(e).__name__}: {e}")
    finally:
        for p in (tmp, probe):
            try:
                os.remove(p)
            except OSError:
                pass
    return CheckResult("ckpt_dir", True, f"{path} writable (tmp+fsync+rename)")


def check_excache(path: str) -> CheckResult:
    """Probe the executable cache end-to-end: writability, AOT
    serialize/deserialize round-trip, stale-entry refusal. Probe entries
    are cleaned up after themselves (like the ckpt_dir probe)."""
    import json as _json

    import numpy as np

    from deep_vision_tpu.core.excache import ExecutableCache
    from deep_vision_tpu.obs.registry import Registry

    try:
        import jax

        os.makedirs(path, exist_ok=True)
        # private registry: a probe must not bump the run's excache
        # counters before the first real warmup
        cache = ExecutableCache(path, registry=Registry())
        f = jax.jit(lambda x: x * 2.0 + 1.0)
        lowered = f.lower(jax.ShapeDtypeStruct((8,), "float32"))
        text = lowered.as_text()
        key = cache.key_for(text)
        compiled = lowered.compile()
        cleanup = [key]
        try:
            if not cache.store(key, compiled, name="preflight-probe"):
                return CheckResult(
                    "excache", False,
                    f"{path}: store failed — dir unwritable, or this "
                    "backend cannot serialize executables (the cache "
                    "would never hit)")
            loaded = cache.load(key, lowered, name="preflight-probe")
            if loaded is None:
                return CheckResult(
                    "excache", False,
                    f"{path}: stored probe entry did not load back "
                    "(corrupting filesystem, or deserialize unsupported)")
            x = np.ones((8,), np.float32)
            if not np.array_equal(np.asarray(loaded(x)),
                                  np.asarray(compiled(x))):
                return CheckResult(
                    "excache", False,
                    f"{path}: round-tripped executable computes a "
                    "different answer — refuse this cache")
            # stale-entry detection: a version-skewed manifest must be
            # refused, never loaded (the never-load-stale contract)
            skew_key = cache.key_for(text + "\n; preflight-skew-probe")
            cleanup.append(skew_key)
            cache.store(skew_key, compiled, name="preflight-skew-probe")
            man = os.path.join(path, skew_key + ".json")
            doc = _json.load(open(man))
            doc["fingerprint"]["jax"] = "0.0.0-preflight-skew"
            with open(man, "w") as fh:
                fh.write(_json.dumps(doc))
            if cache.load(skew_key, lowered,
                          name="preflight-skew-probe") is not None:
                return CheckResult(
                    "excache", False,
                    f"{path}: version-skewed entry LOADED — stale-entry "
                    "detection is broken, refuse this cache",
                    kind=KIND_VERSION_SKEW)
        finally:
            for k in cleanup:
                for p in (os.path.join(path, k + ".exe"),
                          os.path.join(path, k + ".json")):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
    except Exception as e:
        # any probe failure — an unwritable mount (OSError), a wedged
        # device erroring the probe compile/run (XlaRuntimeError), a
        # serialize quirk — must render as a FAIL line, never a
        # traceback breaking preflight's exit-0/1 contract (the same
        # hardening check_rendezvous needed)
        return CheckResult("excache", False,
                           f"{path}: {type(e).__name__}: {e}")
    n = len([f for f in os.listdir(path) if f.endswith(".json")])
    return CheckResult(
        "excache", True,
        f"{path} writable, AOT round-trip ok, stale entry refused "
        f"({n} cached entr{'y' if n == 1 else 'ies'})")


def check_sharding_tables() -> CheckResult:
    """Device-free semantic audit of the curated sharding tables
    (tools/shard_check.py): every family's table must still clear its
    coverage floor against an abstract eval_shape state tree. The
    108 -> 34 MULTICHIP coverage regression fails HERE, before any
    mesh is built or a single byte is compiled."""
    from deep_vision_tpu.tools.shard_check import FAMILIES, check_family

    fails: List[str] = []
    summary: List[str] = []
    for family in FAMILIES:
        try:
            report = check_family(family)
        except Exception as e:  # a broken table is a FAIL line, never a
            # traceback breaking preflight's exit-0/1 contract
            fails.append(f"{family}: {type(e).__name__}: {e}")
            continue
        summary.append(f"{family} {report['sharded']}/{report['min_sharded']}")
        if not report["ok"]:
            reasons = report["errors"] or [
                f"coverage {report['sharded']} < floor "
                f"{report['min_sharded']}"]
            fails.append(f"{family}: {reasons[0]}")
    if fails:
        return CheckResult("sharding_tables", False, "; ".join(fails))
    return CheckResult(
        "sharding_tables", True,
        "coverage floors hold abstractly (" + ", ".join(summary) + ")")


def host_versions() -> dict:
    """This host's side of the join-time version exchange: the jax/jaxlib
    client pair plus the backend's platform_version string (on TPU, the
    libtpu build a skew error quotes). Pure dict so the
    handshake comparison (`rendezvous.versions_compatible`) is
    unit-testable with fabricated values."""
    out = {}
    try:
        import jax
        import jaxlib

        out["client_version"] = f"jax {jax.__version__}, " \
                                f"jaxlib {jaxlib.__version__}"
        devs = jax.devices()
        pv = str(getattr(getattr(devs[0], "client", None),
                         "platform_version", "") or "")
        if pv:
            out["platform_version"] = pv.splitlines()[0]
    except Exception:
        pass  # version-less members compare compatible (fail open on
        # missing introspection, closed on an actual mismatch)
    return out


def check_rendezvous(expect_hosts: int, rendezvous_dir: str,
                     host_id: Optional[str] = None,
                     budget_s: float = DEFAULT_BUDGET_S,
                     versions: Optional[dict] = None) -> CheckResult:
    """Join the elastic rendezvous and run the version handshake.

    The joiner writes its member record (client + platform versions
    embedded), and the incumbent world's reference versions are compared
    on every poll: a skew is refused in seconds as `version_skew` — the
    preflight teeth for the one backend failure `BackendSupervisor`
    correctly refuses to retry. On success the probe LEAVES again (drops
    its member record): preflight must not squat a membership slot the
    real run is about to claim."""
    from deep_vision_tpu.resilience.rendezvous import (
        HostLostError,
        Rendezvous,
        RendezvousError,
        RendezvousRefused,
        RendezvousTimeout,
    )

    versions = host_versions() if versions is None else versions
    host_id = host_id or f"preflight-{os.uname().nodename}-{os.getpid()}"
    r = Rendezvous(rendezvous_dir, host_id,
                   client_version=versions.get("client_version"),
                   platform_version=versions.get("platform_version"))
    try:
        view = r.join(expect_hosts=expect_hosts, timeout_s=budget_s)
    except RendezvousRefused as e:
        return CheckResult("rendezvous", False, str(e), kind=e.kind)
    except RendezvousTimeout as e:
        return CheckResult("rendezvous", False, str(e), kind="timeout")
    except RendezvousError as e:
        # e.g. HostLostError: a probe peer died mid-assembly — still a
        # one-line failed check, never an unhandled traceback breaking
        # preflight's exit-0/1 contract
        kind = "host_lost" if isinstance(e, HostLostError) else ""
        return CheckResult("rendezvous", False, str(e), kind=kind)
    finally:
        r.leave()
    return CheckResult(
        "rendezvous", True,
        f"world of {view.world_size} assembled at generation "
        f"{view.generation} (rank {view.rank}, versions agree)")


# -- the runner ----------------------------------------------------------------

def run_preflight(data: int = -1, model: int = 1,
                  expect_devices: Optional[int] = None,
                  ckpt_dir: Optional[str] = None,
                  budget_s: float = DEFAULT_BUDGET_S,
                  probe: Optional[Callable] = None,
                  expect_hosts: Optional[int] = None,
                  rendezvous_dir: Optional[str] = None,
                  host_id: Optional[str] = None,
                  excache_dir: Optional[str] = None,
                  shard_tables: bool = True,
                  journal=None) -> Tuple[bool, List[CheckResult]]:
    """Run every applicable check; returns (all_ok, results).

    Ordering matters: the backend probe runs FIRST because when it fails
    nothing downstream (device count, mesh resolve) is meaningful — those
    checks are skipped rather than cascading the same root cause."""
    results: List[CheckResult] = []

    def run(fn, *args, **kw) -> CheckResult:
        t0 = time.perf_counter()
        r = fn(*args, **kw)
        r.elapsed_ms = round((time.perf_counter() - t0) * 1e3, 1)
        results.append(r)
        return r

    run(check_client_versions)
    backend = run(check_backend, budget_s=budget_s, probe=probe)
    if backend.ok:
        import jax

        run(check_mesh_shape, len(jax.devices()), data=data, model=model,
            expect_devices=expect_devices)
    if shard_tables:
        # device-free (pure eval_shape): runs even when the backend
        # probe failed — a gutted table is reportable regardless
        run(check_sharding_tables)
    if ckpt_dir:
        run(check_ckpt_dir, ckpt_dir)
    if excache_dir and backend.ok:
        # the probe compiles a trivial executable, so a dead backend
        # already failed above and would only cascade here
        run(check_excache, excache_dir)
    if expect_hosts is not None:
        if not rendezvous_dir:
            results.append(CheckResult(
                "rendezvous", False,
                "--expect-hosts needs --rendezvous-dir (the shared "
                "coordination directory every host mounts)"))
        elif backend.ok:
            # version exchange needs the backend's platform_version (the
            # terminal half of the handshake); a dead backend already
            # failed above and would only cascade here
            run(check_rendezvous, expect_hosts, rendezvous_dir,
                host_id=host_id, budget_s=budget_s)
    ok = all(r.ok for r in results)
    if journal is not None:
        try:
            journal.write("note", note="preflight",
                          ok=ok, checks=[asdict(r) for r in results])
        except Exception:
            pass
    return ok, results


def render(results: List[CheckResult], out=sys.stderr) -> None:
    for r in results:
        verdict = "PASS" if r.ok else "FAIL"
        kind = f" [{r.kind}]" if r.kind else ""
        print(f"preflight: {verdict} {r.name}{kind} — {r.detail} "
              f"({r.elapsed_ms:.0f} ms)", file=out)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ckpt-dir", default=None,
                   help="also probe this checkpoint dir for writability")
    p.add_argument("--mesh-data", type=int, default=-1,
                   help="requested data-axis size (-1: all remaining)")
    p.add_argument("--mesh-model", type=int, default=1,
                   help="requested model-axis size")
    p.add_argument("--expect-devices", type=int, default=None,
                   help="fail unless exactly this many devices are live")
    p.add_argument("--expect-hosts", type=int, default=None,
                   help="join the elastic rendezvous and fail unless this "
                        "many version-compatible hosts assemble (a skewed "
                        "joiner is refused as version_skew in seconds)")
    p.add_argument("--rendezvous-dir", default=None,
                   help="shared rendezvous directory (with --expect-hosts)")
    p.add_argument("--host-id", default=None,
                   help="this host's rendezvous member id (default: a "
                        "probe-scoped id that leaves after the check)")
    p.add_argument("--excache", default=None, metavar="DIR",
                   help="also probe this persistent executable-cache dir "
                        "(writability, AOT round-trip, stale-entry "
                        "refusal — core/excache.py)")
    p.add_argument("--no-shard-check", action="store_true",
                   help="skip the device-free sharding-table audit "
                        "(tools/shard_check.py)")
    p.add_argument("--budget", type=float, default=DEFAULT_BUDGET_S,
                   help="seconds the backend probe may take before the "
                        "backend is declared dead")
    p.add_argument("--json", action="store_true",
                   help="print one machine-readable JSON line to stdout")
    args = p.parse_args(argv)
    ok, results = run_preflight(
        data=args.mesh_data, model=args.mesh_model,
        expect_devices=args.expect_devices, ckpt_dir=args.ckpt_dir,
        budget_s=args.budget, expect_hosts=args.expect_hosts,
        rendezvous_dir=args.rendezvous_dir, host_id=args.host_id,
        excache_dir=args.excache,
        shard_tables=not args.no_shard_check,
    )
    render(results)
    if args.json:
        print(json.dumps({"ok": ok,
                          "checks": [asdict(r) for r in results]}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
