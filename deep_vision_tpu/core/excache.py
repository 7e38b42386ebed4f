"""Persistent compiled-executable cache: pay the XLA compiler once.

Every cold path in the system used to pay the compiler in full — serve
``warmup()`` compiled every (model, bucket) pair from scratch, the
elastic rebuild-replay re-jitted after backend loss, a re-exec'd host
recompiled its whole mesh program, and a replica respawned onto a fresh
device had no surviving engine to borrow executables from. This module
closes all four: a content-addressed on-disk store of AOT-serialized
executables (``jax.experimental.serialize_executable`` over the
``lowered.compile()`` artifact), keyed by

    sha256( stablehlo lowering text
          , jax version, jaxlib version
          , platform, platform_version, device kind, device count
          , mesh shape )

so a cache produced under a different compiler, topology, or libtpu
build can never satisfy a lookup — a skewed entry is a MISS by key
construction, and an entry whose *manifest* disagrees with the current
environment (a cache dir copied between machines, a tampered entry, a
hand-rolled key collision) journals a typed ``excache_invalid`` and
falls through to the compiler. Never load a stale executable.

Entries are written with the PR 4/5 file-integrity idiom: payload and
manifest both land tmp + fsync + rename, the manifest embeds the
payload's crc32c, and a corrupt or undeserializable entry is QUARANTINED
to ``<root>/quarantine/`` (so the bad bytes stop matching lookups but
stay inspectable) while the caller falls through to a fresh compile.
Concurrent warmers over one cache dir are safe by the same idiom: stores
race through ``os.replace`` (identical content, last rename wins) and a
reader can never observe a torn entry.

Observability: typed ``excache_hit`` / ``excache_miss`` /
``excache_store`` / ``excache_invalid`` journal events (schemas in
obs/README.md, validated by ``check_journal --strict``) and
``excache_{hits,misses,stores,invalid}_total`` counters.

DONATION CONTRACT: only donation-free lowerings may be cached. The
serialize round trip drops jax's donated-buffer bookkeeping, so a
deserialized DONATING executable silently aliases input buffers the
caller still owns — measured as params corruption and then a segfault
on the second call of a cached train step (the verify drive caught
it). Engine.warmup and the Trainer's cache-path jits therefore lower
without ``donate_argnums`` when a cache is attached; the trade is one
donated buffer's worth of transient memory per cached executable.

The supplementary half is :func:`place_compile_cache`: JAX's own
persistent compilation cache catches the jit-traced compiles this
module's explicit AOT entries don't cover (the Trainer's eval step,
one-off host utilities). Every entry point calls it first; the AOT store
above never moves it. The ZERO-compile warmup contract cache-smoke proves
rides the explicit AOT entries only.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Optional, Tuple

import google_crc32c

from deep_vision_tpu.obs import locksmith

__all__ = [
    "ExecutableCache",
    "env_fingerprint",
    "place_compile_cache",
    "EXCACHE_INVALID_REASONS",
    "EXCACHE_ENV",
]

#: environment variable the CLIs read when --executable-cache is absent
EXCACHE_ENV = "DVT_EXCACHE"

#: why a present entry was refused (journaled as excache_invalid.reason)
EXCACHE_INVALID_REASONS = ("version_skew", "topology_skew", "corrupt",
                           "deserialize_failed")

#: manifest fields that indicate a stale COMPILER when they disagree
_VERSION_FIELDS = ("jax", "jaxlib", "platform_version")
#: manifest fields that indicate the wrong TOPOLOGY when they disagree
_TOPOLOGY_FIELDS = ("platform", "device_kind", "device_count", "mesh_shape")


def env_fingerprint(mesh_shape=None) -> dict:
    """The environment half of the cache key: everything that, if it
    changes, makes a serialized executable unloadable or — worse —
    silently wrong. Versions (the skew axis), platform +
    device kind + device count (the topology axis), and the mesh shape
    when the caller compiles against one."""
    import jax
    import jaxlib

    devs = jax.devices()
    pv = str(getattr(getattr(devs[0], "client", None),
                     "platform_version", "") or "")
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devs[0].platform,
        "platform_version": pv.splitlines()[0] if pv else "",
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "mesh_shape": list(int(d) for d in mesh_shape)
        if mesh_shape is not None else None,
    }


def place_compile_cache() -> str:
    """Decide where JAX's persistent compilation cache lives and return
    that directory. Called first thing by every entry point (train_cli,
    the serving replicas, tools/infer, chip_smoke.py), before
    anything compiles.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — nothing is
    set in code, so whoever launched the process owns the placement.
    Unset: ``<checkout>/.jax_cache``, resolved from this package's
    location. The path is part of the cache key's environment, so it is a
    fixed place — never a temp dir, a pid or a time — and two processes
    from one checkout share one cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class ExecutableCache:
    """Content-addressed store of AOT-serialized executables.

    Wire-up (what serve/engine.py warmup and the Trainer's cold paths
    do)::

        cache = ExecutableCache(root, journal=journal)
        lowered = jitted.lower(variables, spec)
        compiled, source = cache.get_or_compile(lowered, name="yolo/b4")
        # source == "cache": zero backend compiles; "compiled": stored
        # for the next cold start

    Every entry is two files under ``root``::

        <key>.exe    serialize_executable's payload bytes, written
                     tmp+fsync+rename (call PyTreeDefs are re-derived
                     from the caller's live lowering at load time — a
                     treedef's static aux may not pickle)
        <key>.json   manifest: payload crc32c + the env fingerprint the
                     entry was compiled under + name + created ts

    ``load`` re-validates the manifest against the CURRENT environment
    on every lookup, even though the fingerprint is hashed into the key:
    a copied cache dir or a tampered manifest must journal a typed
    ``excache_invalid`` and fall through to the compiler, never serve a
    stale executable.
    """

    def __init__(self, root: str, journal=None, registry=None,
                 mesh_shape=None):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.journal = journal
        self.mesh_shape = mesh_shape
        # lazy: jax.devices() initializes the backend, and callers build
        # the cache object before deciding platform knobs
        self._fp: Optional[dict] = None
        self._lock = locksmith.lock("core.excache")
        if registry is None:
            from deep_vision_tpu.obs.registry import get_registry

            registry = get_registry()
        self._c_hits = registry.counter(
            "excache_hits_total", "executable cache hits")
        self._c_misses = registry.counter(
            "excache_misses_total", "executable cache misses")
        self._c_stores = registry.counter(
            "excache_stores_total", "executable cache stores")
        self._c_invalid = registry.counter(
            "excache_invalid_total",
            "present-but-refused executable cache entries")

    # -- keys ---------------------------------------------------------------

    @property
    def fingerprint(self) -> dict:
        with self._lock:
            if self._fp is None:
                self._fp = env_fingerprint(self.mesh_shape)
            return self._fp

    def key_for(self, lowered) -> str:
        """Content-addressed key: the stablehlo lowering text (shapes,
        dtypes, and the whole computation) + the env fingerprint."""
        text = lowered if isinstance(lowered, str) else lowered.as_text()
        h = hashlib.sha256()
        h.update(text.encode())
        h.update(json.dumps(self.fingerprint, sort_keys=True).encode())
        return h.hexdigest()[:32]

    def _paths(self, key: str) -> Tuple[str, str]:
        return (os.path.join(self.root, key + ".exe"),
                os.path.join(self.root, key + ".json"))

    # -- journal/counter plumbing -------------------------------------------

    def _event(self, event: str, key: str, **fields) -> None:
        if self.journal is not None:
            self.journal.write(event, key=key, **fields)

    def _quarantine(self, key: str, reason: str) -> None:
        """Move both files of a condemned entry aside so the bad bytes
        stop matching lookups but stay inspectable (the PR 4 checkpoint
        idiom). Best-effort: a cross-warmer race losing the rename is
        the same outcome — the entry is gone from the lookup path."""
        qdir = os.path.join(self.root, "quarantine")
        try:
            os.makedirs(qdir, exist_ok=True)
        except OSError:
            return
        for p in self._paths(key):
            if os.path.exists(p):
                try:
                    os.replace(p, os.path.join(
                        qdir, f"{os.path.basename(p)}.{reason}"))
                except OSError:
                    pass

    # -- load ---------------------------------------------------------------

    def _check_manifest(self, manifest: dict) -> Optional[str]:
        """None when the entry's recorded environment matches the current
        one; otherwise the invalid-reason. Version skew is checked before
        topology so a dir copied across BOTH axes reports the one that
        can never heal mid-run."""
        recorded = manifest.get("fingerprint")
        if not isinstance(recorded, dict):
            return "corrupt"
        current = self.fingerprint
        if any(recorded.get(f) != current.get(f) for f in _VERSION_FIELDS):
            return "version_skew"
        if any(recorded.get(f) != current.get(f) for f in _TOPOLOGY_FIELDS):
            return "topology_skew"
        return None

    def load(self, key: str, lowered, name: str = ""):
        """The compiled executable for ``key``, or None (journaling why).

        ``lowered`` is the live jax Lowered object the key was computed
        from: only the serialized executable PAYLOAD lives on disk, and
        the call trees are re-derived from ``lowered.args_info`` /
        ``out_info`` at load time — a PyTreeDef can carry unpicklable
        static aux (a TrainState's apply_fn/tx), so it must never be
        part of the entry.

        miss     -> no entry on disk
        invalid  -> entry present but version/topology-skewed (refused,
                    left in place: it may be valid for the env that wrote
                    it), or corrupt / undeserializable (quarantined)
        """
        exe_path, man_path = self._paths(key)
        if not (os.path.exists(exe_path) and os.path.exists(man_path)):
            self._c_misses.inc()
            self._event("excache_miss", key, name=name)
            return None
        try:
            with open(man_path) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError):
            self._quarantine(key, "corrupt")
            self._c_invalid.inc()
            self._event("excache_invalid", key, name=name, reason="corrupt",
                        detail="unreadable manifest")
            return None
        skew = self._check_manifest(manifest)
        if skew == "corrupt":
            self._quarantine(key, "corrupt")
            self._c_invalid.inc()
            self._event("excache_invalid", key, name=name, reason="corrupt",
                        detail="manifest carries no fingerprint")
            return None
        if skew is not None:
            # NOT quarantined: the entry may be perfectly valid for the
            # environment that wrote it (a shared cache mount serving two
            # pools mid-upgrade) — it is merely unusable HERE
            self._c_invalid.inc()
            self._event("excache_invalid", key, name=name, reason=skew,
                        recorded={f: manifest["fingerprint"].get(f)
                                  for f in _VERSION_FIELDS + _TOPOLOGY_FIELDS
                                  if manifest["fingerprint"].get(f)
                                  != self.fingerprint.get(f)})
            return None
        try:
            with open(exe_path, "rb") as f:
                blob = f.read()
        except OSError as e:
            self._c_misses.inc()
            self._event("excache_miss", key, name=name,
                        detail=f"{type(e).__name__}: {e}"[:200])
            return None
        if int(google_crc32c.value(blob)) != manifest.get("crc32c"):
            self._quarantine(key, "corrupt")
            self._c_invalid.inc()
            self._event("excache_invalid", key, name=name, reason="corrupt",
                        detail="payload crc32c mismatch")
            return None
        try:
            import jax.tree_util as jtu
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            # the devices the lowering was made for: the default is
            # every device of the backend, and a one-device executable
            # then fails at call time wanting one shard per device
            compiled = deserialize_and_load(
                blob,
                jtu.tree_structure(lowered.args_info),
                jtu.tree_structure(lowered.out_info),
                execution_devices=lowered._lowering._device_list)
        except Exception as e:
            # crc-valid bytes the runtime refuses: a PJRT build drift the
            # fingerprint fields don't capture — condemn and recompile
            self._quarantine(key, "deserialize_failed")
            self._c_invalid.inc()
            self._event("excache_invalid", key, name=name,
                        reason="deserialize_failed",
                        detail=f"{type(e).__name__}: {e}"[:200])
            return None
        self._c_hits.inc()
        self._event("excache_hit", key, name=name, bytes=len(blob))
        return compiled

    # -- store --------------------------------------------------------------

    def store(self, key: str, compiled, name: str = "") -> bool:
        """Serialize + write one entry (payload first, manifest last, both
        tmp+fsync+rename). Never raises: a backend that cannot serialize
        executables degrades to compile-every-time with a journaled note,
        not a crashed warmup."""
        try:
            from jax.experimental.serialize_executable import serialize

            # payload bytes ONLY: the in/out PyTreeDefs are re-derived
            # from the caller's live lowering at load time (their static
            # aux — e.g. a TrainState's apply_fn — does not pickle)
            blob = bytes(serialize(compiled)[0])
        except Exception as e:
            if self.journal is not None:
                self.journal.write(
                    "note", note="excache_serialize_unsupported", key=key,
                    name=name, error=f"{type(e).__name__}: {e}"[:200])
            return False
        exe_path, man_path = self._paths(key)
        manifest = {
            "key": key,
            "name": name,
            "crc32c": int(google_crc32c.value(blob)),
            "bytes": len(blob),
            "fingerprint": self.fingerprint,
            "created": time.time(),
        }
        try:
            # payload BEFORE manifest: a reader keys presence on the pair,
            # so the torn window (payload without manifest) reads as a
            # plain miss, never a corrupt entry
            import threading as _threading

            for path, data in ((exe_path, blob),
                               (man_path,
                                json.dumps(manifest).encode())):
                # pid+thread-unique tmp: same-process concurrent warmers
                # (threads) racing the same key must not truncate each
                # other's in-flight tmp file — a torn payload published
                # under a full-crc manifest would quarantine a good entry
                tmp = path + f".tmp-{os.getpid()}-{_threading.get_ident()}"
                with open(tmp, "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
        except OSError as e:
            if self.journal is not None:
                self.journal.write(
                    "note", note="excache_store_failed", key=key, name=name,
                    error=f"{type(e).__name__}: {e}"[:200])
            return False
        self._c_stores.inc()
        self._event("excache_store", key, name=name, bytes=len(blob))
        return True

    # -- the one-call form ---------------------------------------------------

    def get_or_compile(self, lowered, name: str = ""):
        """(compiled, source): load ``lowered``'s executable from the
        cache, or compile and store it. source is "cache" (zero backend
        compiles) or "compiled" (the cold path, now paid forward)."""
        key = self.key_for(lowered)
        compiled = self.load(key, lowered, name=name)
        if compiled is not None:
            return compiled, "cache"
        compiled = lowered.compile()
        self.store(key, compiled, name=name)
        return compiled, "compiled"

    def entries(self) -> list:
        """Manifest dicts of every readable entry (diagnostics/preflight)."""
        out = []
        for fn in sorted(os.listdir(self.root)):
            if fn.endswith(".json"):
                try:
                    with open(os.path.join(self.root, fn)) as f:
                        out.append(json.load(f))
                except (OSError, json.JSONDecodeError):
                    continue
        return out
