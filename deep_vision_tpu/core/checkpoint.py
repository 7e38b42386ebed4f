"""Checkpoint/resume for the whole zoo.

Semantics preserved from the reference (SURVEY.md §2.6):
  (a) full training-state capture incl. optimizer + scheduler + metric history
      (torch dict at ResNet/pytorch/train.py:417-428);
  (b) resume-by-flag (`-c <ckpt>`, ResNet/pytorch/train.py:293-307);
  (c) best-val-only saving (YOLO/tensorflow/train.py:243-247);
  (d) keep-every vs max_to_keep policies (CycleGAN/tensorflow/train.py:142-143,
      DCGAN/tensorflow/main.py:40).

TPU-native mechanism: orbax async checkpointing of the TrainState pytree,
step-indexed directories, plus a small JSON sidecar for host-side state
(metric history, plateau-scheduler state) that must never enter jit.

Storage is treated as unreliable by design (Check-N-Run, NSDI '22): the
sidecar is written tmp+fsync+rename with an embedded crc32c so a crash
mid-write can never leave a half-written JSON that breaks `resume()`,
writes retry transient I/O errors through the shared
`resilience.RetryPolicy`, and `restore()` walks a fallback chain — a
step whose arrays fail to restore, whose sidecar is corrupt, or whose
sidecar is missing while sibling steps have one (the
killed-between-array-commit-and-sidecar signature) is QUARANTINED (moved
to `<dir>/quarantine/`, typed `ckpt_quarantine` journal event) and the
newest remaining valid step is restored instead of crashing the run.
`resilience.faults` injection points (`ckpt.save`, `ckpt.restore`,
`ckpt.sidecar` incl. the after-write torn window) make every one of
those paths testable on CPU.

The sidecar is also the input pipeline's checkpoint home: Trainer saves
the train DataLoader's `data/snapshot.py` DataLoaderState under the
`data_state` host-state key (epoch, batches consumed, shard cursor,
bad-record-budget spend), so `resume()` re-arms the batch stream at the
exact position the model state corresponds to — the PR 10 elastic
guarantees extended to the data plane (a resumed run must not silently
re-visit data the step counter says it already trained on).

Elastic (cross-mesh) restore: every save records leaf-level sharding
metadata in the sidecar (`resilience.elastic.sharding_meta` under the
reserved `__sharding__` key), so a run checkpointed on N hosts/devices
restores onto M — `restore(..., mesh=current_mesh)` re-places every
restored array against the *current* mesh's NamedShardings, re-resolving
each saved PartitionSpec per dimension and replicating whatever the new
topology cannot honor. Proven on CPU by saving under an 8-device mesh
and restoring under 4 and 1 (tests/test_elastic.py).
"""
from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Callable, List, Optional, Tuple

import google_crc32c
import jax
import orbax.checkpoint as ocp

from deep_vision_tpu.resilience import RetryPolicy, faults
from deep_vision_tpu.resilience import elastic

_SIDECAR_RE = re.compile(r"host_state_(\d+)\.json$")
_SIDECAR_FORMAT = 1


def state_arrays(state) -> dict:
    """The serializable slice of a TrainState: arrays only, no apply_fn/tx
    closures. THE single definition — CheckpointManager.save/restore and the
    GAN trainers all build their trees from it."""
    return {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "rng": state.rng,
    }


class CheckpointCorruptError(RuntimeError):
    """An explicitly requested step failed validation (corrupt sidecar or
    unrestorable arrays). The latest-step path never raises this — it
    quarantines and falls back instead."""


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        max_to_keep: Optional[int] = 3,
        save_interval_steps: int = 1,
        best_mode: Optional[str] = None,  # None | 'min' | 'max'
        best_metric: Optional[str] = None,
        journal=None,  # obs.RunJournal: ckpt_quarantine / retry events
        retry: Optional[RetryPolicy] = None,
    ):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._best_mode = best_mode
        self._best_metric = best_metric
        self._best_value = None
        self.journal = journal
        self._retry = retry or RetryPolicy(
            name="ckpt.sidecar", max_attempts=4, base_delay_s=0.05,
            max_delay_s=2.0, journal=journal,
        )
        # array restores retry transient I/O before the fallback chain may
        # judge a step corrupt: quarantining the newest good step over one
        # network-FS hiccup would be an irreversible answer to a
        # retryable question
        self._restore_retry = RetryPolicy(
            name="ckpt.restore", max_attempts=3, base_delay_s=0.2,
            max_delay_s=5.0, journal=journal,
        )
        #: did the last restore() place arrays itself (mesh= given)?
        #: Callers that blanket-replicate after a legacy restore consult
        #: this so they don't clobber a metadata-driven placement.
        self.last_restore_placed = False
        self._mgr = ocp.CheckpointManager(
            self.directory, options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                save_interval_steps=save_interval_steps,
                enable_async_checkpointing=True,
            ))

    # -- host-side sidecar -------------------------------------------------
    def _sidecar_path(self, step: int) -> str:
        return os.path.join(self.directory, f"host_state_{step}.json")

    def _sidecar_steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.directory):
            m = _SIDECAR_RE.match(name)
            if m:
                out.append(int(m.group(1)))
        return out

    def _write_sidecar(self, step: int, host_state: dict) -> None:
        """Atomic, checksummed, retried sidecar write.

        The payload crc travels inside the file: a torn write (crash between
        the first byte and the rename — impossible now, but the file may
        also rot on disk or be fed through a corrupting transport) is
        detected at read time instead of surfacing as a JSONDecodeError
        inside resume()."""
        self._retry.call(self._write_sidecar_once, step, host_state)

    def _write_sidecar_once(self, step: int, host_state: dict) -> None:
        faults.fire("ckpt.sidecar")
        payload = json.dumps(host_state, sort_keys=True)
        doc = json.dumps({
            "__sidecar_format__": _SIDECAR_FORMAT,
            "crc32c": int(google_crc32c.value(payload.encode())),
            "payload": host_state,
        }, sort_keys=True)
        # the corrupt fault flips bytes AFTER checksumming — simulating rot
        # the checksum must catch, never corruption it would vouch for
        data = faults.transform("ckpt.sidecar", doc.encode())
        path = self._sidecar_path(step)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            faults.fire("ckpt.sidecar", stage="after_write")
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    def _read_sidecar(self, step: int) -> Tuple[Optional[dict], Optional[str]]:
        """(host_state, error). (None, None) = no sidecar on disk;
        (None, reason) = a sidecar exists but failed validation."""
        path = self._sidecar_path(step)
        if not os.path.exists(path):
            return None, None
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            return None, f"sidecar unreadable: {type(e).__name__}: {e}"
        if not isinstance(doc, dict):
            return None, "sidecar is not a JSON object"
        if "__sidecar_format__" not in doc:
            return doc, None  # pre-checksum legacy sidecar: accept as-is
        payload = doc.get("payload")
        want = doc.get("crc32c")
        got = int(google_crc32c.value(
            json.dumps(payload, sort_keys=True).encode()))
        if want != got:
            return None, f"sidecar checksum mismatch (want {want}, got {got})"
        return payload, None

    def _gc_sidecars(self) -> None:
        """Drop sidecars whose array step was pruned by max_to_keep (they
        would otherwise accumulate forever AND make every pruned step look
        like an incomplete save to the fallback chain)."""
        keep = set(self._mgr.all_steps())
        if not keep:
            return
        for s in self._sidecar_steps():
            if s not in keep:
                try:
                    os.remove(self._sidecar_path(s))
                except OSError:
                    pass

    # -- quarantine + fallback restore -------------------------------------

    def _reload(self) -> None:
        self._mgr.reload()

    def _quarantine(self, step: int, reason: str) -> None:
        """Move a failed step (array dir + sidecar) under quarantine/ so the
        operator can post-mortem it, and make the manager forget it.

        Only process 0 moves files (same single-writer rule as the sidecar
        writes): the validation that CONDEMNED the step is deterministic
        over shared on-disk bytes, so every process walks to the same
        surviving step; letting each of them race os.replace on a shared
        checkpoint dir would not be."""
        qdir = os.path.join(self.directory, "quarantine")

        def unique(dst: str) -> str:
            out, n = dst, 1
            while os.path.exists(out):
                out = f"{dst}.{n}"
                n += 1
            return out

        moved = []
        if jax.process_index() == 0:
            os.makedirs(qdir, exist_ok=True)
            for src in (os.path.join(self.directory, str(step)),
                        self._sidecar_path(step)):
                if os.path.exists(src):
                    dst = unique(os.path.join(qdir, os.path.basename(src)))
                    try:
                        os.replace(src, dst)
                        moved.append(dst)
                    except OSError as e:
                        reason += f"; quarantine move failed: {e}"
        print(f"checkpoint: QUARANTINED step {step} ({reason}); "
              f"falling back to the newest valid step", file=sys.stderr)
        try:
            from deep_vision_tpu.obs.registry import get_registry

            get_registry().counter(
                "ckpt_quarantine_total", "checkpoint steps quarantined").inc()
        except Exception:
            pass
        if self.journal is not None:
            self.journal.write("ckpt_quarantine", step=int(step),
                               reason=reason, moved_to=moved)
        self._reload()

    def _restore_with_fallback(
        self, do_restore: Callable[[int, Optional[dict]], Any],
        step: Optional[int]
    ) -> Tuple[Optional[int], Any, Optional[dict]]:
        """(restored_step, value, host_state); (None, None, None) when no
        valid checkpoint remains. Explicit `step` = validate-or-raise (the
        operator pinned it; silently restoring a different one would be
        worse than failing); `step=None` = newest valid, quarantining
        losers along the way. `do_restore` receives the step's (already
        validated) host sidecar so a cross-mesh restorer can derive the
        target shardings BEFORE orbax places anything."""
        def attempt(s: int, host_state: Optional[dict]):
            # transient I/O (OSError family) is retried here, so only a
            # failure that SURVIVES the retry budget can condemn a step
            def once():
                faults.fire("ckpt.restore")
                return do_restore(s, host_state)

            return self._restore_retry.call(once)

        if step is not None:
            if step not in set(self._mgr.all_steps()):
                # fail BEFORE orbax sees the doomed restore: besides the
                # clearer error, a failed typed restore on a fresh manager
                # poisons its item-structure registry for later saves
                raise FileNotFoundError(
                    f"no checkpoint step {step} in {self.directory!r}")
            host_state, err = self._read_sidecar(step)
            if err is not None:
                raise CheckpointCorruptError(
                    f"checkpoint step {step} in {self.directory!r}: {err}")
            return step, attempt(step, host_state), host_state
        sidecar_steps = set(self._sidecar_steps())
        for s in sorted(self._mgr.all_steps(), reverse=True):
            host_state, err = self._read_sidecar(s)
            if (err is None and host_state is None
                    and sidecar_steps - {s}):
                # arrays committed, sidecar never landed, while sibling
                # steps do carry one: the process died between the array
                # commit and the sidecar rename — an incomplete save
                err = ("sidecar missing while other steps have one "
                       "(save died before the sidecar landed)")
            if err is None:
                try:
                    return s, attempt(s, host_state), host_state
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    err = f"array restore failed: {type(e).__name__}: {e}"
            self._quarantine(s, err)
            sidecar_steps.discard(s)
        return None, None, None

    def _typed_restorer(self, template, mesh) -> Callable:
        """The do_restore closure shared by restore/restore_tree: with a
        `mesh`, the template is handed to orbax as ABSTRACT arrays whose
        shardings come from the step's sidecar metadata re-resolved
        against that mesh — each array lands once, already placed (no
        restore-then-re-place double transfer)."""
        def do_restore(s: int, host_state: Optional[dict]):
            tmpl = template
            if mesh is not None:
                meta = (host_state or {}).get(elastic.SHARDING_META_KEY)
                tmpl = elastic.abstract_template(template, meta, mesh)
            return self._mgr.restore(s, args=ocp.args.StandardRestore(tmpl))

        return do_restore

    # -- save/restore API ---------------------------------------------------

    def save(self, step: int, state, host_state: Optional[dict] = None, metrics=None):
        """Save TrainState (async) + JSON host state. Returns True if saved."""
        if self._best_mode and metrics is not None and self._best_metric in metrics:
            v = float(metrics[self._best_metric])
            better = (
                self._best_value is None
                or (self._best_mode == "min" and v < self._best_value)
                or (self._best_mode == "max" and v > self._best_value)
            )
            if not better:
                return False
            self._best_value = v
        faults.fire("ckpt.save")
        arrays = state_arrays(state)
        saved = self._mgr.save(step, args=ocp.args.StandardSave(arrays))
        # multi-host: orbax coordinates the array save across processes;
        # the JSON sidecar is host-side state, written once by the primary.
        # REQUIRES a shared checkpoint filesystem (the standard orbax
        # multi-host setup): non-primary hosts read the same sidecar on
        # restore. With per-host local directories they would see
        # host_state=None and resume with divergent plateau/LR state.
        # Every save now carries a sidecar: the leaf-level sharding
        # metadata it embeds is what lets a later restore re-place the
        # arrays on a DIFFERENT mesh (elastic cross-mesh resume).
        if saved and jax.process_index() == 0:
            self._write_sidecar(step, self._with_sharding(host_state, arrays))
            self._gc_sidecars()
        return saved

    @staticmethod
    def _with_sharding(host_state: Optional[dict], tree) -> dict:
        doc = dict(host_state) if host_state else {}
        try:
            doc[elastic.SHARDING_META_KEY] = elastic.sharding_meta(tree)
        except Exception:
            pass  # metadata is an upgrade, never a reason to fail a save
        return doc

    def _place_restored(self, found: int, restored, host_state, mesh):
        """Strip the sharding metadata out of the host sidecar and, when a
        `mesh` was given, re-place every restored leaf against it — the
        cross-mesh half of an elastic resume. Returns (tree, host_state)."""
        self.last_restore_placed = False
        meta = None
        if isinstance(host_state, dict):
            meta = host_state.pop(elastic.SHARDING_META_KEY, None)
        if mesh is None:
            return restored, host_state
        # the typed restorer already landed every array on its target
        # sharding (abstract template); this pass is a near-free identity
        # (device_put to an equal sharding short-circuits) that also
        # covers managers whose do_restore did not pre-place
        restored, stats = elastic.replace_on_mesh(restored, meta, mesh)
        self.last_restore_placed = True
        if self.journal is not None and meta:
            self.journal.write(
                "note", note="ckpt_resharded", step=int(found),
                saved_mesh=meta.get("mesh"),
                saved_devices=meta.get("device_count"),
                mesh={str(k): int(v) for k, v in mesh.shape.items()},
                **stats,
            )
        return restored, host_state

    def restore(self, state, step: Optional[int] = None, mesh=None):
        """Restore into the structure of `state`; returns (state, host_state).

        With `step=None`, walks the fallback chain: corrupt/incomplete
        steps are quarantined and the newest valid one wins. When nothing
        valid remains, returns the input state untouched (fresh start).

        With `mesh`, the restored arrays are re-placed against THAT mesh
        using the sharding metadata the save recorded — a checkpoint from
        an 8-device run restores onto 4 (or 1) with every leaf landing on
        the new topology (specs the new mesh cannot honor replicate)."""
        template = state_arrays(state)
        found, restored, host_state = self._restore_with_fallback(
            self._typed_restorer(template, mesh), step)
        if found is None:
            self.last_restore_placed = False
            return state, None
        restored, host_state = self._place_restored(
            found, restored, host_state, mesh)
        return state.replace(**restored), host_state

    def save_tree(self, step: int, tree, host_state: Optional[dict] = None):
        """Save an arbitrary array pytree (multi-model trainers: the GAN
        trainers save {'g': ..., 'd': ...} of per-state array dicts — the
        tf.train.Checkpoint(generator.., discriminator..) analog at
        CycleGAN/tensorflow/train.py:133-148)."""
        faults.fire("ckpt.save")
        saved = self._mgr.save(step, args=ocp.args.StandardSave(tree))
        if saved and jax.process_index() == 0:
            self._write_sidecar(step, self._with_sharding(host_state, tree))
            self._gc_sidecars()
        return saved

    def restore_tree(self, template, step: Optional[int] = None, mesh=None):
        """Restore a pytree saved by `save_tree` into `template`'s structure;
        returns (tree, host_state) or (None, None) when nothing valid is
        saved (same quarantine-and-fall-back and cross-mesh `mesh=`
        semantics as `restore`)."""
        found, restored, host_state = self._restore_with_fallback(
            self._typed_restorer(template, mesh), step)
        if found is None:
            self.last_restore_placed = False
            return None, None
        return self._place_restored(found, restored, host_state, mesh)

    def restore_variables(self, step: Optional[int] = None) -> dict:
        """Template-free restore of just the model variables.

        Inference/export flows (tools/infer.py, tools/export.py) must not
        need to reconstruct the exact optimizer + schedule state tree the
        trainer saved — orbax can restore with the on-disk structure, and
        only `params`/`batch_stats` are kept. Returns a flax variables dict.
        """
        step = step if step is not None else self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory!r}")
        faults.fire("ckpt.restore")
        restored = self._mgr.restore(step)
        out = {"params": restored["params"]}
        if restored.get("batch_stats"):
            out["batch_stats"] = restored["batch_stats"]
        return out

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def wait(self):
        self._mgr.wait_until_finished()

    def close(self):
        self._mgr.wait_until_finished()
        self._mgr.close()
