"""Perf ledger + noise-aware regression gate.

    PYTHONPATH=. python tools/perf_gate.py RESULT.json [...] \
        --ledger artifacts/perf_ledger.jsonl [--journal run.jsonl] \
        [--k 4.0] [--window 8] [--min-history 3] [--bless]
    PYTHONPATH=. JAX_PLATFORMS=cpu python tools/perf_gate.py --smoke \
        [--workdir artifacts/perf_gate]

The repo's perf story used to be write-only: bench JSON artifacts
accumulated with no consumer, so a regression would sail through verify
unnoticed. This tool is the consumer. Every bench/smoke result appends one row to an append-only
`perf_ledger.jsonl` — stamped with the excache-style env fingerprint
(jax/jaxlib/platform/device kind+count/mesh shape), carrying its own
crc32c so torn or hand-edited rows quarantine instead of poisoning the
baseline — and is compared against the rolling per-(metric, env
fingerprint) history before it lands:

    baseline  = median of the last N same-key rows (failed rows excluded)
    threshold = max(k * 1.4826 * MAD, rel_floor * |median|)
    verdict   = fail when the new value is worse than baseline+threshold

Median +/- scaled-MAD is the noise-aware part: one outlier in the
history moves the threshold barely at all (a mean/std gate would chase
it), and the relative floor keeps a perfectly quiet history (MAD=0)
from failing runs over measurement jitter. Worse is direction-aware —
`ms` metrics regress upward, `per_sec`/`efficiency` metrics downward.
A breach exits nonzero and journals a typed `perf_regression` event;
an INTENTIONAL regression is blessed (`--bless`): the row lands with
verdict `blessed`, joins the baseline, and the gate re-anchors.

`--smoke` is the `make perf-gate` CI loop, proved end-to-end on CPU:
two seeded bench runs build the ledger, a third run slowed through the
fault-injection machinery (injected data.read io_errors absorbed at
retry-backoff cost, exactly like the pipeline's bad-record path) must
FAIL the gate via the real CLI with a strict-valid perf_regression
event — plus the collective-inventory cross-check: a data-parallel
sharded ViT table step's predicted all-reduce bytes must match its
gradient-tree size within 5% (obs/costmodel's end-to-end honesty
assertion).

Exit status: 0 = all gated results passed (or --smoke held), 1 = a
regression breached (or a smoke contract broke), 2 = usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import google_crc32c

GATE_VERDICTS = ("pass", "fail", "insufficient_history", "blessed")

#: gate defaults — the knobs `README.md` documents
DEFAULT_K = 4.0
DEFAULT_WINDOW = 8
DEFAULT_MIN_HISTORY = 3
DEFAULT_REL_FLOOR = 0.05
#: consistency constant: MAD of a normal distribution * 1.4826 ~= sigma
MAD_SCALE = 1.4826

#: ledger rotation: past `max_rows` rows, the oldest spill to
#: `<ledger>.old` and the newest `keep_rows` stay hot
DEFAULT_MAX_ROWS = 4096
DEFAULT_KEEP_ROWS = 1024


def _row_crc(row: dict) -> int:
    """crc32c over the canonical JSON of the row WITHOUT its crc field."""
    payload = {k: v for k, v in row.items() if k != "crc"}
    blob = json.dumps(payload, sort_keys=True).encode()
    return int(google_crc32c.value(blob))


def env_key(env: dict) -> str:
    """The stable ledger-key projection of an env fingerprint: history
    is only comparable within one software+hardware+mesh world."""
    return "|".join(f"{k}={env.get(k)}" for k in sorted(env))


def default_env(mesh_shape=None) -> dict:
    """The excache env fingerprint, or a degraded host-only stamp when
    jax isn't importable (the gate must still work on bare artifacts)."""
    try:
        from deep_vision_tpu.core.excache import env_fingerprint

        return env_fingerprint(mesh_shape=mesh_shape)
    except Exception:
        import platform

        return {"jax": None, "jaxlib": None, "platform": sys.platform,
                "platform_version": platform.platform(),
                "device_kind": None, "device_count": None,
                "mesh_shape": mesh_shape}


def metric_direction(metric: str, unit: Optional[str] = None) -> str:
    """'lower' when smaller is better (times), 'higher' otherwise
    (throughput/efficiency/accuracy). Heuristic over the repo's metric
    vocabulary; rows may carry an explicit `direction` to override."""
    text = f"{metric} {unit or ''}"
    for marker in ("_ms", " ms", "wall", "latency", "_s ", "seconds",
                   "compile", "bytes", "recompiles"):
        if marker in text:
            return "lower"
    return "higher"


class PerfLedger:
    """Append-only crc-manifested JSONL perf history.

    Normal operation only ever appends (one fsynced line per result).
    `read()` validates every row's embedded crc32c; corrupt rows are
    moved to `<path>.quarantine` and the main file is rewritten without
    them (tmp+fsync+rename, the excache idiom) — a torn write costs one
    row, never the history. Past `max_rows` rows, `append` spills the
    oldest into `<path>.old` so the hot file stays scan-cheap.
    """

    def __init__(self, path: str, max_rows: int = DEFAULT_MAX_ROWS,
                 keep_rows: int = DEFAULT_KEEP_ROWS):
        self.path = path
        self.max_rows = int(max_rows)
        self.keep_rows = min(int(keep_rows), self.max_rows)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)

    @property
    def quarantine_path(self) -> str:
        return self.path + ".quarantine"

    @property
    def rotated_path(self) -> str:
        return self.path + ".old"

    def append(self, row: dict) -> dict:
        """Stamp + crc + append one row; returns the stored form."""
        row = dict(row)
        row.setdefault("ts", time.time())
        row["crc"] = _row_crc(row)
        line = json.dumps(row, sort_keys=True) + "\n"
        with open(self.path, "a") as f:
            f.write(line)
            f.flush()
            os.fsync(f.fileno())
        self._maybe_rotate()
        return row

    def read(self) -> List[dict]:
        """Every crc-valid row, oldest first; quarantines the rest."""
        rows, bad = self._scan()
        if bad:
            self._quarantine(rows, bad)
        return rows

    def _scan(self) -> Tuple[List[dict], List[str]]:
        rows: List[dict] = []
        bad: List[str] = []
        if not os.path.exists(self.path):
            return rows, bad
        with open(self.path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                    if not isinstance(row, dict):
                        raise ValueError("not an object")
                    if int(row.get("crc", -1)) != _row_crc(row):
                        raise ValueError("crc mismatch")
                except (ValueError, TypeError, json.JSONDecodeError):
                    bad.append(line)
                    continue
                rows.append(row)
        return rows, bad

    def _rewrite(self, rows: List[dict]) -> None:
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            for row in rows:
                f.write(json.dumps(row, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def _quarantine(self, rows: List[dict], bad: List[str]) -> None:
        with open(self.quarantine_path, "a") as f:
            for line in bad:
                f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())
        self._rewrite(rows)

    def _maybe_rotate(self) -> None:
        rows, bad = self._scan()
        if len(rows) + len(bad) <= self.max_rows:
            return
        if bad:
            self._quarantine(rows, bad)
        spill, keep = rows[:-self.keep_rows], rows[-self.keep_rows:]
        with open(self.rotated_path, "a") as f:
            for row in spill:
                f.write(json.dumps(row, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        self._rewrite(keep)


def _median(values: List[float]) -> float:
    s = sorted(values)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def mad_gate(history: List[float], value: float, *,
             direction: str = "lower", k: float = DEFAULT_K,
             window: int = DEFAULT_WINDOW,
             min_history: int = DEFAULT_MIN_HISTORY,
             rel_floor: float = DEFAULT_REL_FLOOR) -> dict:
    """Verdict of one value against its rolling history (oldest first).

    Returns {"verdict", "baseline", "observed", "threshold", "window"};
    baseline/threshold are None under insufficient history.
    """
    recent = [float(v) for v in history[-int(window):]]
    if len(recent) < max(1, int(min_history)):
        return {"verdict": "insufficient_history", "baseline": None,
                "observed": float(value), "threshold": None,
                "window": len(recent)}
    med = _median(recent)
    mad = _median([abs(v - med) for v in recent])
    threshold = max(k * MAD_SCALE * mad, rel_floor * abs(med))
    worse = (float(value) - med) if direction == "lower" \
        else (med - float(value))
    return {
        "verdict": "fail" if worse > threshold else "pass",
        "baseline": round(med, 6),
        "observed": float(value),
        "threshold": round(threshold, 6),
        "window": len(recent),
    }


def gate_result(ledger: PerfLedger, metric: str, value: float, *,
                unit: Optional[str] = None, env: Optional[dict] = None,
                direction: Optional[str] = None, journal=None,
                k: float = DEFAULT_K, window: int = DEFAULT_WINDOW,
                min_history: int = DEFAULT_MIN_HISTORY,
                rel_floor: float = DEFAULT_REL_FLOOR,
                bless: bool = False, extra: Optional[dict] = None) -> dict:
    """Gate one result against the ledger, then append it.

    History is the same-(metric, env_key) rows minus failed ones — a
    regression that FAILED the gate must not become the baseline the
    next regression hides behind. `bless=True` skips the verdict and
    lands the row as `blessed`: history RESTARTS at the most recent
    blessed row (the pre-bless level must not drag the median back),
    and that one row is baseline enough on its own — blessing is an
    explicit declaration, not a sample. On `fail`, a typed
    `perf_regression` event is journaled when a journal is given.
    """
    env = env or default_env()
    key = env_key(env)
    direction = direction or metric_direction(metric, unit)
    rows_h = [r for r in ledger.read()
              if r.get("metric") == metric and r.get("env_key") == key
              and r.get("verdict") != "fail"]
    anchor = max((i for i, r in enumerate(rows_h)
                  if r.get("verdict") == "blessed"), default=None)
    if anchor is not None:
        rows_h = rows_h[anchor:]
        min_history = 1
    history = [float(r["value"]) for r in rows_h]
    if bless:
        verdict = {"verdict": "blessed", "baseline": None,
                   "observed": float(value), "threshold": None,
                   "window": len(history[-int(window):])}
    else:
        verdict = mad_gate(history, value, direction=direction, k=k,
                           window=window, min_history=min_history,
                           rel_floor=rel_floor)
    row = {
        "metric": metric,
        "value": float(value),
        "unit": unit,
        "direction": direction,
        "env": env,
        "env_key": key,
        "verdict": verdict["verdict"],
    }
    if extra:
        row.update({k_: v for k_, v in extra.items() if k_ not in row})
    ledger.append(row)
    out = dict(verdict, metric=metric, direction=direction)
    if verdict["verdict"] == "fail" and journal is not None:
        journal.write("perf_regression", metric=metric,
                      baseline=verdict["baseline"],
                      observed=verdict["observed"],
                      threshold=verdict["threshold"],
                      direction=direction, window=verdict["window"],
                      env_key=key)
    try:
        from deep_vision_tpu.obs import perfwatch

        perfwatch.note_gate(out)
    except Exception:
        pass
    return out


def _iter_results(paths: List[str]):
    """Yield (metric, value, unit, env, extra) from bench-contract JSON
    artifacts: a single object, a list, or JSONL — anything with a
    numeric `value` and a `metric`."""
    for path in paths:
        with open(path) as f:
            text = f.read()
        docs: List[dict] = []
        try:
            obj = json.loads(text)
            docs = obj if isinstance(obj, list) else [obj]
        except json.JSONDecodeError:
            for line in text.splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    docs.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
        for doc in docs:
            if not isinstance(doc, dict):
                continue
            metric = doc.get("metric")
            value = doc.get("value")
            if not metric or not isinstance(value, (int, float)):
                continue
            extra = {kk: doc[kk] for kk in ("run", "n_devices", "multistep")
                     if kk in doc}
            yield (str(metric), float(value), doc.get("unit"),
                   doc.get("env"), extra)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("results", nargs="*",
                   help="bench-contract JSON artifacts to gate+append")
    p.add_argument("--ledger", default="artifacts/perf_ledger.jsonl")
    p.add_argument("--journal", default=None,
                   help="journal path for typed perf_regression events")
    p.add_argument("--k", type=float, default=DEFAULT_K)
    p.add_argument("--window", type=int, default=DEFAULT_WINDOW)
    p.add_argument("--min-history", type=int, default=DEFAULT_MIN_HISTORY)
    p.add_argument("--rel-floor", type=float, default=DEFAULT_REL_FLOOR)
    p.add_argument("--bless", action="store_true",
                   help="land the results as an intentional new baseline "
                        "(verdict 'blessed', no gating)")
    p.add_argument("--smoke", action="store_true",
                   help="run the make perf-gate CI loop")
    p.add_argument("--workdir", default="artifacts/perf_gate")
    args = p.parse_args(argv)

    if args.smoke:
        return smoke(args.workdir)
    if not args.results:
        p.error("no result files given (or use --smoke)")

    journal = None
    if args.journal:
        from deep_vision_tpu.obs.journal import RunJournal

        journal = RunJournal(args.journal, kind="perf_gate")
        journal.manifest(config={"tool": "perf_gate"})
    ledger = PerfLedger(args.ledger)
    failed = []
    try:
        for metric, value, unit, env, extra in _iter_results(args.results):
            out = gate_result(
                ledger, metric, value, unit=unit, env=env, journal=journal,
                k=args.k, window=args.window, min_history=args.min_history,
                rel_floor=args.rel_floor, bless=args.bless, extra=extra)
            print(f"perf_gate: {metric} = {value:g} -> {out['verdict']}"
                  + (f" (baseline {out['baseline']:g} "
                     f"threshold {out['threshold']:g})"
                     if out["baseline"] is not None else ""))
            if out["verdict"] == "fail":
                failed.append(metric)
    finally:
        if journal is not None:
            journal.close()
    if failed:
        print(f"perf_gate: REGRESSION in {len(failed)} metric(s): "
              + ", ".join(failed))
        return 1
    return 0


# -- the make perf-gate smoke ------------------------------------------------


def _smoke_bench_step_ms(steps: int = 24) -> float:
    """One seeded micro-bench: wall ms/step of a jitted matmul step fed
    through a data-read boundary that absorbs injected io_errors at
    retry-backoff cost — the same shape as the pipeline's bad-record
    path, which is what makes the fault-slowed run honest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deep_vision_tpu.resilience import faults

    rng = np.random.RandomState(0)
    batches = [rng.rand(32, 256).astype(np.float32) for _ in range(8)]
    w = jnp.asarray(rng.rand(256, 256).astype(np.float32) * 0.01)

    @jax.jit
    def step(w, x):
        return jnp.tanh(x @ w).sum()

    def read(i):
        for _ in range(4):
            try:
                faults.fire("data.read")
                return batches[i % len(batches)]
            except faults.FaultInjected:
                time.sleep(0.02)  # the retry backoff the fault costs
        return batches[i % len(batches)]

    step(w, jnp.asarray(batches[0])).block_until_ready()
    t0 = time.perf_counter()
    loss = None
    for i in range(steps):
        loss = step(w, jnp.asarray(read(i)))
    loss.block_until_ready()
    return (time.perf_counter() - t0) / steps * 1e3


def _smoke_vit_inventory(check) -> None:
    """The collective-inventory honesty cross-check: a data-parallel
    sharded ViT table step's predicted all-reduce bytes vs its gradient
    tree, within 5%. Pure DP on purpose — a model-parallel mesh mixes
    activation collectives into the bill (shard_smoke covers that
    shape); here the all-reduces ARE the gradient reduction and nothing
    else, so the equality is exact up to the loss scalars."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deep_vision_tpu.core.train_state import create_train_state
    from deep_vision_tpu.losses.classification import classification_loss_fn
    from deep_vision_tpu.models.vit import ViT
    from deep_vision_tpu.obs import costmodel
    from deep_vision_tpu.parallel.mesh import create_mesh, data_sharding
    from deep_vision_tpu.parallel.shardmap import VIT_RULES
    from deep_vision_tpu.train.optimizers import build_optimizer

    mesh = create_mesh(data=len(jax.devices()), model=1)
    model = ViT(depth=2, dim=16, num_heads=2, patch=8, num_classes=8)
    tx = build_optimizer("sgd", learning_rate=0.05, momentum=0.9)
    state = create_train_state(model, tx,
                               jnp.ones((2, 16, 16, 3), jnp.float32))
    shardings, _ = VIT_RULES.resolve(state, mesh)
    state = jax.device_put(state, shardings)
    rng = np.random.RandomState(0)
    batch = {
        "image": rng.rand(16, 16, 16, 3).astype(np.float32),
        "label": (np.arange(16) % 8).astype(np.int32),
    }
    batch = {k: jax.device_put(v, data_sharding(mesh, np.asarray(v).ndim))
             for k, v in batch.items()}

    def train_step(state, batch):
        step_rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            outputs = state.apply_fn(
                {"params": params}, batch["image"], train=True,
                rngs={"dropout": step_rng})
            loss, _ = classification_loss_fn(outputs, batch)
            return loss

        grads = jax.grad(loss_fn)(state.params)
        return state.apply_gradients(grads)

    # jaxlint: disable=DV003 -- inventory probe: compiled to be PARSED, never dispatched, so donation has nothing to buy
    compiled = jax.jit(train_step).lower(state, batch).compile()
    hlo = costmodel.hlo_text(compiled)
    inv = costmodel.collective_inventory(hlo) if hlo else []
    ar = costmodel.predicted_collective_bytes(inv, "all-reduce")
    grad_bytes = costmodel.tree_bytes(state.params)
    rel = abs(ar - grad_bytes) / max(1, grad_bytes)
    kinds = sorted({c["kind"] for c in inv})
    check(any(c["kind"] == "all-reduce" for c in inv),
          f"sharded ViT step inventory names its all-reduces ({kinds})")
    check(rel <= 0.05,
          f"predicted all-reduce bytes {ar} match grad-tree bytes "
          f"{grad_bytes} within 5% (off by {rel * 100:.2f}%)")


def smoke(workdir: str) -> int:
    """make perf-gate: the regression-gate loop, end to end on CPU."""
    # the forced 8-device mesh must precede jax's first backend init
    # (shard_smoke precedent) — the ViT inventory phase wants real
    # data-parallel all-reduces, not a 1-device no-op
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import shutil
    import subprocess

    from deep_vision_tpu.resilience import faults
    from tools.smoke_util import read_jsonl

    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    failures: List[str] = []

    def check(ok: bool, what: str) -> bool:
        print(("  ok  " if ok else "  FAIL") + f"  {what}")
        if not ok:
            failures.append(what)
        return ok

    ledger_path = os.path.join(workdir, "perf_ledger.jsonl")
    journal_path = os.path.join(workdir, "journal.jsonl")
    ledger = PerfLedger(ledger_path)
    metric = "perf_gate_smoke_step_ms"
    # two runs of history + min_history=2 arms the gate for the third
    gate_kw = dict(unit="ms_per_step", min_history=2, window=8)

    print("-- phase 1: two seeded bench runs build the ledger --")
    for run in (1, 2):
        ms = _smoke_bench_step_ms()
        out = gate_result(ledger, metric, ms, extra={"run": run}, **gate_kw)
        check(out["verdict"] in ("insufficient_history", "pass"),
              f"clean run {run} ({ms:.2f} ms/step) -> {out['verdict']}")
    rows = ledger.read()
    check(len(rows) == 2 and all(r.get("crc") for r in rows),
          "ledger holds 2 crc-stamped rows")
    check(all(r.get("env", {}).get("jax") and r.get("env_key")
              for r in rows),
          "every row carries the env fingerprint + ledger key")

    print("-- phase 2: a fault-slowed third run FAILS the gate --")
    faults.install_spec("data.read:io_error@0.4", seed=7)
    try:
        slow_ms = _smoke_bench_step_ms()
    finally:
        faults.install_spec(None)
    result_path = os.path.join(workdir, "slow_result.json")
    with open(result_path, "w") as f:
        json.dump({"metric": metric, "value": slow_ms,
                   "unit": "ms_per_step"}, f)
    baseline_ms = _median([r["value"] for r in rows])
    check(slow_ms > baseline_ms * 2,
          f"injected io_errors slowed the bench ({slow_ms:.2f} vs "
          f"{baseline_ms:.2f} ms/step baseline)")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), result_path,
         "--ledger", ledger_path, "--journal", journal_path,
         "--min-history", "2"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, PYTHONPATH=ROOT))
    check(proc.returncode == 1,
          f"perf_gate CLI exits nonzero on the breach (rc={proc.returncode}"
          f", {proc.stdout.strip()!r})")
    events = read_jsonl(journal_path)
    regress = [e for e in events if e.get("event") == "perf_regression"]
    check(len(regress) == 1 and regress[0].get("metric") == metric
          and regress[0].get("observed", 0) > regress[0].get("baseline", 0),
          "typed perf_regression event journaled with baseline/observed/"
          "threshold")
    rows = ledger.read()
    check(rows and rows[-1]["verdict"] == "fail",
          "failed row lands in the ledger marked fail (excluded from "
          "future baselines)")

    print("-- phase 3: blessing re-anchors the baseline --")
    out = gate_result(ledger, metric, slow_ms, bless=True, **gate_kw)
    check(out["verdict"] == "blessed", "--bless lands without gating")
    out = gate_result(ledger, metric, slow_ms * 1.02, **gate_kw)
    check(out["verdict"] == "pass",
          f"post-bless run at the new level passes ({out['verdict']})")

    print("-- phase 4: corrupt ledger rows quarantine --")
    with open(ledger_path, "a") as f:
        f.write('{"metric": "tampered", "value": 1, "crc": 123}\n')
        f.write("not json at all\n")
    n_before = len(ledger.read())  # quarantines the two bad lines
    check(os.path.exists(ledger.quarantine_path)
          and len(read_jsonl(ledger.quarantine_path)) >= 1,
          "corrupt rows moved to the quarantine file")
    check(len(ledger.read()) == n_before,
          "ledger re-reads clean after quarantine")

    print("-- phase 5: journal validates --strict --")
    from tools.check_journal import check_journal

    errs = check_journal(journal_path, strict=True)
    check(not errs, "check_journal --strict accepts the perf_regression "
          + (f"event: {errs[:2]}" if errs else "event"))

    print("-- phase 6: sharded ViT collective inventory vs grad tree --")
    _smoke_vit_inventory(check)

    if failures:
        print(f"\nperf-gate: {len(failures)} contract(s) FAILED:")
        for what in failures:
            print("  - " + what)
        return 1
    print("\nperf-gate: all contracts held")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
