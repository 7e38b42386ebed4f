"""Shard smoke: declarative sharding must be real, cheap, and loud.

    PYTHONPATH=. JAX_PLATFORMS=cpu python tools/shard_smoke.py \
        [--workdir artifacts/shard_smoke]

The CI teeth behind parallel/shardmap.py (`make shard-smoke`, a `make
verify` prerequisite), on a forced 8-device virtual-CPU mesh
(data=4, model=2):

  A. vit        a depth-2 ViT trains GENUINELY SHARDED multi-step
                (Trainer(sharding_rules=VIT_RULES, multistep=2,
                device_prefetch=2)): params/moments placed per the
                table (model-axis specs on device, shards smaller than
                the global array), `tp_sharded_leaves` at or above the
                family's declared floor AND above the infer_tp_sharding
                heuristic's count, a typed `sharding_resolved` event in
                the journal, and ZERO recompiles across the second
                epoch (superstep + epoch-tail single step both warmed).
  B. moe        the V-MoE variant (experts stacked on the leading E
                axis) with MOE_RULES: expert weights sharded over the
                MODEL axis, router replicated, same floor/heuristic/
                zero-recompile assertions.
  C. gutted     a deliberately gutted table (catch-all only, floor
                kept) must FAIL AT STARTUP with a
                ShardingCoverageError that NAMES the replicated leaf
                paths — the 108 -> 34 regression signature, now
                debuggable from the message; and a table missing its
                catch-all must refuse at construction.
  D. scaling    tools/scaling.py measures throughput at data={1,2,4,8}
                sub-meshes and the rows land as a typed `bench` event,
                each carrying
                the compiled step's predicted comm bytes next to the
                measured step-time delta vs the 1-device baseline.
  E. artifacts  journals pass `check_journal --strict`
                (sharding_resolved schema included) and obs_report
                renders the sharding section with rule hit counts and
                the scaling-efficiency rows.

Exit status 0 = every contract held; 1 = something broke.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List

# the 8-device virtual mesh MUST be configured before jax's first
# backend init (conftest.py does the same for the test tier)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class Failures:
    def __init__(self):
        self.errors: List[str] = []

    def check(self, ok: bool, what: str) -> bool:
        print(("  ok  " if ok else "  FAIL") + f"  {what}")
        if not ok:
            self.errors.append(what)
        return ok


def _batches(n: int, batch: int, classes: int, seed: int = 0):
    import numpy as np

    rng = np.random.RandomState(seed)
    return [
        {"image": rng.rand(batch, 16, 16, 3).astype(np.float32),
         "label": rng.randint(0, classes, (batch,)).astype(np.int32)}
        for _ in range(n)
    ]


def _train_phase(f: Failures, name: str, model, rules, journal_path: str):
    """One sharded multi-step training run; returns the journal events."""
    import jax
    import jax.numpy as jnp

    from deep_vision_tpu.losses.classification import classification_loss_fn
    from deep_vision_tpu.obs.journal import RunJournal
    from deep_vision_tpu.obs.stepclock import recompile_count
    from deep_vision_tpu.parallel.mesh import create_mesh
    from deep_vision_tpu.parallel.shardmap import HeuristicRules
    from deep_vision_tpu.train.optimizers import build_optimizer
    from deep_vision_tpu.train.trainer import Trainer
    from tools.smoke_util import read_jsonl

    mesh = create_mesh(data=4, model=2)
    journal = RunJournal(journal_path, kind="shard_smoke")
    journal.manifest(config={"tool": "shard_smoke", "phase": name})
    tx = build_optimizer("sgd", learning_rate=0.05, momentum=0.9)
    trainer = Trainer(
        model, tx, classification_loss_fn,
        jnp.ones((2, 16, 16, 3), jnp.float32), mesh=mesh,
        journal=journal, sharding_rules=rules,
        multistep=2, device_prefetch=2,
    )
    # 9 batches = 4 supersteps + 1 tail single step per epoch, so BOTH
    # executables compile in epoch 0 and epoch 1 must compile nothing
    data = _batches(9, batch=8, classes=8)
    trainer.fit(lambda: data, epochs=1)
    warm = recompile_count()
    trainer.fit(lambda: data, epochs=1)
    f.check(recompile_count() - warm == 0,
            f"{name}: zero recompiles across the post-warmup epoch "
            f"(delta {recompile_count() - warm})")

    # genuinely sharded: the table's model-axis layout is on the device,
    # with per-device shards smaller than the global array
    probe = trainer.state.params
    leaf = None
    for path in (("ViTBlock_0", "Attention_0", "qkv", "kernel"),):
        node = probe
        try:
            for k in path:
                node = node[k]
            leaf = node
        except (KeyError, TypeError):
            pass
    f.check(leaf is not None, f"{name}: probe leaf found")
    if leaf is not None:
        spec_axes = {a for e in leaf.sharding.spec
                     for a in ((e,) if isinstance(e, str) else (e or ()))}
        shard_size = leaf.addressable_shards[0].data.size
        f.check("model" in spec_axes,
                f"{name}: qkv kernel sharded over the model axis "
                f"({leaf.sharding.spec})")
        f.check(shard_size * 2 == leaf.size,
                f"{name}: per-device shard is half the global array "
                f"({shard_size} vs {leaf.size})")

    # coverage: at/above the family floor via the TABLE, and above the
    # size heuristic the table replaces
    _, table_report = rules.resolve(trainer.state, mesh)
    _, heur_report = HeuristicRules(min_size=1024).resolve(
        trainer.state, mesh)
    floor = rules.floor_for(mesh)
    f.check(table_report["sharded_leaves"] >= floor > 0,
            f"{name}: tp_sharded_leaves {table_report['sharded_leaves']} "
            f">= declared floor {floor}")
    f.check(table_report["sharded_leaves"] > heur_report["sharded_leaves"],
            f"{name}: table shards more than the heuristic "
            f"({table_report['sharded_leaves']} vs "
            f"{heur_report['sharded_leaves']})")
    f.check(bool(jnp.isfinite(
        trainer.state.params["Dense_0"]["kernel"]).all()),
            f"{name}: params finite after sharded training")
    # perf attribution (obs/perfwatch): the compiled step's collective
    # inventory must NAME the partitioner's comm — a sharded step whose
    # HLO shows zero all-reduces isn't reducing gradients at all. (The
    # byte-vs-grad-tree equality check lives in perf_gate's smoke on the
    # pure-DP mesh, where no tensor-parallel activation collectives mix
    # into the bill.) Runs AFTER the recompile assertions: the probe's
    # non-donating AOT lowering owns one compile of its own.
    prof = trainer.profile_step(data[0])
    f.check(prof is not None and prof["collective_bytes"] > 0
            and any(c["kind"] == "all-reduce" for c in prof["collectives"]),
            f"{name}: compiled-step collective inventory names its "
            f"all-reduces ({0 if prof is None else prof['collective_bytes']}"
            " bytes)")
    trainer.close()
    journal.close()
    events = read_jsonl(journal_path)
    resolved = [e for e in events if e.get("event") == "sharding_resolved"]
    f.check(len(resolved) == 1
            and resolved[0].get("model") == rules.name
            and resolved[0].get("sharded_leaves", -1) >= floor,
            f"{name}: one sharding_resolved event with the table's "
            "ledger")
    steps = [e for e in events if e.get("event") == "step"]
    f.check(any(e.get("multistep") == 2 for e in steps),
            f"{name}: superstep dispatches journaled with multistep=2")
    profiles = [e for e in events if e.get("event") == "perf_profile"]
    f.check(any(e.get("collective_count", 0) > 0 for e in profiles),
            f"{name}: typed perf_profile event journaled with the "
            "collective roll-up")
    return events


def _gutted_phase(f: Failures):
    import jax.numpy as jnp

    from deep_vision_tpu.losses.classification import classification_loss_fn
    from deep_vision_tpu.models.vit import ViT
    from deep_vision_tpu.parallel.mesh import (
        ShardingCoverageError,
        create_mesh,
    )
    from deep_vision_tpu.parallel.shardmap import (
        ShardingRuleError,
        ShardingRules,
    )
    from deep_vision_tpu.train.optimizers import build_optimizer
    from deep_vision_tpu.train.trainer import Trainer

    mesh = create_mesh(data=4, model=2)
    model = ViT(depth=2, dim=16, num_heads=2, patch=8, num_classes=8)
    tx = build_optimizer("sgd", learning_rate=0.05, momentum=0.9)
    gutted = ShardingRules(name="vit", rules=(("*", ()),), min_sharded=12)
    err = None
    try:
        Trainer(model, tx, classification_loss_fn,
                jnp.ones((2, 16, 16, 3), jnp.float32), mesh=mesh,
                sharding_rules=gutted)
    except ShardingCoverageError as e:
        err = str(e)
    f.check(err is not None,
            "gutted table fails AT STARTUP (Trainer construction)")
    f.check(err is not None and "replicated float leaves" in err
            and "ViTBlock" in err,
            "gutted-table failure NAMES the replicated leaf paths")
    try:
        # jaxlint: disable=DV205 -- deliberately malformed test subject
        ShardingRules(name="bad", rules=(
            ("*.Attention_*.qkv.kernel", (None, None, "model", None)),))
        f.check(False, "missing catch-all refused at construction")
    except ShardingRuleError:
        f.check(True, "missing catch-all refused at construction")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workdir", default="artifacts/shard_smoke")
    args = p.parse_args(argv)
    import shutil

    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir, exist_ok=True)
    f = Failures()

    import jax

    # hard CPU pin (conftest.py precedent), then hard-check the forced
    # device count actually took
    jax.config.update("jax_platforms", "cpu")
    n = len(jax.devices())
    f.check(n == 8, f"forced 8-device CPU mesh up (have {n})")

    from deep_vision_tpu.models.vit import ViT
    from deep_vision_tpu.parallel.shardmap import MOE_RULES, VIT_RULES

    print("-- phase A: ViT sharded multistep training --")
    vit_journal = os.path.join(args.workdir, "vit_journal.jsonl")
    vit_events = _train_phase(
        f, "vit", ViT(depth=2, dim=16, num_heads=2, patch=8, num_classes=8),
        VIT_RULES, vit_journal)

    print("-- phase B: MoE sharded multistep training --")
    moe_journal = os.path.join(args.workdir, "moe_journal.jsonl")
    moe_events = _train_phase(
        f, "moe", ViT(depth=2, dim=16, num_heads=2, patch=8, num_classes=8,
                      num_experts=4),
        MOE_RULES, moe_journal)
    moe_resolved = [e for e in moe_events
                    if e.get("event") == "sharding_resolved"]
    if moe_resolved:
        hits = moe_resolved[0].get("rules", {})
        f.check(hits.get("*.MoeMlp_*.w1", 0) > 0
                and hits.get("*.MoeMlp_*.router", 0) > 0,
                "moe: expert weights sharded, router replicated "
                "(rule hits journaled)")

    print("-- phase C: gutted table fails at startup --")
    _gutted_phase(f)

    print("-- phase D: scaling efficiency at data={1,2,4,8} --")
    from deep_vision_tpu.obs.journal import RunJournal
    from deep_vision_tpu.tools.scaling import (
        format_rows,
        measure_scaling,
        scaling_result,
    )

    bench_journal = os.path.join(args.workdir, "bench_journal.jsonl")
    journal = RunJournal(bench_journal, kind="shard_smoke")
    journal.manifest(config={"tool": "shard_smoke", "phase": "scaling"})
    rows = measure_scaling(batch_per_device=4, steps=4, warmup=1)
    print(format_rows(rows))
    journal.bench("multichip_scaling", scaling_result(rows))
    journal.close()
    f.check(len(rows) == 4 and [r["data"] for r in rows] == [1, 2, 4, 8],
            "scaling rows cover data={1,2,4,8}")
    f.check(all(r["examples_per_sec"] > 0 for r in rows)
            and rows[0]["efficiency"] == 1.0,
            "scaling rows well-formed (positive throughput, 1-device "
            "anchor at 1.0)")
    f.check(rows[0]["predicted_comm_bytes"] == 0
            and all(r["predicted_comm_bytes"] > 0 for r in rows[1:]),
            "scaling rows carry the predicted comm bill (0 at data=1, "
            "positive on every multi-device sub-mesh)")

    print("-- phase E: artifacts validate --")
    from tools.check_journal import check_journal

    for path in (vit_journal, moe_journal, bench_journal):
        errs = check_journal(path, strict=True)
        f.check(not errs, f"check_journal --strict {os.path.basename(path)}"
                + (f": {errs[:2]}" if errs else ""))
    from tools.obs_report import render, summarize_run
    from tools.smoke_util import read_jsonl

    text = render(summarize_run(read_jsonl(vit_journal)))
    f.check("sharding vit" in text and "rule" in text,
            "obs_report renders the sharding section with rule hits")
    text_b = render(summarize_run(read_jsonl(bench_journal)))
    f.check("scaling data=8" in text_b and "efficiency" in text_b,
            "obs_report renders the scaling-efficiency rows")

    if f.errors:
        print(f"\nshard-smoke: {len(f.errors)} FAILURE(S)")
        for e in f.errors:
            print("  - " + e)
        return 1
    print("\nshard-smoke: all contracts held")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
