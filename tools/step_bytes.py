#!/usr/bin/env python3
"""Bytes a training step moves through HBM, by kind of op, with no chip.

    JAX_PLATFORMS=cpu python tools/step_bytes.py --workload resnet50_train_b128
    ... --ops chiprun_out/<dir>/ops.json   # + traced ms by kind, from a chip
    ... --scopes /gated_delta/ /mlp/       # + both by where in the model

The step program of a benchmark cell (`BENCHMARK.json`), built the way the
cell builds it (`benchmark/adapters/train.build_trainer`, the Trainer's own
`_train_step_impl`, state donated), is compiled for a described v5e
(`jax.experimental.topologies`, PERF.md §5's recipe): the program and its
op names are the chip's. Every instruction of the optimized entry
computation is then charged its operands' bytes plus its result's, and
summed by kind. A step bound by HBM bandwidth takes that sum over 819 GB/s
(PERF.md §5, which also reckons 14.5 GB as ResNet-50's floor at batch 128).
`pred_gb` is the part of all that in `pred` arrays over 1 MB: a mask
stored beside what it masks (PR 35); `mover_gb`, by dtype, the part in
`copy`, `reshape` and `transpose` instructions: a tensor that changes
layout and nothing else (PR 39). The state's arrays are never built
(`jax.eval_shape`), so a cell whose state fills a chip counts here too.
Nothing runs: no time comes from here. `--ops` takes a chip's traced
table {op name: seconds a step} (`--dump-ops`, on the chip, writes one)
and lays its milliseconds beside the bytes, by the same kinds; `--scopes`
sums both by where in the model an instruction comes from (its
`op_name`), the movers' part apart.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KINDS = ("convolution fusion", "reduction fusion", "elementwise fusion",
         "copy", "reshape/transpose", "custom call", "select-and-scatter",
         "optimizer", "all-reduce", "async copy/slice", "other")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
# no time and no traffic of their own
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id", "iota"}


def shapes_bytes(text: str, only: str | None = None) -> list:
    """Bytes of each array shape written in `text`, in order; of dtype
    `only` alone where given."""
    return [_DTYPE_BYTES[d] * math.prod(int(n) for n in dims.split(",") if n)
            for d, dims in _SHAPE.findall(text) if only in (None, d)]


def computations(hlo: str) -> dict:
    """{computation name: its instruction lines}; the entry's under
    "ENTRY"."""
    out, name = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            head = line.split()
            name = "ENTRY" if head[0] == "ENTRY" else head[0].lstrip("%")
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def _opcodes(lines) -> set:
    return {m.group(3) for m in map(_INSTR.match, lines) if m}


def _moves(hlo: str, shape_bytes=shapes_bytes):
    """(name, opcode, the line's rest, operands' bytes, the result's parts'
    bytes, bytes moved) of each instruction of the entry computation that moves any, an array's
    bytes as `shape_bytes` counts them."""
    sizes = {}
    for line in computations(hlo)["ENTRY"]:
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        parts = shape_bytes(result)
        sizes[name] = sum(parts)
        if opcode in _FREE or opcode.endswith("-done"):
            continue  # a -done is charged at its -start
        operands = [sizes.get(o, 0) for o in re.findall(
            r"%([\w.\-]+)", rest.split("), ", 1)[0])]
        read = sum(operands)
        # an async copy goes to or from the chip's near memory (`S(1)` in
        # a layout): the destination crosses HBM once. The start's result
        # repeats its operand beside the destination.
        moved = (sizes[name] - read if opcode in ("copy-start", "slice-start")
                 else sizes[name] + read)
        yield name, opcode, rest, operands, parts, moved


def pred_bytes(hlo: str, over: int = 1_000_000) -> int:
    """Bytes of `pred` arrays over `over` bytes that the entry computation
    writes and reads, async pairs included: a mask stored beside what it
    masks (PERF.md §6, PR 35)."""
    return sum(m[-1] for m in _moves(hlo, lambda text: [
        b for b in shapes_bytes(text, "pred") if b > over]))


def mover_bytes(hlo: str) -> dict:
    """{dtype: bytes} that the entry computation's `copy`, `reshape` and
    `transpose` instructions read and write, by their result's dtype: a
    tensor that changes layout and nothing else (PERF.md §6, PR 39)."""
    out = defaultdict(int)
    for line in computations(hlo)["ENTRY"]:
        m = _INSTR.match(line)
        if m and m.group(3) in ("copy", "reshape", "transpose"):
            # a mover reads what it writes: twice the result
            out[_SHAPE.search(m.group(2)).group(1)] += 2 * sum(
                shapes_bytes(m.group(2)))
    return dict(out)


def classify(hlo: str, param_bytes: set = frozenset()) -> dict:
    """{instruction of the entry computation: (kind, bytes)}. A fusion is a
    convolution fusion if a convolution is inside, else a reduction fusion
    if a reduce is, else elementwise; one whose every big operand and
    result has a parameter's size (`param_bytes`) is the optimizer's."""
    comps = computations(hlo)
    out = {}
    for name, opcode, rest, operands, parts, moved in _moves(hlo):
        if opcode in ("copy-start", "slice-start"):
            kind = "async copy/slice"
        elif opcode.endswith("-start"):
            kind = "all-reduce"
        elif opcode == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", rest)
            inside = _opcodes(comps.get(called.group(1), [])) if called else ()
            big = operands + parts
            if "convolution" in inside:
                kind = "convolution fusion"
            elif param_bytes and all(
                    b in param_bytes or b <= 4096 for b in big):
                kind = "optimizer"
            elif "reduce" in inside or "reduce-window" in inside:
                kind = "reduction fusion"
            else:
                kind = "elementwise fusion"
        elif opcode == "copy":
            kind = "copy"
        elif opcode in ("reshape", "transpose"):
            kind = "reshape/transpose"
        elif opcode == "custom-call":
            if "tpu_custom_call" not in rest:
                continue  # ConcatBitcast and the like: no pass over HBM
            kind = "custom call"
        elif opcode == "select-and-scatter":
            kind = "select-and-scatter"
        elif opcode in ("all-reduce", "all-gather", "reduce-scatter"):
            kind = "all-reduce"
        else:
            kind = "other"
        out[name] = (kind, moved)
    return out


def _op_ms(classified: dict, op_seconds: dict | None):
    """-> ({instruction: traced ms a step}, the ms of ops this program does
    not have). An async pair's time is its -done's wait; the -start is an
    issue."""
    unmatched, op_ms = 0.0, defaultdict(float)
    for name, seconds in (op_seconds or {}).items():
        base = re.sub(r"-done(\.\d+)?$", r"-start\1", name)
        if base in classified:
            op_ms[base] += seconds * 1e3
        else:
            unmatched += seconds * 1e3
    return op_ms, unmatched


def table(classified: dict, op_seconds: dict | None = None,
          largest: int = 12) -> dict:
    """-> {"kinds": {kind: {"ops", "gb", "ms"}}, "largest": the ops that
    move most, as [name, kind, MB, ms], "unmatched_ms": the traced time of
    ops this program does not have (another program was traced)}."""
    rows = {k: {"ops": 0, "gb": 0.0, "ms": 0.0} for k in KINDS}
    op_ms, unmatched = _op_ms(classified, op_seconds)
    for name, (kind, moved) in classified.items():
        rows[kind]["ops"] += 1
        rows[kind]["gb"] += moved / 1e9
        rows[kind]["ms"] += op_ms.get(name, 0.0)
    top = sorted(classified, key=lambda n: -classified[n][1])[:largest]
    return {"kinds": {k: v for k, v in rows.items() if v["ops"]},
            "largest": [[n, classified[n][0], classified[n][1] / 1e6,
                         op_ms.get(n, 0.0)] for n in top],
            "unmatched_ms": unmatched}


_MOVERS = ("copy", "reshape/transpose")


def by_scope(hlo: str, classified: dict, scopes: list,
             op_seconds: dict | None = None) -> dict:
    """-> {scope: {"ops", "gb", "ms", "mover_ops", "mover_gb", "mover_ms"}}:
    each instruction charged to the first of `scopes` (regular
    expressions) found in its `op_name`, to "" where none is; `mover_*` the
    part of it in `copy`, `reshape` and `transpose` instructions."""
    op_ms, _ = _op_ms(classified, op_seconds)
    rows = defaultdict(lambda: dict.fromkeys(
        ("ops", "gb", "ms", "mover_ops", "mover_gb", "mover_ms"), 0))
    for name, _, rest, *_ in _moves(hlo):
        if name not in classified:
            continue
        kind, moved = classified[name]
        op_name = re.search(r'op_name="([^"]*)"', rest)
        row = rows[next((s for s in scopes if op_name and re.search(
            s, op_name.group(1))), "")]
        for prefix in ("", "mover_") if kind in _MOVERS else ("",):
            row[prefix + "ops"] += 1
            row[prefix + "gb"] += moved / 1e9
            row[prefix + "ms"] += op_ms.get(name, 0.0)
    return dict(rows)


def _described_mesh(like):
    """The described v5e devices, laid out as the mesh `like`."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = np.array(topo.devices[:like.size]).reshape(like.devices.shape)
    return Mesh(devices, like.axis_names)


def _cell(workload: str, manifest_path: str):
    """-> (cell, config, traffic, the configuration's adapter module)."""
    from benchmark import run as bench

    manifest = bench.load_manifest(manifest_path)
    cell, config, traffic = bench.resolve(manifest, workload)
    adapter = bench.load_py(bench.find_file(manifest, "adapters",
                                            config["kind"] + ".py"))
    return cell, config, traffic, adapter


def compile_step(workload: str, manifest_path: str):
    """-> (the compiled step of the cell, the byte sizes of its parameter
    leaves). The state is shapes alone (`jax.eval_shape` of the Trainer's
    `create_train_state`: a 0.93 B-parameter state is never built here),
    the batch one of the cell's traffic, zeros."""
    cell, config, traffic, adapter = _cell(workload, manifest_path)
    if cell["chips"] > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    from unittest import mock

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from benchmark import traffic as traffic_mod
    from deep_vision_tpu.core import backend
    from deep_vision_tpu.parallel.mesh import replicated
    from deep_vision_tpu.train import trainer as trainer_mod

    make_state = trainer_mod.create_train_state

    def place_abstract(self, state):  # as `_place_state` without a table
        where = replicated(self.mesh)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=where), state)

    def abstract_state(*args):
        """Shapes alone, but for the scalars (the step, the optimizer's
        hyperparameters: the Trainer reads its base rate), which a jit
        that returns nothing else makes without the arrays."""
        shapes = jax.eval_shape(lambda: make_state(*args))
        scalars = iter(jax.jit(lambda: [
            x for x in jax.tree.leaves(make_state(*args)) if x.ndim == 0])())
        return jax.tree.map(
            lambda x: x if x.ndim else next(scalars), shapes)

    with mock.patch.object(trainer_mod, "create_train_state",
                           abstract_state), \
            mock.patch.object(trainer_mod.Trainer, "_place_state",
                              place_abstract):
        trainer, _, input_shape = adapter.build_trainer(
            config, traffic["global_batch"])
    batch = trainer._place_one({
        name: np.zeros(spec.shape, spec.dtype) for name, spec in
        traffic_mod.batch_spec(traffic, config, input_shape).items()}).data
    mesh = _described_mesh(trainer.mesh)

    def described(x):
        spec = getattr(x.sharding, "spec", None)
        where = (NamedSharding(mesh, spec) if spec is not None
                 else SingleDeviceSharding(mesh.devices.flat[0]))
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where)

    state, batch = jax.tree.map(described, (trainer.state, batch))
    # built here on the CPU; traced as on the chip, where a kernel compiles
    backend.current_platform = lambda: "tpu"
    with jax.set_mesh(mesh):
        compiled = jax.jit(trainer._train_step_impl, donate_argnums=0).lower(
            state, batch).compile()
    leaves = {x.size * x.dtype.itemsize
              for x in jax.tree.leaves(trainer.state.params)}
    return compiled, leaves


def dump_ops(workload, manifest_path, seed, seconds, out):
    """On the chip: one traced run of the cell, as `benchmark/run.py
    --trace 1` makes it, and the whole op table {name: seconds a step}
    (the result line keeps ten) written to `out`."""
    import jax

    from deep_vision_tpu.core import excache

    excache.place_compile_cache()  # the cell's own cache, as run.py sets it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell, config, traffic, adapter = _cell(workload, manifest_path)
    record = adapter.run(cell, config, traffic, seed, seconds, True,
                         time.time())
    red = record["trace"]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"workload": workload, "seed": seed,
                   "correct": bool(record["correct"]),
                   "step_device_ms": red["step_device_ms"],
                   "periods": red["periods"],
                   "op_s_per_step": red["op_s_per_step"]}, f)
    print(f"wrote {len(red['op_s_per_step'])} ops to {out}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--manifest",
                        default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--ops", help="a chip's {op: seconds a step} table")
    parser.add_argument("--dump-ops", metavar="OUT",
                        help="on the chip: trace the cell, write its table")
    parser.add_argument("--scopes", nargs="+", metavar="REGEX",
                        help="bytes and ms by the first of these found in "
                        "an instruction's op_name")
    parser.add_argument("--hlo", metavar="OUT",
                        help="write the compiled step's text there")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=20.0)
    args = parser.parse_args(argv)
    if args.dump_ops:
        return dump_ops(args.workload, args.manifest, args.seed,
                        args.seconds, args.dump_ops)
    t0 = time.time()
    compiled, leaves = compile_step(args.workload, args.manifest)
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    ops = None
    if args.ops:
        with open(args.ops) as f:
            ops = json.load(f)["op_s_per_step"]
    classified = classify(text, leaves)
    result = table(classified, ops)
    if args.scopes:
        result["scopes"] = by_scope(text, classified, args.scopes, ops)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    result.update(
        workload=args.workload, compile_s=round(time.time() - t0, 1),
        tpu_custom_calls=text.count('custom_call_target="tpu_custom_call"'),
        temp_gb=mem.temp_size_in_bytes / 1e9,
        pred_gb=pred_bytes(text) / 1e9,
        mover_gb={d: b / 1e9 for d, b in sorted(mover_bytes(text).items())},
        cost_analysis_gb=(cost[0] if isinstance(cost, list) else cost).get(
            "bytes accessed", 0.0) / 1e9,
        sync_gb=sum(v["gb"] for k, v in result["kinds"].items()
                    if k not in ("async copy/slice", "all-reduce")))
    print(json.dumps(result, indent=1))


if __name__ == "__main__":
    main()
