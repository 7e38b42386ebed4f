"""The readings that the comparison's limits are set from, taken on the
chip at a cell's own size, many seeds in one process:

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 3 --fault-share 2 --out chiprun_out/readings.jsonl

- `program`: the program's first steps (through `Trainer.fit`, as a run
  drives them) against the plain reference: the lower readings.
- `control`: the reference computed in the precision below the
  configuration's, put in the program's place: it has to fail.
- `fault`: the reference over the first `1/--fault-share` of each batch's
  rows, the mean taken over those: half of the batch left out (2), or the
  exchange between chips left out (the number of chips).

Each reading is passed through `compare.judge` at the cell's own limits,
as a run's is, and its row says whether it came out `correct`. Beside the
three numbers compared a row holds `delta_distance` (`compare.py`): how far
the elements of the worst leaf's change lie from the reference's, which no
run is judged by. Not run by the benchmark's own runs.

Memory: the programs' readings are all taken first and wait on the host,
one float32 tree of the parameters (the change after three steps) a seed,
and the reference's gradient and change wait there too while the control's
and the fault's references have the device. So a cell whose parameters
fill gigabytes is read a few seeds a call.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.abspath(sys.path[0]) == os.path.join(ROOT, "benchmark"):
    sys.path[0] = ROOT


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3,
                        help="control and fault on the first N seeds")
    parser.add_argument("--fault-share", default="2",
                        help="comma-separated: 2 = half of the batch left "
                        "out, the number of chips = the exchange left out")
    parser.add_argument("--out", default=None)
    parser.add_argument("--manifest",
                        default=os.path.join(ROOT, "BENCHMARK.json"))
    args = parser.parse_args(argv)

    import jax

    from benchmark import compare, run, traffic as traffic_mod
    from benchmark.adapters import train
    from deep_vision_tpu.core import excache

    excache.place_compile_cache()
    manifest = run.load_manifest(args.manifest)
    cell, config, traffic = run.resolve(manifest, args.workload)
    devices = jax.devices()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = {seed: {"seed": seed, "cell": cell["name"],
                   "device": devices[0].device_kind} for seed in seeds}

    trainer, journal, image_shape = train.build_trainer(
        config, traffic["global_batch"])

    def pool_of(seed):
        return traffic_mod.make_pool(traffic, config, image_shape, seed)

    module = train.reference_module(config)
    init = jax.jit(lambda k: module.init(config, k))
    program = {seed: train.first_steps(
        trainer, journal, config, pool_of(seed),
        init(train.seed_key(seed))) for seed in seeds}
    trainer.close()
    del trainer
    gc.collect()
    normalised = train.normalised_update(config)

    def judged(readings, reference):
        values = compare.gaps(readings, reference, normalised, distance=True)
        values["correct"], _ = compare.judge(values, cell["limits"])
        return values

    for n, seed in enumerate(seeds):
        pool = pool_of(seed)
        # to the host: the control's and the fault's references come next
        reference = jax.device_get(
            train.reference_steps(config, pool, seed, devices))
        row = rows[seed]
        row["reference_losses"] = reference["losses"]
        row["program"] = judged(program.pop(seed), reference)
        if n < args.control_seeds:
            row["control"] = judged(train.as_program(train.reference_steps(
                config, pool, seed, devices, control=True)), reference)
            for share in args.fault_share.split(","):
                keep = traffic["global_batch"] // int(share)
                row[f"fault_rows_{keep}"] = judged(train.as_program(
                    train.reference_steps(config, pool, seed, devices,
                                          rows=keep)), reference)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
