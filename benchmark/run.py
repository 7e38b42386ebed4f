"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up, warm-up, a timed window, the comparison that decides `correct`,
and as the last line of standard output one JSON object. Everything that
belongs to one cell, configuration, traffic mix or metric is a file found
by its name in the manifest (`BENCHMARK.json`): this file names none.
Without the chips the cell asks for it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_CHIP, BAD_CALL = 3, 2
if os.path.abspath(sys.path[0]) == os.path.join(ROOT, "benchmark"):
    # started as a script: import from the checkout's root, so that this
    # directory's `trace.py` never stands in for the standard library's
    sys.path[0] = ROOT


def load_manifest(path):
    with open(path) as f:
        return json.load(f)


def find_file(manifest, *parts):
    """`<path>/<parts>` in the first of the manifest's `paths` that has it."""
    for base in manifest["paths"]:
        candidate = os.path.join(ROOT, base, *parts)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError(
        f"{os.path.join(*parts)} under none of {manifest['paths']}")


def load_py(path):
    name = "benchmark_file_" + path.replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(manifest, workload):
    """-> (cell, configuration's dict, traffic's dict) of a cell's name.
    The cell is its manifest entry and what its own file `cells/<name>.json`
    holds: the comparison's limits, set from readings at the cell's size."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r}; have {sorted(cells)}")
    with open(find_file(manifest, "cells", workload + ".json")) as f:
        cell = {**json.load(f), **cells[workload]}
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(find_file(manifest, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def read_metrics(manifest, group, record):
    """Every metric of `group` that applies to the record's cell, each read
    by the file of its name; a reader that finds nothing is left out."""
    out = {}
    for metric in manifest[group]:
        if not applies(metric, record["cell"]["name"]):
            continue
        reader = load_py(find_file(manifest, "metrics",
                                   metric["name"] + ".py"))
        value = reader.read(record)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def peaks_for(manifest, device_kind):
    with open(find_file(manifest, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       "add it with its source, do not default it")
    return table[device_kind]


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def run_cell(manifest, workload, seed, seconds, trace, require_chip=True):
    """-> the result object that `main` prints. Raises `SystemExit(NO_CHIP)`
    where the chips are not there."""
    cell, config, traffic = resolve(manifest, workload)
    try:
        from deep_vision_tpu.core import excache
    except ImportError as e:
        print(f"the program is not in this checkout: {e}", file=sys.stderr)
        raise SystemExit(BAD_CALL)
    import jax

    if require_chip:
        excache.place_compile_cache()  # <checkout>/.jax_cache unless set
        # every program of a run, small ones too, comes from the cache on
        # the next run: set-up is then the same work each time
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) != cell["chips"]):
        print(f"cell {workload} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        raise SystemExit(NO_CHIP)
    peaks = peaks_for(manifest, devices[0].device_kind) if require_chip \
        else None

    adapter = load_py(find_file(manifest, "adapters", config["kind"] + ".py"))
    record = adapter.run(cell, config, traffic, seed, seconds, bool(trace),
                         T_PROCESS_START)
    record.update(cell=cell, peaks=peaks)

    group = "per_layer" if trace else "end_to_end"
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"],
              # the same reading once the reference has run and been
              # compared: what the comparison cost beside the program
              "memory_peak_bytes_after": record["memory_peak_bytes_after"]}
    result = {"correct": bool(record["correct"]),
              "attempted": record["attempted"], "failed": record["failed"],
              "metrics": read_metrics(manifest, group, record),
              "device": device}
    if trace:
        red = record["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": top(red["op_s_per_step"]),
                               "idle_gaps": top(red["gap_s_per_step"])}
        # the device's clock against the host's: the slice's wall per whole
        # period beside the window's median step interval
        result["slice"] = {
            "periods": red["periods"],
            "wall_per_period_ms": red["window_s"] / red["periods"] * 1e3,
            "interval_median_ms": statistics.median(
                record["intervals_s"]) * 1e3}
    # whether the window stalled between two steps; read by no metric
    result["window"] = record["window"]
    result["reference_s"] = record["reference_s"]
    result["faults"] = record["faults"]
    result["compared"] = record["compared"]
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest",
                        default=os.path.join(ROOT, "BENCHMARK.json"))
    args = parser.parse_args(argv)
    result = run_cell(load_manifest(args.manifest), args.workload, args.seed,
                      args.seconds, args.trace)
    sys.stdout.flush()
    for fault in result["faults"]:
        print("fault:", fault, file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']})"
              + (f" at {c['leaf']}" if "leaf" in c else ""), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    # no teardown of the runtime: the line above is the last thing printed
    os._exit(0)


if __name__ == "__main__":
    main()
