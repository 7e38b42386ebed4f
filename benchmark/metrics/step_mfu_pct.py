"""The whole step's share of the chip's peak: FLOPs of one training step
of the plain reference at the global batch (forward, and backward to every
parameter; `benchmark/flops.py`), over the traced slice's wall per step,
per chip, over the peak of `peaks.json`."""
import importlib

from benchmark import flops


def read(run):
    t = run["trace"]
    if not t or not run["peaks"]:
        return None
    module = importlib.import_module(
        "benchmark.reference." + run["config"]["reference"])
    rows = run["global_batch"]
    per_step = flops.train_step_flops(module, run["config"],
                                      (rows, *run["image_shape"]), (rows,))
    step_s = t["window_s"] / t["periods"]
    return per_step / step_s / run["chips"] / run["peaks"][
        "bf16_flops_per_s"] * 100.0
