"""The whole step's share of the chip's peak: FLOPs of one training step
of the plain reference over the run's batch (forward, and backward to
every parameter; `flops.train_step_flops`: the reference's written count,
or its jaxpr's), over the traced slice's wall per step, per chip, over the
peak of `peaks.json`."""
import importlib

from benchmark import flops


def read(run):
    t = run["trace"]
    if not t or not run["peaks"]:
        return None
    module = importlib.import_module(
        "benchmark.reference." + run["config"]["reference"])
    per_step = flops.train_step_flops(module, run["config"],
                                      run["batch_spec"])
    step_s = t["window_s"] / t["periods"]
    return per_step / step_s / run["chips"] / run["peaks"][
        "bf16_flops_per_s"] * 100.0
