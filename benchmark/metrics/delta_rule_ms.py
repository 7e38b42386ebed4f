"""Traced milliseconds a step of the ops that implement the gated delta
rule, mean over the cell's devices: the step table's ops whose names begin
with one of the cell's `delta_rule_ops` (`cells/<cell>.json`; the rule of
`flash_roofline.kernel_seconds`: the name itself, or the name and XLA's
number). In plain `jax.numpy` the rule is its chunk scans and the
triangular solves' loops, `while` ops in the step's table, which hold the
ops of their bodies; a kernel would be named by its own name. A cell that
names none, a step that holds none of them and a run without a trace read
nothing."""
from benchmark.metrics.flash_roofline import kernel_seconds


def seconds(run):
    """-> the traced seconds a step, or None."""
    prefixes = run["cell"].get("delta_rule_ops")
    if not run["trace"] or not prefixes:
        return None
    return kernel_seconds(run["trace"]["op_s_per_step"], prefixes) or None


def read(run):
    s = seconds(run)
    return None if s is None else s * 1e3
