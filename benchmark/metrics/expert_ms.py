"""Traced milliseconds a step of the ops that implement the held experts'
grouped products, mean over the cell's devices: the step table's ops
whose names begin with one of the cell's `expert_kernel_ops`
(`cells/<cell>.json`; the rule of `flash_roofline.kernel_seconds`: the
name itself, or the name and XLA's number). The products are Pallas calls
(`megablox`'s `gmm` forward and for the input's gradient, `tgmm` for the
experts' gradient); the dispatch's and combine's gathers and the shared
expert ride in fusions of their own and are not counted. A cell that
names none, a step that holds none of them and a run without a trace read
nothing."""
from benchmark.metrics.flash_roofline import kernel_seconds


def seconds(run):
    """-> the traced seconds a step, or None."""
    prefixes = run["cell"].get("expert_kernel_ops")
    if not run["trace"] or not prefixes:
        return None
    return kernel_seconds(run["trace"]["op_s_per_step"], prefixes) or None


def read(run):
    s = seconds(run)
    return None if s is None else s * 1e3
