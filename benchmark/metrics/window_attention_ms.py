"""Traced milliseconds a step of the sliding-window attention kernels, mean
over the cell's devices: the step table's ops whose names begin with one of
the cell's `window_kernel_ops` (`cells/<cell>.json`; the rule of
`flash_roofline.kernel_seconds`: the name itself, or the name and XLA's
number). The windowed layers' forward, `dq` and `dkv` kernels are Pallas
calls named apart from the causal ones (`flash_window_fwd`,
`flash_window_bwd_dq`, `flash_window_bwd_dkv`), so a full layer's causal
kernels are not counted. A cell that names none, a step that holds none of
them (a program without windowed kernels) and a run without a trace read
nothing."""
from benchmark.metrics.flash_roofline import kernel_seconds


def seconds(run):
    """-> the traced seconds a step, or None."""
    prefixes = run["cell"].get("window_kernel_ops")
    if not run["trace"] or not prefixes:
        return None
    return kernel_seconds(run["trace"]["op_s_per_step"], prefixes) or None


def read(run):
    s = seconds(run)
    return None if s is None else s * 1e3
