"""Median duration of the train step's executions on the device plane's
`XLA Modules` line, mean over the cell's devices."""


def read(run):
    return run["trace"]["step_device_ms"] if run["trace"] else None
