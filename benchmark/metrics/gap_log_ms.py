"""Traced slice: the device's idle time per step while
the host was in `train/log`: the clock's commit to registry and journal,
the anomaly triggers, the loggers, the health guard, the preemption poll.
See `benchmark/hostspans.py` for the rule."""
from benchmark import hostspans


def read(run):
    return hostspans.gap_ms(run, "train/log")
