"""Traced slice: the device's idle time per step while
the host was in `train/fetch` and the step module had ended (or idled
inside): the step counter, the learning rate and each metric, a round trip each.
See `benchmark/hostspans.py` for the rule."""
from benchmark import hostspans


def read(run):
    return hostspans.gap_ms(run, "train/fetch")
