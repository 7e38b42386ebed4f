"""Traced slice: the device's idle time per step while
the host was in `train/dispatch`: the executable's lookup and the
enqueue of the step program.
See `benchmark/hostspans.py` for the rule."""
from benchmark import hostspans


def read(run):
    return hostspans.gap_ms(run, "train/dispatch")
