"""Traced slice: the device's idle time per step while
the host was in `train/data_wait`: the feed's `next()`.
See `benchmark/hostspans.py` for the rule."""
from benchmark import hostspans


def read(run):
    return hostspans.gap_ms(run, "train/data_wait")
