"""Traced slice: the device's idle time per step while
the host was in `train/place`: pad, mask and the enqueue of the batch's
copy to the device.
See `benchmark/hostspans.py` for the rule."""
from benchmark import hostspans


def read(run):
    return hostspans.gap_ms(run, "train/place")
