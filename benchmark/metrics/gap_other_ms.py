"""Traced slice: the device's idle time per step while
the host was in none of the loop's five spans (`other`).
See `benchmark/hostspans.py` for the rule."""
from benchmark import hostspans


def read(run):
    return hostspans.gap_ms(run, "other")
