"""Traced slice: the device's idle time per step while
the step was dispatched and the device had not begun it (`launch`): its
input copy still in flight, or the launch itself.
See `benchmark/hostspans.py` for the rule."""
from benchmark import hostspans


def read(run):
    return hostspans.gap_ms(run, "launch")
