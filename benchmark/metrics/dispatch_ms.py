"""Median ms of `train/dispatch` over the window's dispatches, from the
program's span ring (`benchmark/loopspans.py`)."""
from benchmark import loopspans


def read(run):
    return loopspans.median_ms(run, loopspans.DISPATCH)
