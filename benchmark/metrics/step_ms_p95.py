"""95th percentile of every step interval of the window, host clock."""
import numpy as np


def read(run):
    return float(np.percentile(run["intervals_s"], 95)) * 1e3
