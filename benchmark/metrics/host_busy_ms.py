"""Median ms a step period that the loop's thread is busy: `train/data_wait`
+ `train/step` less the wait for the device's report
(`benchmark/loopspans.py`). Beside `step_ms_p95` it is the host's headroom."""
from benchmark import loopspans


def read(run):
    return loopspans.host_busy_ms(run)
