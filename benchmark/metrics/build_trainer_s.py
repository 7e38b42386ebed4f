"""Seconds of `train_cli.build_trainer`, from the program's own
`setup/build_trainer` span (`benchmark/loopspans.py`)."""
from benchmark import loopspans


def read(run):
    return loopspans.build_trainer_s()
