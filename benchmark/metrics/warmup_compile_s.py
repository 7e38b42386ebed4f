"""Wall of the warm-up `fit`: the first steps, compile or cache load."""


def read(run):
    return run["warmup_s"]
