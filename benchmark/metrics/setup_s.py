"""Process start to the end of warm-up: imports, init, compile or cache
load, and the first steps."""


def read(run):
    return run["setup_s"]
