"""1 - (union of op intervals on the device plane / traced slice), mean
over the cell's devices."""


def read(run):
    t = run["trace"]
    if not t:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
