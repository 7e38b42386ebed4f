"""Traced slice: wall per step minus device busy per step."""


def read(run):
    t = run["trace"]
    if not t:
        return None
    return (t["window_s"] - t["busy_s"]) / t["periods"] * 1e3
