"""Peak device memory of the fullest device after the window."""


def read(run):
    return run["memory_peak_bytes"] / 1e9
