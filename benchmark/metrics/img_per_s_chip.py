"""All images of all steps completed in the window, over the window's wall
seconds, per chip. Whole window; never a median of steps."""


def read(run):
    return run["steps"] * run["global_batch"] / run["window_s"] / run["chips"]
