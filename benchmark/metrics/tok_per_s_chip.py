"""Tokens of all steps completed in the window, over the window's wall
seconds, per chip: the tokens a step as the program counts them
(`train_tokens_total` over `train_steps_total` of the process's registry,
rows x sequence length a dispatch; warm-up and window feed the same shape)
times the window's steps. None where the program has no such counter or
the feed is not of tokens."""


def read(run):
    try:
        from deep_vision_tpu.obs.registry import get_registry
    except ImportError:
        return None
    # looked up, not `counter(...)`: that would create what is not there
    counters = {m.name: m for m in get_registry().metrics()}
    tokens = counters.get("train_tokens_total")
    steps = counters.get("train_steps_total")
    if tokens is None or steps is None or not steps.value or not tokens.value:
        return None
    return (run["steps"] * tokens.value / steps.value / run["window_s"]
            / run["chips"])
