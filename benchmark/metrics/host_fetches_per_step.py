"""Blocking device->host fetches the step loop made per dispatched step:
`train_host_fetches_total` over `train_steps_total` of the process's
registry (warm-up and window alike). None where the program has no such
counter."""


def read(run):
    try:
        from deep_vision_tpu.obs.registry import get_registry
    except ImportError:
        return None
    # looked up, not `counter(...)`: that would create what is not there
    counters = {m.name: m for m in get_registry().metrics()}
    fetches = counters.get("train_host_fetches_total")
    steps = counters.get("train_steps_total")
    if fetches is None or steps is None or not steps.value:
        return None
    return fetches.value / steps.value
