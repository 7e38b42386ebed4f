"""Share of the dispatched steps whose report was not yet ready when the
loop came to read it, one dispatch late: the host was back before the
device had finished, so the device never waited for the host.
`train_steps_covered_total` over `train_steps_total` of the process's
registry (warm-up and window alike), in per cent. Near 100 the loop is
bound by the device; near 0 by the host. None where the program has no
such counter."""


def read(run):
    try:
        from deep_vision_tpu.obs.registry import get_registry
    except ImportError:
        return None
    # looked up, not `counter(...)`: that would create what is not there
    counters = {m.name: m for m in get_registry().metrics()}
    covered = counters.get("train_steps_covered_total")
    steps = counters.get("train_steps_total")
    if covered is None or steps is None or not steps.value:
        return None
    return 100.0 * covered.value / steps.value
