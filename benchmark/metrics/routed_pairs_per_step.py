"""(token, choice) pairs the held experts computed a step, over all the
step's expert layers: `moe_routed_pairs_total` over `train_steps_total` of
the process's registry (warm-up and window alike; the program folds each
step's count into the counter, `obs.registry.COUNT_PREFIX`). Routing is by
the tokens, so this is what the grouped products' time should follow, not
the `T k` rows of their buffer. None where the program has no such
counter."""


def per_step(name):
    """`<name>` over `train_steps_total`, or None."""
    try:
        from deep_vision_tpu.obs.registry import get_registry
    except ImportError:
        return None
    # looked up, not `counter(...)`: that would create what is not there
    counters = {m.name: m for m in get_registry().metrics() if not m.labels}
    count, steps = counters.get(name), counters.get("train_steps_total")
    if count is None or steps is None or not steps.value:
        return None
    return count.value / steps.value


def read(run):
    return per_step("moe_routed_pairs_total")
