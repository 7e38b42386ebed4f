"""Adapter for training cells: one run of `train_cli.build_trainer` +
`Trainer.fit` — set-up, the first steps (which compile, and which the
comparison reads), the timed window, and the comparison with the plain
reference.

From the program it takes `build_trainer`, the `Trainer` it returns and
its journal rows; nothing else. Weights, inputs, clocks, the trace and
the comparison are the benchmark's own.
"""
from __future__ import annotations

import gc
import importlib
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark import compare, traffic as traffic_mod

COMPARED_STEPS = 3  # the first steps: warm-up, and what `correct` reads
TRACE_FROM, TRACE_STEPS = 5, 20  # the traced slice, in window steps


class MemoryJournal:
    """What `Trainer` needs of a run journal, kept in memory. Taps are not
    called: `train.py` without `--journal` has none either."""

    def __init__(self):
        self.steps, self.events = [], []

    def step(self, step, **fields):
        self.steps.append((step, fields))

    def write(self, event, **fields):
        self.events.append((event, fields))

    def add_tap(self, fn):
        pass

    def add_closer(self, fn):
        pass


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              seed // 2 ** 31)


def reference_module(config):
    return importlib.import_module(
        "benchmark.reference." + config["reference"])


def experiment_config(config, global_batch):
    """The program's `ExperimentConfig` of a configuration's file: the task
    (classification where the file names none), and the input shape, number
    of classes and loss arguments where the file has them."""
    import jax.numpy as jnp

    from deep_vision_tpu.configs import ExperimentConfig

    stated = {k: config[k] for k in ("num_classes", "loss_kwargs")
              if k in config}
    if "input_shape" in config:
        stated["input_shape"] = tuple(config["input_shape"])
    return ExperimentConfig(
        name=config["model"], task=config.get("task", "classification"),
        model=config["model"],
        model_kwargs={**config.get("model_kwargs", {}),
                      "dtype": jnp.dtype(config["compute_dtype"])},
        batch_size=global_batch, optimizer=dict(config["optimizer"]),
        schedule=config.get("schedule"), plateau=config.get("plateau"),
        **stated)


def build_trainer(config, global_batch):
    """The trainer as `train.py -m <model> --fake-data` builds it, at the
    configuration's stated precision (the optimizer's state in its
    `optimizer_state_dtype`, float32 where it states none), with no
    checkpoints and no eval."""
    from deep_vision_tpu import train_cli

    cfg = experiment_config(config, global_batch)
    journal = MemoryJournal()
    trainer = train_cli.build_trainer(
        cfg, None, ckpt_dir=None, journal=journal,
        steps_per_epoch=config["steps_per_epoch"],
        opt_state_dtype=config.get("optimizer_state_dtype"))
    return trainer, journal, train_cli.model_input_shape(cfg)


def seed_state(trainer, variables):
    """Put the benchmark's seeded variables into the trainer, at step 0
    with a fresh optimizer state. Refuses a tree the program does not
    have: leaf names and shapes are the program's. The state the trainer
    held is freed before the new optimizer state is made, so the device
    never holds two."""
    import jax
    import jax.numpy as jnp

    state = trainer.state
    held = {"params": state.params, "batch_stats": state.batch_stats}
    have = jax.tree.map(lambda x: (x.shape, str(x.dtype)), held)
    made = jax.tree.map(lambda x: (x.shape, str(x.dtype)), variables)
    if have != made:
        raise ValueError("the reference's variable tree is not the "
                         "program's: " + _first_difference(have, made))
    for leaf in jax.tree.leaves((held, state.opt_state)):
        leaf.delete()
    trainer.state = trainer._place_state(state.replace(
        step=jnp.zeros((), jnp.int32), params=variables["params"],
        batch_stats=variables["batch_stats"],
        opt_state=jax.jit(trainer._tx.init)(variables["params"])))


def _first_difference(a, b):
    import jax

    fa = dict(jax.tree_util.tree_flatten_with_path(
        a, is_leaf=lambda x: isinstance(x, tuple))[0])
    fb = dict(jax.tree_util.tree_flatten_with_path(
        b, is_leaf=lambda x: isinstance(x, tuple))[0])
    for k in sorted(set(fa) | set(fb), key=str):
        if fa.get(k) != fb.get(k):
            return (f"{jax.tree_util.keystr(k)}: program {fa.get(k)}, "
                    f"reference {fb.get(k)}")
    return "same leaves, another container"


def _find(state, name):
    """The first field called `name` in optax's nested state tuples."""
    if name in getattr(state, "_fields", ()):
        return getattr(state, name)
    if isinstance(state, (tuple, list)):
        for sub in state:
            found = _find(sub, name)
            if found is not None:
                return found
    return None


def first_gradient_norms(config, opt_state, params0):
    """Per-leaf norms of the first gradient as the optimizer got it, worked
    out from its state after one step (upcast first, where the state is
    stored below float32) in one program that keeps no gradient tree.
    `params0`, the parameters before that step, is read under SGD alone."""
    import jax
    import jax.numpy as jnp

    o = config["optimizer"]
    if o["name"] == "sgd":  # trace_1 = g_1 + weight_decay * p_0
        wd = o.get("weight_decay", 0.0)
        first = lambda t, p: t.astype("float32") - wd * p
        trace = _find(opt_state, "trace")
        trees = (trace, jax.device_put(
            params0, jax.tree.map(lambda t: t.sharding, trace)))
    elif o["name"] == "adamw":  # mu_1 = (1 - b1) * g_1
        b1 = o.get("b1", 0.9)
        first = lambda m: m.astype("float32") / (1 - b1)
        trees = (_find(opt_state, "mu"),)
    else:
        raise ValueError(f"no first gradient for optimizer {o['name']!r}")
    sums = jax.device_get(jax.jit(lambda *trees: [
        jnp.sum(jnp.square(first(*leaves)))
        for leaves in zip(*map(jax.tree.leaves, trees))])(*trees))
    return np.sqrt(np.asarray(sums, np.float64))


def _fit(trainer, batches):
    """The window's own call, closed: every step of it has finished."""
    import jax

    trainer.fit(lambda: batches, epochs=1, handle_preemption=False)
    jax.block_until_ready(trainer.state)


def first_steps(trainer, journal, config, pool, variables):
    """Drive the trainer through its first steps with the window's own call
    and feed. -> what the comparison reads of the program, on the host.
    Consumes `variables`: they become the trainer's state, which the steps
    donate; the caller keeps no name for them.

    The first gradient is read from the optimizer's state after exactly
    one step: a `fit` of one batch, closed, so that a loop which runs or
    fetches ahead cannot put the reading before or after that step."""
    import jax

    params0 = jax.device_get(variables["params"])
    seed_state(trainer, variables)
    del variables
    n_before = len(journal.steps)
    batches = [pool[i % len(pool)] for i in range(COMPARED_STEPS)]
    _fit(trainer, batches[:1])
    got = {"grad_norms": first_gradient_norms(
        config, trainer.state.opt_state, params0)}
    _fit(trainer, batches[1:])
    # on the host, where it waits out the window: float32 as the device
    # subtracts, but that the host keeps subnormals
    got["delta"] = jax.tree.map(lambda a, b: a - b,
                                jax.device_get(trainer.state.params), params0)
    got["losses"] = [f["metrics"]["loss"]
                     for _, f in journal.steps[n_before:]][:COMPARED_STEPS]
    return got


def reference_steps(config, pool, seed, devices, control=False, rows=None):
    """The plain reference over the same first steps, from the same seed;
    with `control`, in the precision below the configuration's."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.reference import steps

    module = reference_module(config)
    mesh = Mesh(np.asarray(devices), ("rows",))
    # set-up's own program makes them; placing them gives the source up
    variables = jax.device_put(
        jax.jit(lambda k: module.init(config, k))(seed_key(seed)),
        NamedSharding(mesh, P()), donate=True)
    batches = [jax.device_put(b, NamedSharding(mesh, P("rows")))
               for b in pool[:COMPARED_STEPS]]
    row_blocks = config.get("reference_row_blocks", 1)
    if rows is not None:  # a block stays whole rows where few rows are kept
        row_blocks = math.gcd(row_blocks, rows)
    return steps.run_steps(module, config, variables, batches,
                           control=control, rows=rows, row_blocks=row_blocks)


def as_program(reference_out):
    """A reference's readings in the shape of the program's, to put it in
    the program's place (the control, a planted fault)."""
    return {"losses": reference_out["losses"],
            "grad_norms": compare.leaf_norms(reference_out["grad"]),
            "delta": reference_out["delta"]}


def normalised_update(config) -> bool:
    return config["optimizer"]["name"].startswith("adam")


def timed_window(trainer, pool, seconds, trace_dir):
    """`Trainer.fit` over the cycled pool until `seconds` have passed.
    -> (steps, window seconds, step intervals in seconds)."""
    import jax

    stamps, tracing = [], [False]

    def stop_trace():
        if tracing[0]:
            tracing[0] = False
            jax.profiler.stop_trace()

    def feed():
        deadline = t_start + seconds
        for i, batch in enumerate(traffic_mod.cycle_from(pool,
                                                         COMPARED_STEPS)):
            now = time.perf_counter()
            stamps.append(now)
            if now >= deadline:
                return
            if trace_dir is not None and i == TRACE_FROM:
                options = jax.profiler.ProfileOptions()
                # the device's planes are all the reduction reads; the
                # host's tracers slow the loop they would watch
                options.python_tracer_level = 0
                options.host_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                tracing[0] = True
            if i == TRACE_FROM + TRACE_STEPS:
                stop_trace()
            yield batch

    t_start = time.perf_counter()
    try:
        trainer.fit(feed, epochs=1, handle_preemption=False)
        jax.block_until_ready(trainer.state)
    finally:
        stop_trace()
    t_end = time.perf_counter()
    steps = len(stamps) - 1
    stamps[-1] = t_end  # the last step's interval runs to the window's close
    return steps, t_end - t_start, list(np.diff(stamps))


def window_summary(intervals) -> dict:
    """Whether the window stalled: its steps, the median and the longest
    step interval, how many intervals took over three times the median,
    and the five longest as `[step, ms]`, steps from 0. The last interval
    runs to the window's close, over the step in flight too, so it is two
    steps long."""
    ms = np.asarray(intervals) * 1e3
    median = float(np.median(ms))
    longest = np.argsort(-ms, kind="stable")[:5]
    return {"steps": len(ms),
            "interval_median_ms": median,
            "interval_max_ms": float(ms[longest[0]]),
            "intervals_over_3x_median": int(np.sum(ms > 3 * median)),
            "longest": [[int(i), float(ms[i])] for i in longest]}


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest device: live arrays and the memory the
    programs reserve, whichever the backend reports higher."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(max(stats.get("peak_bytes_in_use", 0),
                         stats.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


def run(cell, config, traffic, seed, seconds, trace, t_process_start):
    """One run of a training cell. -> the run's record (see `run.py`)."""
    import jax

    from benchmark import trace as trace_mod

    devices = jax.devices()
    t_import = time.perf_counter()
    trainer, journal, image_shape = build_trainer(config,
                                                  traffic["global_batch"])
    t_built = time.perf_counter()
    pool = traffic_mod.make_pool(traffic, config, image_shape, seed)
    module = reference_module(config)
    # in a list, so that no name here outlives their hand-over
    variables = [jax.jit(lambda k: module.init(config, k))(seed_key(seed))]
    t_warm = time.perf_counter()
    program = first_steps(trainer, journal, config, pool, variables.pop())
    t_ready = time.perf_counter()
    print(f"set-up: {t_import - t_process_start:.1f} s to the adapter, "
          f"{t_built - t_import:.1f} s build_trainer, "
          f"{t_warm - t_built:.1f} s inputs and weights, "
          f"{t_ready - t_warm:.1f} s first steps", file=sys.stderr)

    compiles = _CompileCounter()
    n_rows = len(journal.steps)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        with compiles:
            steps, window_s, intervals = timed_window(trainer, pool, seconds,
                                                      trace_dir)
        reduction = trace_mod.reduce_trace(trace_dir) if trace else None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rows = [f for _, f in journal.steps[n_rows:]]
    losses = [f.get("metrics", {}).get("loss") for f in rows]
    faults = []
    # (the first row's compile_ms is the benchmark's own reading of the
    # first steps, made between warm-up and window)
    if compiles.count or any(f.get("compile_ms") for f in rows[1:]):
        faults.append(f"{compiles.count} compile events inside the window: "
                      f"{sorted(compiles.names)}")
    bad = sum(1 for x in losses if x is None or not np.isfinite(x))
    if bad or len(rows) != steps:
        faults.append(f"{bad} of {steps} steps with no finite loss "
                      f"({len(rows)} journal rows)")
    peak = memory_peak_bytes(devices)

    # the program's state goes before the reference comes: both would not fit
    trainer.close()
    del trainer
    gc.collect()
    t_ref = time.perf_counter()
    reference = reference_steps(config, pool, seed, devices)
    values = compare.gaps(program, reference, normalised_update(config))
    correct, compared = compare.judge(values, cell["limits"])
    return {
        "correct": correct and not faults, "faults": faults,
        "compared": compared, "attempted": steps, "failed": bad,
        "steps": steps, "window_s": window_s, "intervals_s": intervals,
        "window": window_summary(intervals),
        "global_batch": traffic["global_batch"], "chips": cell["chips"],
        "setup_s": t_ready - t_process_start, "warmup_s": t_ready - t_warm,
        "reference_s": time.perf_counter() - t_ref,
        "memory_peak_bytes": peak,
        "memory_peak_bytes_after": memory_peak_bytes(devices),
        "trace": reduction,
        "config": config,
        "batch_spec": traffic_mod.batch_spec(traffic, config, image_shape),
    }


class _CompileCounter:
    """Counts JAX's trace, lower and compile events while it is entered:
    nothing may compile inside the window."""

    def __init__(self):
        self.count, self.names, self.on = 0, set(), False

    def _event(self, event, duration, **kw):
        if self.on and ("compile" in event or "jaxpr_trace" in event):
            self.count += 1
            self.names.add(event)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._event)
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False
