"""Plain reference: the first training steps, optimizer included.

`run_steps` follows the first steps of a training run from the same seeded
variables and the same batches as the program, in float32 at "highest"
matmul precision, and returns what the comparison reads: each step's
loss, the first gradient, and the parameters' change after the last step.
The optimizer updates are written out (SGD with momentum and L2 weight
decay as torch's; AdamW, decoupled decay, bias-corrected) and the learning
rate schedules too; the optimizer's state is stored in the configuration's
`optimizer_state_dtype` (float32 where it states none), the update itself
in float32. Imports nothing of the program, and not optax.

`control` computes the same in the precision below the configuration's
(`CONTROL_BELOW`): every matmul operand is rounded to that type in the
forward pass, gradients pass straight through the rounding. `rows` keeps
only the first rows of every batch (a planted fault: part of the batch
left out, the mean over the rest).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def learning_rate(cfg, count: int) -> float:
    """The rate the optimizer applies at its `count`-th update (from 0)."""
    base = cfg["optimizer"]["learning_rate"]
    sched = cfg.get("schedule")
    if not sched:
        return base
    if sched["kind"] != "cosine":
        raise ValueError(f"no reference for schedule {sched['kind']!r}")
    spe = cfg["steps_per_epoch"]
    warm = max(sched.get("warmup_epochs", 0) * spe, 1)
    total = sched["total_epochs"] * spe
    if count < warm:
        return base * count / warm
    frac = min((count - warm) / max(total - warm, 1), 1.0)
    return base * 0.5 * (1.0 + math.cos(math.pi * frac))


def _round_bfloat16(x):
    return x + jax.lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(x.dtype) - x)


def _round_fp8(x):
    """Round to float8 e4m3 on a per-tensor scale; identity gradient."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
        jnp.finfo(jnp.float8_e4m3fn).max)
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(y - x)


# the control's rounding, by the precision the configuration states
# (`compute_dtype`): the nearest one below it
CONTROL_BELOW = {"float32": _round_bfloat16, "bfloat16": _round_fp8}


def _state_dtype(cfg):
    """The type the optimizer's state is stored in between steps: the
    configuration's `optimizer_state_dtype`, float32 where it states none."""
    return jnp.dtype(cfg.get("optimizer_state_dtype") or "float32")


def _opt_init(cfg, params):
    names = ("trace",) if cfg["optimizer"]["name"] == "sgd" else ("mu", "nu")
    # `zeros_like` keeps each parameter's placement, so the first step runs
    # the program that the later ones run
    return {name: jax.tree.map(
        lambda p: jnp.zeros_like(p, dtype=_state_dtype(cfg)), params)
        for name in names}


def _opt_update(cfg, params, grads, opt, lr, count):
    """The update in float32 whatever the state is stored in: the state is
    upcast on the way in, the parameters move by the unrounded new state,
    and that is rounded once on the way out."""
    o = cfg["optimizer"]
    wd = o.get("weight_decay", 0.0)
    opt = jax.tree.map(lambda x: x.astype(jnp.float32), opt)
    stored = lambda new: jax.tree.map(
        lambda x: x.astype(_state_dtype(cfg)), new)
    if o["name"] == "sgd":
        trace = jax.tree.map(
            lambda g, p, t: o.get("momentum", 0.0) * t + g + wd * p,
            grads, params, opt["trace"])
        return jax.tree.map(lambda p, t: p - lr * t, params, trace), \
            stored({"trace": trace})
    if o["name"] == "adamw":
        b1, b2, eps = o.get("b1", 0.9), o.get("b2", 0.999), 1e-8
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                          opt["nu"], grads)
        c1 = 1 - jnp.power(b1, count + 1)
        c2 = 1 - jnp.power(b2, count + 1)
        new = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), params, mu, nu)
        return new, stored({"mu": mu, "nu": nu})
    raise ValueError(f"no reference for optimizer {o['name']!r}")


def _host_copy(tree):
    """The tree on the host, in memory of its own: the CPU backend's
    `device_get` is a view of the device's buffer, which a donation could
    then neither take nor overwrite."""
    return jax.tree.map(np.array, jax.device_get(tree))


def run_steps(module, cfg, variables, batches, control=False, rows=None,
              row_blocks=1):
    """Follow `len(batches)` steps; a batch is the feed's dict of arrays,
    rows leading, handed to `module.loss_fn` as it is. -> {"losses": [...],
    "grad": tree of the first step's gradient, "delta": tree of params
    after the last step minus params before the first}, both on the device.

    Consumes `variables`: every step donates its state, so the leaves
    handed in are deleted, and a caller that follows the steps twice makes
    them twice. What is on the device is then the training state (the
    parameters and the optimizer's state as stored) and, inside a step,
    one float32 tree of gradients and the activations of `loss_fn`: the
    first parameters and the first gradient wait on the host, and come back
    once the optimizer's state is freed.

    `row_blocks` > 1 accumulates the gradient over equal blocks of rows (so
    the reference's activations fit the device) at the cost of one more
    float32 tree, the accumulator; refused where rows are coupled. A
    configuration that fills the device runs whole (`row_blocks` 1) and
    blocks inside its own `loss_fn`."""
    q = CONTROL_BELOW[cfg["compute_dtype"]] if control else (lambda x: x)
    if row_blocks > 1 and module.BATCH_COUPLED:
        raise ValueError("rows are coupled through batch statistics")

    def grad_block(params, stats, batch):
        (loss, new_stats), grads = jax.value_and_grad(
            lambda p: module.loss_fn(cfg, p, stats, batch, q),
            has_aux=True)(params)
        return loss, new_stats, grads

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, stats, opt, batch, lr, count):
        if rows is not None:
            batch = jax.tree.map(lambda x: x[:rows], batch)
        if row_blocks == 1:
            loss, new_stats, grads = grad_block(params, stats, batch)
        else:
            def body(acc, block):
                l, s, g = grad_block(params, stats, block)
                return jax.tree.map(lambda a, b: a + b / row_blocks,
                                    acc, (l, g)), s
            split = lambda x: x.reshape(row_blocks, -1, *x.shape[1:])
            zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
            (loss, grads), new_stats = jax.lax.scan(
                body, zero, jax.tree.map(split, batch))
            new_stats = jax.tree.map(lambda x: x[-1], new_stats)
        new_params, new_opt = _opt_update(cfg, params, grads, opt, lr, count)
        return new_params, new_stats, new_opt, loss, grads

    params, stats = variables["params"], variables["batch_stats"]
    placed = jax.tree.map(lambda x: x.sharding, params)
    first, opt = _host_copy(params), _opt_init(cfg, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for count, batch in enumerate(batches):
            params, stats, opt, loss, grads = step(
                params, stats, opt, batch,
                jnp.float32(learning_rate(cfg, count)), jnp.float32(count))
            losses.append(float(loss))
            if count == 0:
                first_grad = _host_copy(grads)
            del grads  # or it lies beside the next step's
    del opt
    delta = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b),
                    donate_argnums=0)(params, jax.device_put(first, placed))
    return {"losses": losses, "grad": jax.device_put(first_grad, placed),
            "delta": delta}
