"""Optimizers and LR schedules for the whole zoo, built on optax.

Covers every recipe the reference configures (SURVEY.md §2.4):
- SGD(momentum, weight_decay), Adam(beta1 override), RMSprop(alpha/eps)
  (ResNet/pytorch/train.py:34-212, CycleGAN/tensorflow/train.py:130-131);
- StepLR / LambdaLR-poly / linear-decay schedules (ResNet/pytorch/train.py:45,
  93,133-138; CycleGAN/tensorflow/utils.py:5-28), cosine for modern recipes;
- ReduceLROnPlateau, which is *stateful host logic* (manual plateau at
  YOLO/tensorflow/train.py:56-68; torch plateau stepped on top-1 at
  ResNet/pytorch/train.py:411-415). Under jit the LR must be a traced input,
  so the optimizer is wrapped in `optax.inject_hyperparams` and the plateau
  object mutates `opt_state.hyperparams['learning_rate']` between steps.

Weight decay follows the reference semantics: torch-style SGD weight_decay is
L2 on *all* params; we default to skipping BN/bias (standard TPU recipe) with
`decay_bn_bias=True` to reproduce torch exactly.
"""
from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import optax

Schedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


def _decay_mask(params, decay_bn_bias: bool):
    if decay_bn_bias:
        return jax.tree_util.tree_map(lambda _: True, params)

    def mask_fn(path, leaf):
        name = "/".join(str(p.key) for p in path if hasattr(p, "key"))
        is_norm_or_bias = (
            name.endswith("bias") or "BatchNorm" in name or name.endswith("scale")
        )
        return not is_norm_or_bias

    return jax.tree_util.tree_map_with_path(mask_fn, params)


def make_schedule(kind: str = "constant", base_lr: float = 0.1, **kw) -> Schedule:
    """Named LR schedules matching the reference's configs."""
    if kind == "constant":
        return base_lr
    if kind == "step":  # torch StepLR (ResNet/pytorch/train.py:93)
        return optax.exponential_decay(
            base_lr,
            transition_steps=kw["step_size"],
            decay_rate=kw.get("gamma", 0.1),
            staircase=True,
        )
    if kind == "poly":  # LambdaLR poly decay (ResNet/pytorch/train.py:133-138)
        return optax.polynomial_schedule(
            init_value=base_lr,
            end_value=kw.get("end_lr", 0.0),
            power=kw.get("power", 1.0),
            transition_steps=kw["total_steps"],
        )
    if kind == "linear_decay":  # CycleGAN LinearDecay (utils.py:5-28)
        hold = kw.get("hold_steps", 0)
        total = kw["total_steps"]
        return optax.schedules.join_schedules(
            [
                optax.constant_schedule(base_lr),
                optax.linear_schedule(base_lr, 0.0, total - hold),
            ],
            boundaries=[hold],
        )
    if kind == "cosine":
        warmup = kw.get("warmup_steps", 0)
        sched = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=base_lr,
            warmup_steps=max(warmup, 1),
            decay_steps=kw["total_steps"],
            end_value=kw.get("end_lr", 0.0),
        )
        return sched
    raise ValueError(f"unknown schedule '{kind}'")


def _cast_float_leaves(tree, dtype):
    """Cast floating-point array leaves; ints (step counters) untouched."""

    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree)


def cast_optimizer_state(
    tx: optax.GradientTransformation,
    state_dtype,
    compute_dtype=jnp.float32,
) -> optax.GradientTransformation:
    """Store optimizer state (momentum, Adam moments) in `state_dtype`.

    The SGD+momentum update reads and rewrites a full params-sized trace
    every step; at f32 that is 2x params bytes of pure HBM traffic per
    step on top of the weights themselves. Storing the trace in bf16
    halves it (PERF.md §5's reckoning prices it: momentum read and write
    are 2 of the 6 passes over the parameters). The update itself still
    runs in `compute_dtype`: state is upcast entering the wrapped
    transform and the new state rounded back on the way out — one rounding
    per step, the same error model as bf16 gradient accumulation. Float
    leaves only; step counters and other integer state pass through
    untouched.
    """

    @jax.jit
    def init(params):
        # one program: the zeros are made in the stored type. Eagerly the
        # float32 moments would lie whole beside their rounded copies,
        # twice the training state at the peak (7.4 GB more at 0.93 B
        # parameters under AdamW)
        return _cast_float_leaves(tx.init(params), state_dtype)

    def update(updates, state, params=None, **extra):
        state = _cast_float_leaves(state, compute_dtype)
        updates, new_state = tx.update(updates, state, params, **extra)
        return updates, _cast_float_leaves(new_state, state_dtype)

    return optax.GradientTransformation(init, update)


def build_optimizer(
    name: str,
    learning_rate: Schedule,
    params=None,
    weight_decay: float = 0.0,
    decay_bn_bias: bool = False,
    grad_clip_norm: Optional[float] = None,
    state_dtype=None,
    **kw,
) -> optax.GradientTransformation:
    """Build an injectable optimizer. `learning_rate` may be float or schedule.

    Returned transformation always has `opt_state.hyperparams['learning_rate']`
    (via inject_hyperparams) so host-side plateau schedules can override it.
    `state_dtype` (e.g. jnp.bfloat16 / 'bfloat16') stores the optimizer
    state — momentum, Adam moments — in that dtype via
    `cast_optimizer_state`, halving the update's HBM traffic at bf16; the
    injected hyperparams (learning_rate) stay f32 so plateau writes and
    schedules are unaffected.
    """

    def _make(learning_rate):
        chain = []
        if grad_clip_norm:
            chain.append(optax.clip_by_global_norm(grad_clip_norm))
        if name == "sgd":
            if weight_decay:
                chain.append(
                    optax.add_decayed_weights(
                        weight_decay, mask=lambda p: _decay_mask(p, decay_bn_bias)
                    )
                )
            chain.append(
                optax.sgd(
                    learning_rate,
                    momentum=kw.get("momentum", 0.0),
                    nesterov=kw.get("nesterov", False),
                )
            )
        elif name == "adam":
            chain.append(
                optax.adam(
                    learning_rate,
                    b1=kw.get("b1", 0.9),
                    b2=kw.get("b2", 0.999),
                    eps=kw.get("eps", 1e-8),
                )
            )
            if weight_decay:
                chain.insert(
                    -1,
                    optax.add_decayed_weights(
                        weight_decay, mask=lambda p: _decay_mask(p, decay_bn_bias)
                    ),
                )
        elif name == "adamw":
            chain.append(
                optax.adamw(
                    learning_rate,
                    b1=kw.get("b1", 0.9),
                    b2=kw.get("b2", 0.999),
                    weight_decay=weight_decay,
                    mask=lambda p: _decay_mask(p, decay_bn_bias),
                )
            )
        elif name == "rmsprop":
            if weight_decay:
                chain.append(
                    optax.add_decayed_weights(
                        weight_decay, mask=lambda p: _decay_mask(p, decay_bn_bias)
                    )
                )
            chain.append(
                optax.rmsprop(
                    learning_rate,
                    decay=kw.get("alpha", 0.9),
                    eps=kw.get("eps", 1e-8),
                    momentum=kw.get("momentum", 0.0),
                )
            )
        elif name == "lamb":  # large-batch ImageNet recipes
            chain.append(
                optax.lamb(learning_rate, weight_decay=weight_decay,
                           mask=lambda p: _decay_mask(p, decay_bn_bias))
            )
        else:
            raise ValueError(f"unknown optimizer '{name}'")
        tx = optax.chain(*chain)
        if state_dtype is not None:
            # cast INSIDE inject_hyperparams: the hyperparams dict (and the
            # LR the plateau writes into it) stays f32, only the big
            # params-shaped state rounds to state_dtype
            tx = cast_optimizer_state(tx, jnp.dtype(state_dtype))
        return tx

    return optax.inject_hyperparams(_make)(learning_rate=learning_rate)


class ReduceLROnPlateau:
    """Host-side plateau schedule, kept outside jit by design.

    Mirrors torch ReduceLROnPlateau stepped on val top-1
    (ResNet/pytorch/train.py:411-415) and the manual plateau at
    YOLO/tensorflow/train.py:56-68. Call `step(metric)` once per epoch; it
    returns the current LR multiplier which the Trainer writes into
    `opt_state.hyperparams['learning_rate']`.
    """

    def __init__(self, factor=0.1, patience=10, mode="max", threshold=1e-4,
                 min_scale=0.0):
        self.factor = factor
        self.patience = patience
        self.mode = mode
        self.threshold = threshold
        # LR floor as a fraction of the base LR (torch's min_lr / base_lr)
        self.min_scale = min_scale
        self.best = None
        self.num_bad = 0
        self.scale = 1.0

    def _is_better(self, v):
        if self.best is None:
            return True
        if self.mode == "max":
            return v > self.best + self.threshold
        return v < self.best - self.threshold

    def step(self, metric: float) -> float:
        if self._is_better(metric):
            self.best = metric
            self.num_bad = 0
        else:
            self.num_bad += 1
            if self.num_bad > self.patience:
                self.scale = max(self.scale * self.factor, self.min_scale)
                self.num_bad = 0
        return self.scale

    def state_dict(self):
        return {
            "best": self.best,
            "num_bad": self.num_bad,
            "scale": self.scale,
        }

    def load_state_dict(self, d):
        self.best = d["best"]
        self.num_bad = d["num_bad"]
        self.scale = d["scale"]
