"""Pipeline parallelism: GPipe-style microbatch streaming over a mesh axis.

The reference has no pipeline parallelism anywhere (its only distribution is
single-host data parallel, SURVEY.md §2.5); this module is part of the
framework's first-class distributed story (DP x TP x PP x SP x EP). Design is
the TPU-native schedule: stages live on consecutive devices of a named mesh
axis, activations hop stage-to-stage with a single `ppermute` per tick (one
ICI hop — neighbours on the axis are physical ICI neighbours on a TPU
torus), and the whole (stages + microbatches - 1)-tick schedule is a
`lax.scan` under `shard_map`, so XLA sees one fused SPMD program and the
GPipe backward schedule falls out of reverse-mode AD over the scan — no
hand-written 1F1B state machine.

Contract: every stage maps activations of one fixed shape to the same shape
(pick stage boundaries accordingly — e.g. hourglass stacks, or the uniform
trunk of a deep residual network; put shape-changing stems/heads outside the
pipelined trunk). Per-stage params are stacked on a leading `num_stages`
axis and sharded over the pipeline axis, so each device holds exactly its
stage's weights: model memory scales 1/S with pipeline depth.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deep_vision_tpu.parallel.mesh import MODEL_AXIS


def stack_pipeline_params(params_list):
    """Stack S per-stage param pytrees on a new leading stage axis.

    All stages must share one tree structure and per-leaf shapes (the
    fixed-activation-shape contract above implies this for conv/dense
    trunks).
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def pipeline_param_sharding(mesh: Mesh, stacked_params,
                            axis_name: str = MODEL_AXIS):
    """Shard the leading (stage) axis of stacked params over `axis_name`."""
    def rule(p):
        return NamedSharding(mesh, P(axis_name, *([None] * (p.ndim - 1))))

    return jax.tree_util.tree_map(rule, stacked_params)


def _pipeline_local(stacked_params, x, *, stage_fn, axis_name: str,
                    n_micro: int):
    """Per-device body (under shard_map).

    stacked_params: this device's (1, ...) slice of the stage-stacked tree.
    x: the full (B, ...) input (replicated; stage 0 reads it).
    """
    params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
    s = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    assert x.shape[0] % n_micro == 0, (
        f"batch {x.shape[0]} not divisible into {n_micro} microbatches"
    )
    micro = x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])
    fwd = [(i, i + 1) for i in range(s - 1)]  # stage i -> i+1 (no wraparound)

    def tick(carry, t):
        act, out = carry
        # stage 0 injects microbatch t (clipped: ticks past the last
        # injection feed a dummy that drains off the end unrecorded)
        inject = micro[jnp.clip(t, 0, n_micro - 1)]
        cur = jnp.where(my == 0, inject, act)
        y = stage_fn(params, cur)
        # the last stage's tick-t output is microbatch t-(s-1); the window
        # check masks both the fill bubble (idx < 0) and the drain dummies
        idx = t - (s - 1)
        record = (my == s - 1) & (idx >= 0) & (idx < n_micro)
        out = jnp.where(
            record,
            jax.lax.dynamic_update_index_in_dim(
                out, y, jnp.clip(idx, 0, n_micro - 1), 0
            ),
            out,
        )
        act = jax.lax.ppermute(y, axis_name, fwd)
        return (act, out), None

    act0 = jnp.zeros_like(micro[0])
    out0 = jnp.zeros_like(micro)
    act0 = jax.lax.pcast(act0, (axis_name,), to="varying")
    out0 = jax.lax.pcast(out0, (axis_name,), to="varying")
    (_, out), _ = jax.lax.scan(
        tick, (act0, out0), jnp.arange(n_micro + s - 1)
    )
    # only the last stage holds real outputs (everyone else accumulated
    # zeros), so a psum over the axis is a broadcast of the result
    out = jax.lax.psum(out, axis_name)
    return out.reshape(x.shape)


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x,
    mesh: Mesh,
    *,
    num_microbatches: int,
    axis_name: str = MODEL_AXIS,
):
    """Run `x` through S pipelined stages sharded over `axis_name`.

    stage_fn: (stage_params, act) -> act, shape-preserving.
    stacked_params: pytree with leading stage axis == mesh.shape[axis_name]
    (see `stack_pipeline_params`); device i computes stage i.
    x: (B, ...) global batch, B divisible by num_microbatches.

    Differentiable end-to-end: grads w.r.t. stacked_params come back with
    the same stage-sharded layout (reverse ppermutes ride the same ICI
    hops), so a pipelined train step is just jax.grad over this call.
    """
    n_stages = mesh.shape[axis_name]
    lead = {p.shape[0] for p in jax.tree_util.tree_leaves(stacked_params)}
    if lead != {n_stages}:
        raise ValueError(
            f"stacked params lead dims {lead} != {n_stages} pipeline stages"
        )
    body = functools.partial(
        _pipeline_local,
        stage_fn=stage_fn,
        axis_name=axis_name,
        n_micro=num_microbatches,
    )
    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), stacked_params
    )
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )
    return mapped(stacked_params, x)
