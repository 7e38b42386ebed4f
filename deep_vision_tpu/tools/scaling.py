"""Parallel scaling-efficiency measurement over data-axis sub-meshes.

The evidence gap this closes: multi-chip dry runs proved `loss=OK` on a
`{'data': 4, 'model': 2}` mesh and nothing else — no number ever said
what the second through eighth chip BUY. This module
measures it: the same table-sharded train step timed at data={1,2,4,8}
sub-meshes of the available devices, reporting throughput, per-device
examples/s, and the efficiency fraction vs the 1-device baseline (1.0 =
linear scaling; the gap is the collective/dispatch cost).

Shared by the `__graft_entry__.dryrun_multichip` scaling section and
`make shard-smoke` (phase D, which journals the rows as a `bench` event)
— one measurement, two consumers, so the numbers are comparable.

On a real multi-chip slice the rows are the scaling story; on a forced
virtual-CPU mesh (every "device" is the same host core) efficiency
honestly degrades toward 1/n — the MECHANISM is what the CPU runs prove,
the number is what the TPU runs report.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence

__all__ = ["measure_scaling", "scaling_result", "format_rows"]

#: sub-mesh sizes the bench reports when enough devices exist
DEFAULT_SUB_SIZES = (1, 2, 4, 8)


def _build_step(devices, batch_per_device: int, rules):
    """(jitted step, placed state, placed batch): a slim flagship-family
    (BottleneckBlock ResNet) train step on a pure-DP mesh over
    `devices`, state placed per the declarative table. Slim for the
    same reason the dryrun's is: the scaling signal is per-step wall
    time, which extra depth inflates without adding information."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deep_vision_tpu.core.train_state import create_train_state
    from deep_vision_tpu.losses.classification import classification_loss_fn
    from deep_vision_tpu.models.resnet import BottleneckBlock, ResNet
    from deep_vision_tpu.parallel.mesh import create_mesh, data_sharding
    from deep_vision_tpu.train.optimizers import build_optimizer

    n = len(devices)
    mesh = create_mesh(devices=devices, data=n, model=1)
    model = ResNet(stage_sizes=(1, 1), block=BottleneckBlock, width=16,
                   num_classes=32)
    tx = build_optimizer("sgd", learning_rate=0.1, momentum=0.9)
    sample = jnp.ones((2, 32, 32, 3), jnp.float32)
    state = create_train_state(model, tx, sample)
    shardings, _report = rules.resolve(state, mesh)
    state = jax.device_put(state, shardings)

    rng = np.random.RandomState(0)
    batch_size = batch_per_device * n
    batch = {
        "image": rng.rand(batch_size, 32, 32, 3).astype(np.float32),
        "label": (np.arange(batch_size) % 32).astype(np.int32),
    }
    batch = {k: jax.device_put(v, data_sharding(mesh, np.asarray(v).ndim))
             for k, v in batch.items()}

    def train_step(state, batch):
        step_rng = jax.random.fold_in(state.rng, state.step)

        def loss_fn(params):
            variables = {"params": params,
                         "batch_stats": state.batch_stats}
            outputs, new_model_state = state.apply_fn(
                variables, batch["image"], train=True,
                rngs={"dropout": step_rng}, mutable=["batch_stats"],
            )
            loss, _ = classification_loss_fn(outputs, batch)
            return loss, new_model_state["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        return (state.apply_gradients(grads).replace(batch_stats=new_bs),
                loss)

    # AOT-compile instead of dispatch-compiling: the compiled artifact is
    # ALSO the evidence — its HLO names every collective the partitioner
    # inserted for this sub-mesh, which is the predicted comm bill each
    # scaling row carries next to its measured step time (obs/costmodel)
    import warnings

    with warnings.catch_warnings():
        # CPU has no donation support and warns once per lowering
        warnings.filterwarnings("ignore", message="Some donated buffers")
        step = jax.jit(train_step, donate_argnums=0).lower(
            state, batch).compile()
    return step, state, batch, batch_size


def _comm_profile(compiled, state) -> dict:
    """Predicted per-device comm bytes of one compiled scaling step, plus
    the gradient-tree size the all-reduce bytes are checked against."""
    from deep_vision_tpu.obs import costmodel

    hlo = costmodel.hlo_text(compiled)
    inv = costmodel.collective_inventory(hlo) if hlo else []
    return {
        "collective_ops": len(inv),
        "predicted_comm_bytes": costmodel.predicted_collective_bytes(inv),
        "predicted_allreduce_bytes": costmodel.predicted_collective_bytes(
            inv, "all-reduce"),
        "grad_tree_bytes": costmodel.tree_bytes(state.params),
    }


def measure_scaling(
    devices: Optional[Sequence] = None,
    sub_sizes: Sequence[int] = DEFAULT_SUB_SIZES,
    *,
    batch_per_device: int = 8,
    steps: int = 8,
    warmup: int = 2,
    rules=None,
) -> list:
    """Throughput rows at each data-parallel sub-mesh size.

    Each row: {"data": d, "examples_per_sec", "per_device_examples_per_sec",
    "efficiency", "wall_ms_per_step", "batch"}. `efficiency` is
    per-device examples/s over the 1-device row's (the fraction of
    linear scaling realized); the 1-device row anchors at 1.0. Sizes
    exceeding the device count are skipped, not faked.
    """
    import jax

    # degenerate knobs (steps=0, warmup=0) would leave
    # `loss` unbound or divide by a zero baseline — clamp, don't crash
    steps = max(1, int(steps))
    warmup = max(1, int(warmup))
    if rules is None:
        from deep_vision_tpu.parallel.shardmap import RESNET_RULES

        rules = RESNET_RULES
    if devices is None:
        devices = jax.devices()
    sizes = [d for d in sub_sizes if d <= len(devices)]
    rows = []
    base_per_device = None
    base_wall_ms = None
    for d in sizes:
        step, state, batch, batch_size = _build_step(
            list(devices[:d]), batch_per_device, rules)
        comm = _comm_profile(step, state)
        for _ in range(warmup):
            state, loss = step(state, batch)
        float(loss)  # close warmup (the scalar fetch waits for the device)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step(state, batch)
        float(loss)
        dt = time.perf_counter() - t0
        ex_s = batch_size * steps / dt
        per_dev = ex_s / d
        wall_ms = dt / steps * 1e3
        if base_per_device is None:
            base_per_device = per_dev
            base_wall_ms = wall_ms
        row = {
            "data": int(d),
            "batch": int(batch_size),
            "wall_ms_per_step": round(wall_ms, 3),
            "examples_per_sec": round(ex_s, 1),
            "per_device_examples_per_sec": round(per_dev, 1),
            "efficiency": round(per_dev / base_per_device, 4),
            # predicted comm bill (compiled HLO) next to what it cost in
            # wall time vs the 1-device baseline: the gap ROADMAP item 2's
            # comm/compute overlap work has to close
            "step_time_delta_ms": round(wall_ms - base_wall_ms, 3),
        }
        row.update(comm)
        rows.append(row)
    return rows


def scaling_result(rows: list, *, metric: str = "multichip_scaling") -> dict:
    """The bench-contract payload for a scaling run: headline `value` is
    the efficiency fraction at the LARGEST sub-mesh, rows carry the full
    curve."""
    import jax

    result = {
        "metric": metric,
        "value": float(rows[-1]["efficiency"]) if rows else 0.0,
        "unit": "efficiency_fraction",
        "rows": rows,
        "n_devices": len(jax.devices()),
    }
    try:
        result["device_kind"] = jax.devices()[0].device_kind
    except Exception:
        pass
    return result


def format_rows(rows: list) -> str:
    """Human lines for the dryrun tail / smoke stdout."""
    out = []
    for r in rows:
        line = (
            f"multichip_scaling: data={r['data']} "
            f"examples_per_sec={r['examples_per_sec']} "
            f"per_device={r['per_device_examples_per_sec']} "
            f"efficiency={r['efficiency']:.3f}")
        if r.get("predicted_comm_bytes") is not None:
            line += (f" comm_bytes={r['predicted_comm_bytes']} "
                     f"dt_ms={r.get('step_time_delta_ms', 0)}")
        out.append(line)
    return "\n".join(out)
