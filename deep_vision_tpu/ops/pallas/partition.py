"""Run a batch-parallel Pallas call per shard of the mesh's data axis.

A `pallas_call` is an opaque custom call to XLA's SPMD partitioner, and the
TPU lowering refuses one outright in a program that spans more than one
device ("Mosaic kernels cannot be automatically partitioned. Please wrap
the call in a shard_map."). Every kernel in this package is independent
along its leading (batch) dimension — flash attention and NMS are
per-image — so the wrap is exact: each device runs the kernel
on the rows it already holds and nothing moves.

The mesh comes from JAX's own context (`jax.set_mesh`, which the trainers
enter around their jitted steps); the batch axis is the repo's `data` axis
(parallel/mesh.py), the one every sharding table declares. With no mesh in
context, or a one-device mesh, the kernel is called directly.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
from jax.sharding import PartitionSpec as P

from deep_vision_tpu.parallel.mesh import DATA_AXIS


def _context_mesh():
    """The multi-device mesh in context, or None — also None inside a
    shard_map (ring attention's), whose body already runs per device."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return None
    if DATA_AXIS not in mesh.axis_names:
        raise ValueError(
            f"Pallas kernels partition over the {DATA_AXIS!r} mesh axis; "
            f"the context mesh has {mesh.axis_names}")
    return mesh


def batch_parallel_only() -> bool:
    """Is the data axis the only axis of the context mesh that spans
    devices (manual axes aside: inside a shard_map a device holds its own
    rows)? Under tensor parallelism the heads are split over another axis;
    `over_data_axis` would gather them and run every head on every device
    of that axis, where XLA partitions the dense expression by heads."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.empty or all(
        size == 1 for name, size in mesh.shape.items()
        if name != DATA_AXIS and name not in mesh.manual_axes)


def over_data_axis(kernel: Callable, batched: Sequence[bool]) -> Callable:
    """`kernel(*args)` with each `batched[i]` arg (and every output) split
    along dim 0 over the context mesh's data axis; other args replicate.
    shard_map raises for a batch the axis does not divide (the trainers pad
    every batch to the axis size)."""
    if _context_mesh() is None:
        return kernel
    return jax.shard_map(
        kernel,
        in_specs=tuple(P(DATA_AXIS) if b else P() for b in batched),
        out_specs=P(DATA_AXIS),
        # the body is one custom call: nothing for the varying-axes
        # checker to see through
        check_vma=False)
