"""The attention kernels' share of their roofline in a training step: the
least time the chip could take over attention's mathematics
(`flops.attention_flops`: 12 T^2 d a head an image a block, the scores'
recomputation not counted; `flops.attention_bytes`: q, k, v, o and their
four gradients once each) over the traced seconds a step of the ops that
implement it, mean over the cell's devices.

The roof is the MXU's here, not HBM's: 12 T^2 d FLOP over 8 T d x 2 bytes
is 0.75 T FLOP a byte, about 3,000 at 4096 tokens against the chip's ridge
of 240 (197 TFLOP/s over 819 GB/s). The reader takes the larger of the two
times all the same, so a short sequence is held to the right roof.

The ops are the step table's whose names begin with one of the cell's
`attention_kernel_ops` (`cells/<cell>.json`; a Pallas call's op carries the
kernel's name and XLA's number: `flash_fwd.3`). A cell that names none, or
whose step holds none of them (attention fell back to the dense einsum),
reads nothing: no number rather than a wrong one."""
import jax.numpy as jnp

from benchmark import flops


def kernel_seconds(op_s_per_step, prefixes) -> float:
    return sum(s for name, s in op_s_per_step.items()
               if any(name == p or name.startswith(p + ".")
                      for p in prefixes))


def read(run):
    t, peaks, cfg = run["trace"], run["peaks"], run["config"]
    prefixes = run["cell"].get("attention_kernel_ops")
    if not t or not peaks or not prefixes:
        return None
    seconds = kernel_seconds(t["op_s_per_step"], prefixes)
    if seconds <= 0:
        return None
    height, width, _ = cfg["input_shape"]
    shape = dict(batch=run["global_batch"] // run["chips"],
                 tokens=(height // cfg["patch"]) * (width // cfg["patch"]),
                 heads=cfg["num_heads"],
                 head_dim=cfg["dim"] // cfg["num_heads"], depth=cfg["depth"])
    least = max(
        flops.attention_flops(**shape) / peaks["bf16_flops_per_s"],
        flops.attention_bytes(
            **shape, itemsize=jnp.dtype(cfg["compute_dtype"]).itemsize)
        / peaks["hbm_bytes_per_s"])
    return least / seconds * 100.0
