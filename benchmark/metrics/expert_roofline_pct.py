"""The held experts' grouped products' share of their roofline in a
training step: the least time the chip could take over their mathematics
at the pairs the step routed to them (`routed_pairs_per_step`; the
reference's `expert_flops`: 18 D F a pair, over the MXU's peak; or its
`expert_bytes`: the held experts' matrices read twice and their gradient
written, and each pair's rows, in the io dtype, over HBM's; the longer)
over the traced seconds a step of the ops that implement them
(`expert_ms`).

At 8 held experts of 1280 and ~1,638 pairs a layer the bytes are the
roof: a held expert sees ~205 tokens a step, so its weights, read and
written, outweigh its products (~18 x 205 / 9 = 410 FLOP a weight value
read against the chip's ridge of 240 a byte, and two bytes a value). A
product whose time follows the `T k` rows of its buffer and not the pairs
reads far under 100. Nothing where the configuration's reference writes
no such count, or either reader finds nothing."""
import importlib

import jax.numpy as jnp

from benchmark.metrics.expert_ms import seconds
from benchmark.metrics.routed_pairs_per_step import per_step


def read(run):
    s, peaks, cfg = seconds(run), run["peaks"], run["config"]
    module = importlib.import_module("benchmark.reference." + cfg["reference"])
    pairs = per_step("moe_routed_pairs_total")
    if s is None or pairs is None or not peaks \
            or not hasattr(module, "expert_flops"):
        return None
    least = max(
        module.expert_flops(cfg, pairs) / peaks["bf16_flops_per_s"],
        module.expert_bytes(cfg, pairs, jnp.dtype(
            cfg["compute_dtype"]).itemsize) / peaks["hbm_bytes_per_s"])
    return least / s * 100.0
