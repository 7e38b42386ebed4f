"""The gated delta rule's share of its roofline in a training step: the
least time the chip could take over the recurrence's mathematics (the
reference's `delta_rule_flops`: 18 dv dk a token a head a layer, over the
MXU's peak; or its `delta_rule_bytes`: q, k, v, g, b, o and their gradients
once each in the io dtype, over HBM's; the longer) over the traced seconds
a step of the ops that implement it (`delta_rule_ms`). The same work is
read whatever implements it: a chunked form executes other products.

At dk 96, dv 192 the bytes are the roof: 18 x 192 x 96 FLOP a token-head
over (2 x 96 + 2 x 192 + 2) x 2 values x 2 bytes is 143 FLOP a byte, under
the chip's ridge of 240. Nothing where the configuration's reference
writes no such count, or `delta_rule_ms` reads nothing."""
import importlib

import jax.numpy as jnp

from benchmark.metrics.delta_rule_ms import seconds


def read(run):
    s, peaks, cfg = seconds(run), run["peaks"], run["config"]
    module = importlib.import_module("benchmark.reference." + cfg["reference"])
    if s is None or not peaks or not hasattr(module, "delta_rule_flops"):
        return None
    rows, tokens = run["batch_spec"]["tokens"].shape
    rows //= run["chips"]
    least = max(
        module.delta_rule_flops(cfg, rows, tokens)
        / peaks["bf16_flops_per_s"],
        module.delta_rule_bytes(
            cfg, rows, tokens, jnp.dtype(cfg["compute_dtype"]).itemsize)
        / peaks["hbm_bytes_per_s"])
    return least / s * 100.0
