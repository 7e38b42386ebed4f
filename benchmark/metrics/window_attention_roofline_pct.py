"""The sliding-window attention kernels' share of their roofline in a
training step: the least time the chip could take over the windowed
layers' attention at the pairs their windows leave (the reference's
`window_attention_flops`: 12 x pairs x head size a head of a row, the
scores' recomputation not counted, over the MXU's peak; or its
`window_attention_bytes`: q, k, v, o and their gradients once each in the
io dtype, over HBM's; the longer) over the traced seconds a step of the
ops that implement it (`window_attention_ms`).

At a window of 512 the MXU's is the roof: 12 W d FLOP a query against
16 d bytes of its q, o and gradients is ~380 FLOP a byte against the
chip's ridge of 240. A kernel whose grid visits the whole causal triangle
(T^2 / 2 pairs) and masks it reads about W / T of what one that skips the
blocks outside the band reads. Nothing where the configuration's reference
writes no such count, or `window_attention_ms` finds nothing."""
import importlib

import jax.numpy as jnp

from benchmark.metrics.window_attention_ms import seconds


def read(run):
    s, peaks, cfg = seconds(run), run["peaks"], run["config"]
    if s is None or not peaks:
        return None
    module = importlib.import_module("benchmark.reference." + cfg["reference"])
    if not hasattr(module, "window_attention_flops"):
        return None
    rows, tokens = run["batch_spec"]["tokens"].shape
    rows //= run["chips"]
    least = max(
        module.window_attention_flops(cfg, rows, tokens)
        / peaks["bf16_flops_per_s"],
        module.window_attention_bytes(cfg, rows, tokens, jnp.dtype(
            cfg["compute_dtype"]).itemsize) / peaks["hbm_bytes_per_s"])
    return least / s * 100.0
