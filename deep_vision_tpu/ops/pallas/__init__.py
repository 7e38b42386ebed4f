"""Pallas TPU kernels for profiled hotspots.

The reference has no custom kernels (its C++/CUDA lives inside torch/TF —
SURVEY.md §2); here the hot ops XLA can't fuse optimally get hand-written
TPU kernels with lax fallbacks for non-TPU platforms and interpret-mode
tests on CPU.
"""
from deep_vision_tpu.ops.pallas.flash_attention import (
    flash_attention,
    fused_attention,
)
from deep_vision_tpu.ops.pallas.nms import pallas_nms

__all__ = [
    "flash_attention",
    "fused_attention",
    "pallas_nms",
]
