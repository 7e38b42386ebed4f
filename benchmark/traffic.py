"""The one traffic generator: reads a mix's parameters from
`benchmark/traffic/<name>.json` and makes the inputs from the seed.

Kinds:

- `resident_pool`: a training feed. `pool_batches` distinct host batches
  of `global_batch` rows (float32 images in [0, 1), int32 labels), cycled
  for as long as the window lasts, handed over as host arrays the way a
  loader hands them: the copy to the device is inside the timed step.
  Every seed gives the same sizes; only the values differ.
"""
from __future__ import annotations

import itertools

import numpy as np


def make_pool(traffic: dict, image_shape, num_classes: int, seed: int):
    if traffic["kind"] != "resident_pool":
        raise ValueError(f"traffic kind {traffic['kind']!r} is not a "
                         "training feed")
    rng = np.random.default_rng(seed)
    rows = traffic["global_batch"]
    return [
        {"image": rng.random((rows, *image_shape), dtype=np.float32),
         "label": rng.integers(0, num_classes, (rows,), dtype=np.int32)}
        for _ in range(traffic["pool_batches"])
    ]


def cycle_from(pool, start: int):
    """The pool, cycled, beginning at batch `start`."""
    return itertools.islice(itertools.cycle(pool), start, None)
