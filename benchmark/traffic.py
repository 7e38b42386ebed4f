"""The one traffic generator: reads a mix's parameters from
`benchmark/traffic/<name>.json` and makes the inputs from the seed.

Kinds, all training feeds: `pool_batches` distinct host batches of
`global_batch` rows, cycled for as long as the window lasts, handed over
as dicts of host arrays the way a loader hands them: the copy to the
device is inside the timed step. Every seed gives the same sizes; only
the values differ. A batch is what its kind makes it, and the adapter, the
plain reference and the FLOP count take it as it is:

- `resident_pool`: `{"image": float32 (rows, *input_shape) in [0, 1),
  "label": int32 (rows,) below the configuration's `num_classes`}`.
- `token_pool`: `{"tokens": int32 (rows, seq_len)}`, `seq_len` the mix's,
  ids uniform below the configuration's `vocab_size` (the slice held, where
  the vocabulary is sliced). One sequence a row, no document boundaries.
"""
from __future__ import annotations

import itertools

import jax
import numpy as np


def _resident_pool(traffic, config, input_shape):
    rows = traffic["global_batch"]
    return {"image": ((rows, *input_shape), np.float32, None),
            "label": ((rows,), np.int32, config["num_classes"])}


def _token_pool(traffic, config, input_shape):
    return {"tokens": ((traffic["global_batch"], traffic["seq_len"]),
                       np.int32, config["vocab_size"])}


# kind -> {array's name: (shape, dtype, integers drawn below this | None for
# floats in [0, 1))}, in the order the generator draws them
KINDS = {"resident_pool": _resident_pool, "token_pool": _token_pool}


def _layout(traffic, config, input_shape):
    if traffic["kind"] not in KINDS:
        raise ValueError(f"traffic kind {traffic['kind']!r} is not a "
                         f"training feed: have {sorted(KINDS)}")
    return KINDS[traffic["kind"]](traffic, config, input_shape)


def batch_spec(traffic: dict, config: dict, input_shape) -> dict:
    """One batch of the mix as `jax.ShapeDtypeStruct`s."""
    return {name: jax.ShapeDtypeStruct(shape, dtype) for name,
            (shape, dtype, _) in _layout(traffic, config, input_shape).items()}


def make_pool(traffic: dict, config: dict, input_shape, seed: int):
    """The mix's host batches, from the seed."""
    rng = np.random.default_rng(seed)
    layout = _layout(traffic, config, input_shape)
    return [
        {name: rng.random(shape, dtype=dtype) if below is None
         else rng.integers(0, below, shape, dtype=dtype)
         for name, (shape, dtype, below) in layout.items()}
        for _ in range(traffic["pool_batches"])
    ]


def cycle_from(pool, start: int):
    """The pool, cycled, beginning at batch `start`."""
    return itertools.islice(itertools.cycle(pool), start, None)
