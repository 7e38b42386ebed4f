"""The comparison that decides `correct` for a training cell.

The program's first steps against the plain reference's, three numbers,
each with a limit of its own from the cell's file (`cells/<cell>.json`):

- `loss_gap`: the widest relative gap of a step's loss;
- `grad_gap`: the first gradient, by the worst leaf: the gap between the
  program's norm of that leaf and the reference's, over the reference's
  norm of that leaf or of the median leaf, whichever is larger (the
  median is over the leaves that have a gradient at all: a recipe that
  starts its residual branches at zero gives most leaves none at the
  first step);
- `delta_gap`: the same measure of the parameters' change after the last
  compared step. Under an optimizer that normalises its update (Adam),
  what has a gradient of nought to rounding moves by round-off alone, so
  there the change is measured over the elements whose reference gradient
  is at least a thousandth of the median leaf's (root mean square per
  element): a rule on the reference's gradient, applied inside leaves too,
  because a fused qkv bias holds the key's bias, which softmax leaves
  without a gradient, beside two that have one.

`readings.py` reads a fourth beside them, which no run is judged by:
`delta_distance`, the norm of the *difference* of the two changes over the
same elements and the same scale. A leaf whose norm is right and whose
elements lie elsewhere shows there alone: under a normalising optimizer
the part of a leaf with a small gradient (q and k in a fused qkv kernel)
moves as far as the rest, so noise on it is in neither `grad_gap` nor,
at its size, `delta_gap` (PERF.md section 7, PR 33).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "delta_gap")
NEGLIGIBLE = 1e-3


def _sq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def leaf_norms(tree) -> np.ndarray:
    """Euclidean norm of every leaf, as float64 on the host."""
    sums = jax.device_get(jax.jit(
        lambda t: [_sq(x) for x in jax.tree.leaves(t)])(tree))
    return np.sqrt(np.asarray(sums, np.float64))


def median_with_gradient(norms: np.ndarray) -> float:
    nonzero = norms[norms > 0]
    return float(np.median(nonzero)) if nonzero.size else 0.0


def worst_leaf(apart: np.ndarray, ref: np.ndarray):
    """-> (the widest of `apart` over the reference's norm of that leaf or
    of the median leaf, whichever is larger; the index of its leaf)."""
    scale = np.maximum(ref, median_with_gradient(ref))
    gap = apart / np.maximum(scale, 1e-300)
    return float(np.max(gap)), int(np.argmax(gap))


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray):
    """-> (the widest gap between the two norms, the index of its leaf)."""
    return worst_leaf(np.abs(prog - ref), ref)


def masked_delta_norms(delta_prog, delta_ref, grad_ref, floor: float):
    """Per-leaf norms of both changes over the elements whose reference
    gradient is at least `floor` in magnitude."""
    # the floor is an argument: as a constant of the program it would make
    # every seed a program of its own, compiled anew in every run
    def norms(dp, dr, g, floor):
        out = []
        for a, b, c in zip(*map(jax.tree.leaves, (dp, dr, g))):
            keep = (jnp.abs(c) >= floor).astype(jnp.float32)
            out.append((_sq(a * keep), _sq(b * keep)))
        return out

    sums = np.asarray(jax.device_get(jax.jit(norms)(
        delta_prog, delta_ref, grad_ref, jnp.float32(floor))), np.float64)
    return np.sqrt(sums[:, 0]), np.sqrt(sums[:, 1])


def masked_delta_distances(delta_prog, delta_ref, grad_ref, floor: float):
    """Per-leaf norm of the two changes' difference, over the same
    elements as `masked_delta_norms` keeps. A program of its own, so that
    the norms' program is the one every run has always compiled."""
    def norms(dp, dr, g, floor):
        return [_sq((a - b) * (jnp.abs(c) >= floor).astype(jnp.float32))
                for a, b, c in zip(*map(jax.tree.leaves, (dp, dr, g)))]

    return np.sqrt(np.asarray(jax.device_get(jax.jit(norms)(
        delta_prog, delta_ref, grad_ref, jnp.float32(floor))), np.float64))


def gaps(program: dict, reference: dict, normalised_update: bool,
         distance: bool = False) -> dict:
    """`program`: {"losses", "grad_norms": per leaf, "delta": tree};
    `reference`: {"losses", "grad": tree, "delta": tree}; one leaf order.
    With `distance`, `delta_distance` beside the three numbers compared."""
    lp, lr = np.asarray(program["losses"]), np.asarray(reference["losses"])
    ref_grad_norms = leaf_norms(reference["grad"])
    floor = 0.0
    if normalised_update:
        sizes = np.asarray([x.size for x in jax.tree.leaves(
            reference["grad"])], np.float64)
        floor = NEGLIGIBLE * median_with_gradient(
            ref_grad_norms / np.sqrt(sizes))
    dp, dr = masked_delta_norms(program["delta"], reference["delta"],
                                reference["grad"], floor)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(reference["grad"])[0]]
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"],
                                         ref_grad_norms)
    delta_gap, delta_leaf = worst_leaf_gap(dp, dr)
    out = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": grad_gap, "delta_gap": delta_gap,
        "worst_leaves": {"grad_gap": names[grad_leaf],
                         "delta_gap": names[delta_leaf]},
    }
    if distance:
        out["delta_distance"], leaf = worst_leaf(masked_delta_distances(
            program["delta"], reference["delta"], reference["grad"], floor),
            dr)
        out["worst_leaves"]["delta_distance"] = names[leaf]
    return out


def judge(values: dict, limits: dict):
    """-> (correct, {name: {"value", "limit"[, "leaf"]}}). Every number
    has a limit and is held to it: one that the cell's file does not
    name, or that is not finite, fails."""
    compared, ok = {}, True
    for name in NUMBERS:
        value, limit = values[name], limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if name in values.get("worst_leaves", {}):
            compared[name]["leaf"] = values["worst_leaves"][name]
        if limit is None or not math.isfinite(value) or value > limit:
            ok = False
    return ok, compared
