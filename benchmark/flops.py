"""Floating-point operations of a function, counted from its jaxpr.

The count is of the mathematics: 2 x the multiply-accumulates of every
`dot_general` and `conv_general_dilated`, found by walking the jaxpr and
every jaxpr nested in it. Counted on the plain reference's
`value_and_grad`, so no remat, kernel or fusion in the program can change
it; a reference whose plain form multiplies by zeros (experts a token is
not routed to, masked scores) writes its count down instead
(`train_step_flops`). The zeros an input-gradient convolution puts between
the rows of a strided convolution's output (lhs dilation) are not counted:
the forward convolution did not multiply by them either.
"""
from __future__ import annotations

import math

import jax


def _conv_flops(eqn) -> float:
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    out = eqn.outvars[0].aval.shape
    dn = eqn.params["dimension_numbers"]
    spatial = math.prod(rhs[d] for d in dn.rhs_spec[2:])
    c_in = rhs[dn.rhs_spec[1]]  # already divided by feature_group_count
    macs = math.prod(out) * spatial * c_in
    macs /= math.prod(eqn.params["lhs_dilation"] or (1,))
    return 2.0 * macs / eqn.params.get("batch_group_count", 1)


def _dot_flops(eqn) -> float:
    lhs = eqn.invars[0].aval.shape
    (contract, _), _ = eqn.params["dimension_numbers"]
    return 2.0 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
        lhs[d] for d in contract)


def jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "dot_general":
            total += _dot_flops(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += jaxpr_flops(sub)
    return total


def flops_of(fn, *args) -> float:
    """FLOPs of `fn(*args)`; args may be `jax.ShapeDtypeStruct`s."""
    return jaxpr_flops(jax.make_jaxpr(fn)(*args).jaxpr)


def train_step_flops(module, cfg, batch_spec) -> float:
    """FLOPs of one training step of the plain reference `module` over a
    batch of `batch_spec` (`traffic.batch_spec`): forward, and backward to
    every parameter.

    Where the module defines `step_flops(cfg, batch_spec)`, a written count
    of the step's mathematics, that is the number: a plain reference
    computes every held expert over every token and every masked score,
    and its jaxpr counts them. Where it defines none, the jaxpr count."""
    if hasattr(module, "step_flops"):
        return float(module.step_flops(cfg, batch_spec))
    # a reference that recomputes in its backward pass (`reference_remat`)
    # holds its forward twice in that jaxpr: the count is of the mathematics,
    # so it is taken with the recomputation off (tracing allocates nothing)
    cfg = {**cfg, "reference_remat": False}
    variables = jax.eval_shape(lambda: module.init(cfg, jax.random.PRNGKey(0)))

    def step(params, stats, batch):
        return jax.value_and_grad(
            lambda p: module.loss_fn(cfg, p, stats, batch), has_aux=True)(
                params)

    return flops_of(step, variables["params"], variables["batch_stats"],
                    batch_spec)


def attention_flops(batch: int, tokens: int, heads: int, head_dim: int,
                    depth: int) -> float:
    """FLOPs of the attention products of one training step, the
    mathematics: a head of an image of a block multiplies Q K^T and P V
    forward (2 x 2 T^2 d) and dP = dO V^T, dV = P^T dO, dQ = dS K,
    dK = dS^T Q backward (4 x 2 T^2 d): 12 T^2 d. A kernel that recomputes
    the scores in its backward pass executes more; that is not counted, so
    the same work is read whatever implements it."""
    return float(depth * batch * heads * 12 * tokens * tokens * head_dim)


def attention_bytes(batch: int, tokens: int, heads: int, head_dim: int,
                    depth: int, itemsize: int) -> float:
    """The least HBM traffic of the same: q, k, v, o forward and dO, dq,
    dk, dv backward, each once in the io dtype; no score ever leaves the
    chip's near memory."""
    return float(depth * 8 * batch * tokens * heads * head_dim * itemsize)
