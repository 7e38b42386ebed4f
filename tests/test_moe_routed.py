"""A chip's share of a sigmoid-routed expert layer
(`parallel/moe.held_experts`) on the CPU, the grouped products
interpreted: against the plain reference's layer
(`benchmark/reference/solar_open2.py`), share by share and whole, with no
pair dropped in the worst case, and the grouped product against a loop over
the experts. float32: program and reference compute the same mathematics
in another order, so they part by float32 rounding (1e-7 to 1e-6 of a
norm); the tolerance 1e-5 stands ten times over that and far under a
dropped pair or a wrong weight (order 1e-2 to 1)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import solar_open2 as reference  # noqa: E402

E, HELD, K, D, F = 320, 8, 8, 16, 8
TOL = 1e-5


def apart(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def layer(seed=0, tokens=24, experts=E, held=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    x = normal(ks[0], tokens, D)
    p = {"router": normal(ks[1], D, experts),
         "gate": 0.3 * normal(ks[2], held, D, F),
         "up": 0.3 * normal(ks[3], held, D, F),
         "down": 0.3 * normal(ks[4], held, F, D),
         "shared_gate": 0.3 * normal(ks[5], D, F),
         "shared_up": 0.3 * normal(ks[6], D, F),
         "shared_down": 0.3 * normal(ks[7], F, D)}
    bias = 0.1 * normal(ks[8], experts)
    return x, p, bias


def cfg(held, offset=0, experts=E):
    return {"router_experts": experts, "num_experts_per_tok": K,
            "n_routed_experts": held, "held_offset": offset,
            "norm_topk_prob": True, "routed_scaling_factor": 1.0,
            "n_shared_experts": 1, "moe_intermediate_size": F}


def program(x, p, bias, offset, held, shared=True):
    experts = {name: jax.lax.dynamic_slice_in_dim(p[name], offset, held)
               for name in ("gate", "up", "down")}
    common = {name: p["shared_" + name] for name in ("gate", "up", "down")}
    return moe.held_experts(x, p["router"], bias, experts,
                            common if shared else None, top_k=K,
                            held_offset=offset)


def reference_layer(c, x, p, bias):
    with jax.default_matmul_precision("highest"):
        y, new_bias = reference._moe(c, lambda a: a, x[None], p, bias)
    return y[0], new_bias


def test_the_held_shares_add_up_to_the_uncut_layer():
    """320 experts over 40 shares of 8: each share's part of the output
    (what its held experts give), the shared expert counted once, sums to
    the uncut reference layer's output; each share's part is the
    reference's given the same share; the pairs the shares compute add up
    to every pair."""
    x, p, bias = layer()
    with jax.default_matmul_precision("highest"):
        parts = [program(x, p, bias, s * HELD, HELD, shared=False)
                 for s in range(E // HELD)]
        shared = moe._swiglu(x, p["shared_gate"], p["shared_up"],
                             p["shared_down"])
    whole, _ = reference_layer(cfg(E), x, p, bias)
    total = sum(y for y, _ in parts) + shared
    assert apart(total, whole) < TOL
    one = 13
    mine, _ = program(x, p, bias, one * HELD, HELD)
    theirs, _ = reference_layer(
        cfg(HELD, one * HELD), x, {**p, **{n: p[n][one * HELD:][:HELD]
                                            for n in ("gate", "up", "down")}},
        bias)
    assert apart(mine, theirs) < TOL
    assert sum(int(stats["pairs"]) for _, stats in parts) == x.shape[0] * K


def test_no_pair_is_dropped_where_every_choice_is_held():
    """The worst case: the correction bias sends every token to the 8 held
    experts, 8 pairs a token in a buffer of `T k` rows, all of them
    computed."""
    x, p, bias = layer(seed=1, tokens=40)
    offset = 16
    forced = bias.at[offset:offset + HELD].add(100.0)
    y, stats = program(x, p, forced, offset, HELD)
    assert int(stats["pairs"]) == 40 * K
    assert int(stats["load_max"]) == 40
    assert int(stats["load"][offset:offset + HELD].sum()) == 40 * K
    theirs, _ = reference_layer(
        cfg(HELD, offset), x,
        {**p, **{n: p[n][offset:offset + HELD] for n in ("gate", "up",
                                                         "down")}}, forced)
    assert apart(y, theirs) < TOL


def test_every_gradient_is_the_references():
    """Through the dispatch's and combine's transposes (gathers, where
    autodiff would scatter-add), the grouped products' own backward and the
    routing weights' normalisation, to x and every parameter."""
    x, p, bias = layer(seed=2)
    offset = 8
    held = {n: p[n][offset:offset + HELD] for n in ("gate", "up", "down")}
    c = cfg(HELD, offset)
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def mine(x, p):
        return jnp.sum(program(x, p, bias, offset, HELD)[0] * ct)

    def theirs(x, p):
        return jnp.sum(reference._moe(c, lambda a: a, x[None], p,
                                      bias)[0][0] * ct)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(mine, argnums=(0, 1))(x, p)
        want = jax.grad(lambda x, h, p: theirs(x, {**p, **h}),
                        argnums=(0, 1, 2))(x, held, p)
    assert apart(got[0], want[0]) < TOL
    for name in ("router", "shared_gate", "shared_up", "shared_down"):
        assert apart(got[1][name], want[2][name]) < TOL, name
    for name in ("gate", "up", "down"):
        g = got[1][name]
        assert float(jnp.abs(g[:offset]).max()) == 0.0, name  # not held
        assert apart(g[offset:offset + HELD], want[1][name]) < TOL, name


def test_the_bias_update_is_the_references():
    """Loads over all 320 experts; the bias moves by 1e-3 toward the mean
    load: up where an expert was chosen less, down where more."""
    x, p, bias = layer(seed=4, tokens=64)
    _, stats = program(x, p, bias, 0, HELD)
    _, want = reference_layer(cfg(HELD), x, p, bias)
    got = moe.bias_update(bias, stats["load"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    load = np.asarray(stats["load"])
    assert load.sum() == 64 * K
    mean = load.mean()
    step = np.asarray(got - bias)
    assert np.all(step[load > mean] < 0) and np.all(step[load < mean] > 0)
    assert np.allclose(np.abs(step[load != mean]), 1e-3)


@pytest.mark.parametrize("sizes", [(40, 0, 129, 7), (0, 0, 0, 0),
                                   (256, 0, 0, 0)],
                         ids=["uneven_with_an_empty_group", "no_pairs",
                              "all_in_one"])
def test_the_grouped_product_is_a_loop_over_the_experts(sizes):
    """Rows grouped in order, each group times its own matrix, rows past
    the groups unread and not compared; forward, and backward to both
    operands."""
    rows, k, n = 256, 32, 48
    sizes = jnp.asarray(sizes, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    lhs = jax.random.normal(ks[0], (rows, k))
    rhs = jax.random.normal(ks[1], (len(sizes), k, n))
    ct = jax.random.normal(ks[2], (rows, n))
    group = np.repeat(np.arange(len(sizes)), np.asarray(sizes))
    live = jnp.arange(rows) < int(sizes.sum())
    owner = jnp.asarray(np.pad(group, (0, rows - len(group))))

    def loop(lhs, rhs):
        return jnp.where(live[:, None], jnp.einsum(
            "rk,rkn->rn", lhs, rhs[owner]), 0.0)

    def grouped(lhs, rhs):
        return jnp.where(live[:, None],
                         moe.grouped_matmul(lhs, rhs, sizes), 0.0)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(grouped(lhs, rhs), loop(lhs, rhs),
                                   rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda *a: jnp.sum(grouped(*a) * ct),
                       argnums=(0, 1))(lhs, rhs)
        want = jax.grad(lambda *a: jnp.sum(loop(*a) * ct),
                        argnums=(0, 1))(lhs, rhs)
    # the input's gradient past the groups is left unwritten too: the
    # layer masks it (`_dispatch`'s transpose) and so does this
    np.testing.assert_allclose(jnp.where(live[:, None], got[0], 0.0),
                               want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)


def test_the_expert_layer_counts_its_sites_while_tracing():
    from deep_vision_tpu.obs.registry import get_registry

    x, p, bias = layer(seed=6)
    sites = get_registry().counter("moe_sites_total")
    before = sites.value
    jax.jit(lambda x: program(x, p, bias, 0, HELD)[0]).lower(x)
    assert sites.value == before + 1
