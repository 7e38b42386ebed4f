"""Laguna (`models/laguna.py`) at a tiny size on the CPU against its plain
reference (`benchmark/reference/laguna.py`): the leaf tree (query heads by
layer type), the loss, every parameter's gradient and one AdamW step, both
rotary forms, the softmax router, a chip's share of an expert layer
against the uncut layer, and the stand-in for the experts held elsewhere
against the uncut layer whose experts are copies of the held ones.

Tolerances. Both are float32 here and compute the same mathematics in
another order (the experts as grouped products over the held pairs
against every held expert over every token, the rotation from tables
against its formula, the loss in blocks against rows), so they part by
float32 rounding: the loss 1e-7 of itself, per-leaf gradients 1e-7 to 5e-6
of a leaf's norm. The limits, 1e-5 and 5e-5, stand ten times over that and
far under what a dropped pair, a key outside the window or bfloat16
anywhere reads (4e-3 a product)."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deep_vision_tpu.losses.causal_lm import causal_lm_loss_fn
from deep_vision_tpu.models import get_model
from deep_vision_tpu.models.laguna import ROPE_PARAMETERS as ROPE
from deep_vision_tpu.obs.registry import get_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import laguna as reference  # noqa: E402
from benchmark.reference import steps  # noqa: E402

LAYERS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
MLPS = ["dense"] + ["sparse"] * 4
HEADS = [6, 8, 8, 8, 6]
CFG = {"hidden_size": 32, "num_key_value_heads": 2, "head_dim": 16,
       "intermediate_size": 64, "moe_intermediate_size": 16,
       "shared_expert_intermediate_size": 16, "num_experts": 4,
       "router_experts": 16, "num_experts_per_tok": 4, "vocab_size": 64,
       "num_hidden_layers": 5, "held_offset": 4, "sliding_window": 16,
       "layer_types": LAYERS, "mlp_layer_types": MLPS,
       "num_attention_heads_per_layer": HEADS, "rope_parameters": ROPE,
       "rms_norm_eps": 1e-6, "moe_routed_scaling_factor": 2.5}
KWARGS = {k: CFG[k] for k in (
    "hidden_size", "num_key_value_heads", "head_dim", "intermediate_size",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "num_experts", "router_experts", "num_experts_per_tok", "vocab_size",
    "num_hidden_layers", "held_offset", "sliding_window", "layer_types",
    "mlp_layer_types", "num_attention_heads_per_layer")}


def apart(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def counted(name):
    return {tuple(sorted(m.labels.items())): m.value
            for m in get_registry().metrics()
            if m.name == name and m.kind == "counter"}


@pytest.fixture(scope="module")
def seeded():
    variables = reference.init(CFG, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    return get_model("laguna_xs2", **KWARGS), variables, tokens


def model_of(stand_in):
    return get_model("laguna_xs2", **KWARGS, stand_in=stand_in,
                     rope_parameters=CFG["rope_parameters"],
                     moe_routed_scaling_factor=2.5)


def test_the_leaf_tree_is_the_written_one(seeded):
    model, variables, _ = seeded
    mine = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 16), jnp.int32)))
    params = mine["params"]
    assert sorted(params) == ["block_0", "block_1", "block_2", "block_3",
                              "block_4", "embed", "final_norm", "head"]
    for i, heads in enumerate(HEADS):
        block = params[f"block_{i}"]
        assert sorted(block) == ["attn", "attn_norm", "mlp", "mlp_norm"]
        attn = block["attn"]
        # the query heads by layer type, over the same 2 KV heads; one gate
        # a head
        assert attn["q"]["kernel"].shape == (32, heads * 16)
        assert attn["k"]["kernel"].shape == (32, 2 * 16)
        assert attn["gate"]["kernel"].shape == (32, heads)
    assert sorted(params["block_0"]["mlp"]) == ["down", "gate", "up"]
    moe = params["block_1"]["mlp"]
    assert sorted(moe) == ["down", "gate", "router", "shared_down",
                           "shared_gate", "shared_up", "up"]
    # the held experts' matrices, the router over every expert
    assert moe["gate"].shape == (4, 32, 16) and moe["router"].shape == (32, 16)
    assert "batch_stats" not in mine  # no correction bias
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert shapes(mine["params"]) == shapes(variables["params"])
    assert variables["batch_stats"] == {}


def program_loss(model, tokens):
    def loss(p):
        out = model.apply({"params": p}, tokens)
        return causal_lm_loss_fn(out, {"tokens": tokens}, block_tokens=16)
    return loss


@pytest.mark.parametrize("stand_in", [False, True])
def test_loss_every_gradient_and_one_adamw_step_are_the_references(
        seeded, stand_in):
    """64 tokens, a window of 16, 4 of 16 experts held from the fifth (the
    others left out, or stood in for by the held ones); then one AdamW step
    of each from its own gradient."""
    _, variables, tokens = seeded
    model = model_of(stand_in)
    cfg = {**CFG, "stand_in": stand_in}
    batch = {"tokens": tokens}
    before = counted("rotary_sites_total")
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(
            program_loss(model, tokens), has_aux=True)(variables["params"])
        (want, _), want_grads = jax.value_and_grad(
            lambda p: reference.loss_fn(cfg, p, {}, batch), has_aux=True)(
            variables["params"])
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    named = jax.tree_util.tree_flatten_with_path(grads)[0]
    # 5 attention kernels a block, 2 norms, dense 3, MoE 7 a layer x 4,
    # embedding, final norm, head
    assert len(named) == 5 * 5 + 5 * 2 + 3 + 4 * 7 + 3 == 69
    for (path, got), ref in zip(named, jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(ref)) > 0, path
        assert apart(got, ref) < 5e-5, jax.tree_util.keystr(path)
    # both rotary forms were traced: YaRN in the two full layers, the
    # default form in the three sliding ones, q and k by one table each
    after = counted("rotary_sites_total")
    grew = {dict(k)["kind"]: v - before.get(k, 0) for k, v in after.items()}
    assert grew["yarn"] > 0 and grew["default"] > 0
    assert grew["default"] == pytest.approx(1.5 * grew["yarn"])
    # the report: the pairs the held experts computed over the four layers,
    # with the stand-in every choice of every token
    pairs = int(metrics["count/moe_routed_pairs"])
    if stand_in:
        assert pairs == 4 * 128 * 4
    else:
        assert 0 < pairs < 4 * 128 * 4
    # one AdamW step each, from the same state: the program's optimizer
    # (optax) and the reference's written one
    opt = {"optimizer": {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9,
                         "b2": 0.95, "weight_decay": 0.1}}
    tx = optax.adamw(1e-3, b1=0.9, b2=0.95, weight_decay=0.1)
    updates, _ = tx.update(grads, tx.init(variables["params"]),
                           variables["params"])
    mine = optax.apply_updates(variables["params"], updates)
    theirs, _ = steps._opt_update(opt, variables["params"], want_grads,
                                  steps._opt_init(opt, variables["params"]),
                                  1e-3, 0)
    for a, b, p in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs),
                       jax.tree.leaves(variables["params"])):
        # Adam's first step moves each element by about the rate, its sign
        # the gradient's: where a gradient is near zero the sign can differ
        # by rounding, so the step is compared as a whole
        assert apart(a - p, b - p) < 1e-3


def test_the_softmax_router_renormalises_its_top_k_and_scales_by_2_5():
    from deep_vision_tpu.parallel.moe import softmax_route

    x = jax.random.normal(jax.random.PRNGKey(0), (24, 32))
    kernel = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    choice, weights = softmax_route(x, kernel, 4, 2.5)
    np.testing.assert_allclose(np.asarray(jnp.sum(weights, axis=-1)), 2.5,
                               rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        want = reference.route({**CFG, "router_experts": 16}, lambda y: y,
                               x, kernel)
        probs = jax.nn.softmax(x @ kernel, axis=-1)
    # the chosen experts are the 4 most probable, their weights in order
    assert sorted(map(tuple, np.sort(np.asarray(choice), axis=1))) == sorted(
        map(tuple, np.sort(np.asarray(jax.lax.top_k(probs, 4)[1]), axis=1)))
    got = jnp.zeros((24, 16)).at[jnp.arange(24)[:, None], choice].set(weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-7)
    # the ratio of two chosen weights is that of their probabilities
    ratio = weights[:, 0] / weights[:, 1]
    want_ratio = (jnp.take_along_axis(probs, choice[:, :1], 1)
                  / jnp.take_along_axis(probs, choice[:, 1:2], 1))[:, 0]
    np.testing.assert_allclose(np.asarray(ratio), np.asarray(want_ratio),
                               rtol=1e-5)


def test_eight_chips_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips, 2 a chip: each chip's held experts' part
    of the layer plus the shared expert, with the shared expert counted
    once, is the reference's layer holding all 16."""
    from deep_vision_tpu.models.decoder import SparseMoe

    whole = {**CFG, "num_experts": 16, "held_offset": 0}
    p = reference.init({**whole, "num_hidden_layers": 2},
                       jax.random.PRNGKey(5))["params"]["block_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 32))
    with jax.default_matmul_precision("highest"):
        want = reference._moe(whole, lambda y: y, x, p)
        shared = reference._ffn(lambda y: y, x.reshape(32, 32),
                                p["shared_gate"], p["shared_up"],
                                p["shared_down"]).reshape(2, 16, 32)
        total = -7 * shared
        for chip in range(8):
            layer = SparseMoe(16, 2, 2 * chip, 4, 16, 16, 2.5,
                              correction_bias=False)
            held = {k: (v[2 * chip:2 * chip + 2]
                        if k in ("gate", "up", "down") else v)
                    for k, v in p.items()}
            y, _ = layer.apply({"params": held}, x)
            total = total + y
    assert apart(total, want) < 1e-5


@pytest.mark.parametrize("chip", [0, 3])
def test_the_stand_in_is_the_uncut_layer_of_copies_of_the_held_experts(chip):
    """16 experts, 4 held from `4 chip`: with `stand_in` expert e is
    computed by held expert (e - 4 chip) mod 4, so the layer is the
    reference's uncut layer whose 16 experts are the held 4 copied into
    every chip's place, and every choice is a pair computed here."""
    from deep_vision_tpu.models.decoder import SparseMoe

    p = reference.init({**CFG, "num_hidden_layers": 2},
                       jax.random.PRNGKey(8))["params"]["block_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 32))
    held = 4 * chip
    # expert e of the uncut layer: held expert (e - held) mod 4
    copies = {k: jnp.stack([v[(e - held) % 4] for e in range(16)])
              if k in ("gate", "up", "down") else v for k, v in p.items()}
    whole = {**CFG, "num_experts": 16, "held_offset": 0}
    share = {**CFG, "held_offset": held, "stand_in": True}
    with jax.default_matmul_precision("highest"):
        want = reference._moe(whole, lambda y: y, x, copies)
        theirs = reference._moe(share, lambda y: y, x, p)
        y, stats = SparseMoe(16, 4, held, 4, 16, 16, 2.5,
                             correction_bias=False, stand_in=True).apply(
            {"params": p}, x)
    assert apart(theirs, want) < 1e-6
    assert apart(y, want) < 1e-5
    assert int(stats["pairs"]) == 32 * 4
    # without it the layer is another: the absent experts' part is left out
    y_cut, cut = SparseMoe(16, 4, held, 4, 16, 16, 2.5,
                           correction_bias=False).apply({"params": p}, x)
    assert apart(y_cut, want) > 1e-2 and int(cut["pairs"]) < 32 * 4


def test_the_stand_in_needs_whole_copies():
    from deep_vision_tpu.parallel.moe import held_experts

    experts = {"gate": jnp.zeros((3, 8, 4)), "up": jnp.zeros((3, 8, 4)),
               "down": jnp.zeros((3, 4, 8))}
    with pytest.raises(ValueError, match="not whole copies"):
        held_experts(jnp.zeros((8, 8)), jnp.zeros((8, 16)), None, experts,
                     top_k=2, stand_in=True)


def test_train_py_trains_a_tiny_laguna(tmp_path):
    """`train.py -m <laguna>` at a test size: two steps of 8 x 64 tokens
    through the normal path, every expert held (all 16), so that the
    pairs computed are every pair."""
    from deep_vision_tpu import train_cli
    from deep_vision_tpu.configs import (CONFIG_REGISTRY, get_config,
                                         register_config)

    def counter(name):
        return sum(m.value for m in get_registry().metrics()
                   if m.name == name and m.kind == "counter")

    register_config(dataclasses.replace(
        get_config("laguna_xs2"), name="laguna_tiny", input_shape=(64,),
        batch_size=8, model_kwargs={**KWARGS, "num_experts": 16,
                                    "held_offset": 0}))
    before = counter("moe_routed_pairs_total")
    try:
        assert train_cli.main([
            "-m", "laguna_tiny", "--fake-data", "--fake-batches", "2",
            "--epochs", "1", "--skip-preflight", "--ckpt-dir",
            str(tmp_path / "ck")]) == 0
    finally:
        del CONFIG_REGISTRY["laguna_tiny"]
    assert counter("moe_routed_pairs_total") - before == 2 * 4 * 8 * 64 * 4
