"""Solar-Open2 (`models/solar_open2.py`) at a tiny size on the CPU against
its plain reference (`benchmark/reference/solar_open2.py`): the leaf tree,
the loss, every parameter's gradient and the routers' bias update.

Tolerances. Both are float32 here and compute the same mathematics in
another order (KDA in chunks of 64 against token by token, the experts as
grouped products over the held pairs against every held expert over every
token, the loss in blocks against rows), so they part by float32 rounding:
the loss 1e-7 of itself, per-leaf gradients 1e-7 to 5e-6 of a leaf's norm.
The limits, 1e-5 and 5e-5, stand ten times over that and far under what a
dropped pair, a wrong decay channel or bfloat16 anywhere reads (4e-3 a
product)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.losses.causal_lm import causal_lm_loss_fn
from deep_vision_tpu.models import get_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import solar_open2 as reference  # noqa: E402

CFG = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 8, "moe_intermediate_size": 16, "n_routed_experts": 4,
       "router_experts": 16, "num_experts_per_tok": 4, "vocab_size": 64,
       "num_hidden_layers": 4, "held_offset": 4, "gqa_layers": [0, 4],
       "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                              "num_heads": 2, "num_kv_heads": None},
       "rms_norm_eps": 1e-5, "use_gqa_gate": True,
       "kda_allow_neg_eigval": True, "norm_topk_prob": True,
       "routed_scaling_factor": 1, "n_shared_experts": 1}
KWARGS = {"hidden_size": 32, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 8, "linear_num_heads": 2,
          "linear_head_dim": 8, "moe_intermediate_size": 16,
          "n_routed_experts": 4, "router_experts": 16,
          "num_experts_per_tok": 4, "vocab_size": 64, "num_hidden_layers": 4,
          "held_offset": 4, "gqa_layers": (0, 4)}
GQA_LEAVES = ["gate", "k", "o", "q", "v"]
KDA_LEAVES = ["A_log", "b", "dt_bias", "f_a", "f_b", "g_a", "g_b", "k",
              "k_conv", "o", "o_norm", "q", "q_conv", "v", "v_conv"]
MOE_LEAVES = ["down", "gate", "router", "shared_down", "shared_gate",
              "shared_up", "up"]


def apart(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


@pytest.fixture(scope="module")
def seeded():
    variables = reference.init(CFG, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)
    return get_model("solar_open2_250b", **KWARGS), variables, tokens


def test_the_leaf_tree_is_the_written_one(seeded):
    model, variables, _ = seeded
    mine = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 16), jnp.int32)))
    params = mine["params"]
    assert sorted(params) == ["block_0", "block_1", "block_2", "block_3",
                              "embed", "final_norm", "head"]
    assert sorted(params["block_0"]["mixer"]) == GQA_LEAVES
    for i in (1, 2, 3):
        block = params[f"block_{i}"]
        assert sorted(block) == ["mixer", "mixer_norm", "moe", "moe_norm"]
        assert sorted(block["mixer"]) == KDA_LEAVES
        assert sorted(block["moe"]) == MOE_LEAVES
    kda = params["block_1"]["mixer"]
    assert kda["A_log"].shape == (2,) and kda["dt_bias"].shape == (16,)
    assert kda["f_b"]["kernel"].shape == (8, 16)
    assert sorted(kda["g_b"]) == ["bias", "kernel"]
    moe = params["block_1"]["moe"]
    # the held experts' matrices, the router over every expert
    assert moe["gate"].shape == (4, 32, 16) and moe["router"].shape == (32, 16)
    assert params["block_0"]["mixer"]["k"]["kernel"].shape == (32, 16)
    stats = mine["batch_stats"]
    assert {k: v["moe"]["router_bias"].shape for k, v in stats.items()} == {
        f"block_{i}": (16,) for i in range(4)}
    shapes = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert shapes(mine) == shapes(variables)


def test_loss_every_gradient_and_the_bias_update_are_the_references(seeded):
    """128 tokens: two chunks of 64 against 128 single tokens, 4 of 16
    experts held from the fifth, the bias moved after the step."""
    model, variables, tokens = seeded
    batch = {"tokens": tokens}

    def program(p):
        out, new = model.apply({"params": p,
                                "batch_stats": variables["batch_stats"]},
                               tokens, mutable=["batch_stats"])
        loss, metrics = causal_lm_loss_fn(out, batch, block_tokens=32)
        return loss, (metrics, new["batch_stats"])

    with jax.default_matmul_precision("highest"):
        (loss, (metrics, stats)), grads = jax.value_and_grad(
            program, has_aux=True)(variables["params"])
        (want, want_stats), want_grads = jax.value_and_grad(
            lambda p: reference.loss_fn(CFG, p, variables["batch_stats"],
                                        batch), has_aux=True)(
            variables["params"])
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    named = jax.tree_util.tree_flatten_with_path(grads)[0]
    # GQA 5, KDA 16 a layer, two norms a block, MoE 7 a layer, embedding,
    # final norm, head
    assert len(named) == 5 + 3 * 16 + 4 * 2 + 4 * 7 + 3 == 92
    for (path, got), ref in zip(named, jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(ref)) > 0, path
        assert apart(got, ref) < 5e-5, jax.tree_util.keystr(path)
    for got, ref in zip(jax.tree.leaves(stats), jax.tree.leaves(want_stats)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        assert float(jnp.max(jnp.abs(got))) == pytest.approx(1e-3)
    # the report: every pair a held expert computed, over the four layers,
    # and the buffer tiles the row passes visited, each layer's holding its
    # pairs and at most 127 rows more, of the 8 its buffer has
    pairs = int(metrics["count/moe_routed_pairs"])
    assert 0 < pairs <= 4 * 256 * 4
    assert 0 < int(metrics["count/moe_held_load_max"]) <= 256
    live = int(metrics["count/moe_live_tiles"])
    assert pairs <= 128 * live < pairs + 4 * 128
    assert live < int(metrics["count/moe_buffer_tiles"]) == 4 * 8


def test_a_step_outside_training_leaves_the_bias():
    model = get_model("solar_open2_250b", **KWARGS)
    tokens = jnp.zeros((1, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    _, new = model.apply(variables, tokens, train=False,
                         mutable=["batch_stats"])
    for leaf in jax.tree.leaves(new):
        assert float(jnp.max(jnp.abs(leaf))) == 0.0


def test_query_heads_share_their_kv_head_by_index():
    """4 query heads over 2 KV heads: heads 0, 1 read KV head 0 and heads
    2, 3 KV head 1. Two query heads given the same projection and reading
    the same KV head give the same output; across KV heads they differ."""
    from deep_vision_tpu.models.solar_open2 import GroupedQueryAttention

    layer = GroupedQueryAttention(4, 2, 8, gate=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    q = params["q"]["kernel"].reshape(32, 4, 8)
    q = q.at[:, 1].set(q[:, 0]).at[:, 2].set(q[:, 0])
    params = {**params, "q": {"kernel": q.reshape(32, 32)}}
    eye = {"o": {"kernel": jnp.eye(32)}}
    with jax.default_matmul_precision("highest"):
        o = layer.apply({"params": {**params, **eye}}, x).reshape(1, 16, 4, 8)
    np.testing.assert_allclose(o[:, :, 0], o[:, :, 1], rtol=1e-6)
    assert float(jnp.max(jnp.abs(o[:, :, 0] - o[:, :, 2]))) > 1e-3


def test_the_step_report_counts_the_buffer_tiles_the_passes_visit(tmp_path):
    """Two steps of 8 x 16 tokens through `train.py` with every choice held
    (all 16 experts on this chip): each of the four expert layers reports
    the buffer tiles its row passes visited and the tiles its buffer has
    (512 pairs, 4 tiles of 128), the Trainer folds both into
    `moe_live_tiles_total` and `moe_buffer_tiles_total`, and every tile is
    live: the share is 1."""
    import dataclasses

    from deep_vision_tpu import train_cli
    from deep_vision_tpu.configs import (CONFIG_REGISTRY, get_config,
                                         register_config)
    from deep_vision_tpu.obs.registry import get_registry

    def counter(name):
        return sum(m.value for m in get_registry().metrics()
                   if m.name == name and m.kind == "counter")

    register_config(dataclasses.replace(
        get_config("solar_open2_250b"), name="solar_open2_tiny",
        input_shape=(16,), batch_size=8,
        model_kwargs={**KWARGS, "n_routed_experts": 16, "held_offset": 0}))
    names = ("moe_live_tiles_total", "moe_buffer_tiles_total",
             "moe_routed_pairs_total")
    before = {name: counter(name) for name in names}
    try:
        assert train_cli.main([
            "-m", "solar_open2_tiny", "--fake-data", "--fake-batches", "2",
            "--epochs", "1", "--skip-preflight", "--ckpt-dir",
            str(tmp_path / "ck")]) == 0
    finally:
        del CONFIG_REGISTRY["solar_open2_tiny"]
    live, tiles, pairs = (counter(name) - before[name] for name in names)
    assert pairs == 2 * 4 * 8 * 16 * 4
    assert live == tiles == 2 * 4 * 4
