"""Olmo-Hybrid on the normal path, at a tiny size on the CPU: `train.py -m
olmo_hybrid_7b --fake-data` through `build_trainer` and `Trainer.fit`, the
counters it leaves, and the repairs to `train_cli` that a rank-1 integer
input asked for. The model against its plain reference:
`tests/benchmark/test_olmo_hybrid_cell.py`."""
import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu import train_cli
from deep_vision_tpu.configs import (
    CONFIG_REGISTRY,
    ExperimentConfig,
    get_config,
    register_config,
)
from deep_vision_tpu.models import get_model, olmo_hybrid as olmo
from deep_vision_tpu.nn.layers import RMSNorm
from deep_vision_tpu.obs.registry import get_registry
from deep_vision_tpu.ops.gated_delta import CHUNK, gated_delta_rule, short_conv

TINY = {"hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 2,
        "linear_num_heads": 2, "linear_key_head_dim": 8,
        "linear_value_head_dim": 16, "vocab_size": 64,
        "num_hidden_layers": 4}


def counter(name, **labels):
    for m in get_registry().metrics():
        if m.name == name and m.labels == labels:
            return m.value
    return 0.0


@pytest.fixture
def tiny_recipe():
    """The registered recipe with its widths overridden: what
    `train.py -m` resolves, at a size the CPU trains."""
    cfg = dataclasses.replace(
        get_config("olmo_hybrid_7b"), name="olmo_hybrid_tiny",
        input_shape=(16,), batch_size=8, model_kwargs=dict(TINY))
    register_config(cfg)
    yield cfg
    del CONFIG_REGISTRY["olmo_hybrid_tiny"]


def test_the_registered_recipe_is_the_published_model():
    cfg = get_config("olmo_hybrid_7b")
    assert (cfg.task, cfg.input_shape) == ("causal_lm", (2048,))
    model = get_model(cfg.model, **cfg.model_kwargs)
    assert (model.vocab_size, model.hidden_size, model.intermediate_size,
            model.num_attention_heads) == (100352, 3840, 11008, 30)
    assert (model.linear_num_heads, model.linear_key_head_dim,
            model.linear_value_head_dim, model.linear_conv_kernel_dim,
            model.linear_allow_neg_eigval, model.rms_norm_eps) == (
                30, 96, 192, 4, True, 1e-6)
    assert len(model.layer_types) == 32
    assert model.layer_types[:4] == ("linear_attention",) * 3 + (
        "full_attention",)
    # a cut keeps the first layers of the pattern
    assert get_model(cfg.model, num_hidden_layers=4).layer_types \
        == model.layer_types[:4]


def test_train_py_runs_two_steps_and_counts_them(tiny_recipe, tmp_path):
    tokens = counter("train_tokens_total")
    linear = counter("sequence_mixer_sites_total", kind="linear")
    full = counter("sequence_mixer_sites_total", kind="full")
    dense = counter("attention_sites_total", path="dense")
    assert train_cli.main([
        "-m", "olmo_hybrid_tiny", "--fake-data", "--fake-batches", "2",
        "--epochs", "1", "--skip-preflight", "--ckpt-dir",
        str(tmp_path / "ck")]) == 0
    # two batches of 8 rows x 16 tokens
    assert counter("train_tokens_total") - tokens == 2 * 8 * 16
    # counted while tracing: three linear mixers for one full, which on
    # the CPU is the dense causal expression
    new_linear = counter("sequence_mixer_sites_total", kind="linear") - linear
    new_full = counter("sequence_mixer_sites_total", kind="full") - full
    assert new_full >= 1 and new_linear == 3 * new_full
    assert counter("attention_sites_total", path="dense") - dense == new_full


def test_a_later_token_changes_no_earlier_hidden_state():
    """Causal in both kinds of mixer, the convolution included."""
    model = get_model("olmo_hybrid_7b", **TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)
    hidden = lambda t: model.apply(params, t)["hidden"]
    before, after = hidden(tokens), hidden(tokens.at[0, 9].add(1))
    np.testing.assert_allclose(after[:, :9], before[:, :9], atol=1e-6)
    assert float(jnp.max(jnp.abs(after[:, 9:] - before[:, 9:]))) > 1e-3


def test_a_masked_row_and_the_last_position_carry_no_loss():
    from deep_vision_tpu.losses.causal_lm import causal_lm_loss_fn, logits

    outputs = {"hidden": jax.random.normal(jax.random.PRNGKey(0), (3, 8, 4)),
               "head": jax.random.normal(jax.random.PRNGKey(1), (4, 10))}
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, 8), 0, 10)
    loss, metrics = causal_lm_loss_fn(
        outputs, {"tokens": tokens, "_mask": jnp.array([1.0, 1.0, 0.0])},
        block_tokens=4)
    logp = jax.nn.log_softmax(logits(outputs))
    nll = -jnp.take_along_axis(logp[:2, :-1], tokens[:2, 1:, None],
                               axis=-1)
    assert float(loss) == pytest.approx(float(jnp.mean(nll)), rel=1e-6)
    assert metrics["loss"] is loss


def test_train_cli_takes_a_rank_one_integer_input():
    cfg = get_config("olmo_hybrid_7b")
    assert train_cli.model_input_shape(cfg) == (2048,)
    assert train_cli.model_input(cfg) == ("tokens", np.int32)
    sample = train_cli.sample_input(dataclasses.replace(
        cfg, input_shape=(16,)))
    assert sample.shape == (2, 16) and sample.dtype == jnp.int32
    # the image tasks keep theirs, the s2d stem's layout included
    resnet = get_config("resnet50")
    assert train_cli.model_input_shape(resnet) == (112, 112, 12)
    assert train_cli.model_input(resnet) == ("image", np.float32)
    assert train_cli.sample_input(resnet).dtype == jnp.float32


@pytest.mark.parametrize("task,says", [
    ("dcgan", "GAN trainer"), ("segmentation", "unknown task")])
def test_build_trainer_names_what_is_wrong_with_a_task(task, says):
    cfg = ExperimentConfig(name="x", task=task, model="lenet5")
    with pytest.raises(ValueError, match=says):
        train_cli.build_trainer(cfg, lambda: [], None, steps_per_epoch=1)


class TokenSideRowMath(nn.Module):
    """`GatedDeltaNet` in the order it had before PR 39, from the public
    helpers: `unit()` token-major in float32 before the crossing, `o_norm`
    token-major after it (`gated_delta_rule` crosses both ways itself).
    The same names, so the same parameters apply."""

    num_heads: int
    key_dim: int
    value_dim: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim

        def mixed(name, width):
            y = olmo._dense(h * width, self.dtype, name)(x)
            kernel = self.param(name + "_conv", olmo.conv_init,
                                (4, h * width), jnp.float32)
            return nn.silu(short_conv(y, kernel)).reshape(b, t, h, width)

        def unit(y):
            return y * jax.lax.rsqrt(
                jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)

        q = unit(mixed("q", dk).astype(jnp.float32)) * dk ** -0.5
        k = unit(mixed("k", dk).astype(jnp.float32))
        v = mixed("v", dv)
        beta = 2.0 * jax.nn.sigmoid(olmo._dense(h, jnp.float32, "b")(x))
        a_log = self.param("A_log", olmo.a_log_init, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", olmo.dt_bias_init, (h,),
                             jnp.float32)
        g = -jnp.exp(a_log) * jax.nn.softplus(
            olmo._dense(h, jnp.float32, "a")(x) + dt_bias)
        o = gated_delta_rule(q, k, v, g, beta,
                             chunk=CHUNK if t % CHUNK == 0 else t,
                             mm_dtype=self.dtype or x.dtype)
        gate = olmo._dense(h * dv, self.dtype, "g")(x).reshape(b, t, h, dv)
        o = RMSNorm(1e-6, name="o_norm")(o).astype(gate.dtype) \
            * nn.silu(gate)
        return olmo._dense(x.shape[-1], self.dtype, "o")(
            o.reshape(b, t, h * dv))


def _two_orders(t, dtype):
    """-> (the layer, the parent's order, shared parameters, x, a
    cotangent), 2 rows of `t` tokens at the tiny widths."""
    heads, dk, dv = 2, 8, 16
    layer = olmo.GatedDeltaNet(heads, dk, dv, dtype=dtype)
    before = TokenSideRowMath(heads, dk, dv, dtype=dtype)
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    x = jax.random.normal(ks[0], (2, t, 32), dtype or jnp.float32)
    params = layer.init(ks[1], x)["params"]
    assert jax.tree.structure(before.init(ks[1], x)["params"]) \
        == jax.tree.structure(params)
    return layer, before, params, x, jax.random.normal(ks[2], x.shape)


@pytest.mark.parametrize("t", [128, 16], ids=["two_chunks_of_64",
                                              "one_chunk_of_16"])
def test_float32_layer_gives_the_token_side_orders_numbers(t):
    """Where the row math stands changes no value: `unit()` and `o_norm`
    work on one (token, head) row, on either side of the crossing. In
    float32 the two orders agree to rounding, output and every parameter's
    gradient: 1e-6 of the largest entry (a sum over rows may be taken in
    another order; nothing else may differ)."""
    layer, before, params, x, ct = _two_orders(t, None)

    def out_and_grads(module):
        def loss(p):
            out = module.apply({"params": p}, x)
            return jnp.sum(out * ct), out

        (_, out), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return out, grads

    (got, got_grads), (want, want_grads) = map(out_and_grads,
                                               (layer, before))
    assert got.dtype == want.dtype == jnp.float32
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) \
        <= 1e-6 * float(jnp.max(jnp.abs(b)))
    assert close(got, want)
    flat = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    assert len(flat) == 13
    for (path, b), a in zip(flat, jax.tree.leaves(got_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0, path
        assert close(a, b), jax.tree_util.keystr(path)


def test_bfloat16_layer_stays_within_an_ulp_of_the_token_side_order():
    """bf16 activations: q, k, v cross as bf16 and are widened on the
    chunk side, `o` is rounded before it crosses back. The same values
    rounded at the same points, so under `jit` the two orders' outputs
    part by at most one bf16 ulp (2^-8 of the largest entry): what XLA's
    excess precision across a convert pair may leave, and no more."""
    layer, before, params, x, _ = _two_orders(128, jnp.bfloat16)
    got, want = (jax.jit(lambda p, x, m=m: m.apply({"params": p}, x))(
        params, x) for m in (layer, before))
    assert got.dtype == want.dtype == jnp.bfloat16
    got, want = (y.astype(jnp.float32) for y in (got, want))
    assert float(jnp.max(jnp.abs(want))) > 0.01
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 2.0 ** -8 * float(jnp.max(jnp.abs(want)))


def test_the_parameter_tree_is_the_one_checkpoints_hold():
    """Name for name and shape for shape what the tree was before the
    crossing moved (PR 38's, written out): `o_norm` is the same `RMSNorm`
    under the same name, over the last axis on either side. A checkpoint
    of the parent restores."""
    model = get_model("olmo_hybrid_7b", **TINY)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    have = {jax.tree_util.keystr(k): v.shape for k, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    d, inter, h, dk, dv, vocab = 32, 48, 2, 8, 16, 64
    block = {"['mixer_norm']['scale']": (d,), "['mlp_norm']['scale']": (d,)}
    block.update({f"['mlp']['{n}']['kernel']": s for n, s in (
        ("gate", (d, inter)), ("up", (d, inter)), ("down", (inter, d)))})
    linear = {"['A_log']": (h,), "['dt_bias']": (h,),
              "['o_norm']['scale']": (dv,),
              "['q_conv']": (4, h * dk), "['k_conv']": (4, h * dk),
              "['v_conv']": (4, h * dv)}
    linear.update({f"['{n}']['kernel']": (d, w) for n, w in (
        ("q", h * dk), ("k", h * dk), ("v", h * dv), ("g", h * dv),
        ("a", h), ("b", h))})
    linear["['o']['kernel']"] = (h * dv, d)
    full = {f"['{n}']['kernel']": (d, d) for n in "qkvo"}
    full.update({"['q_norm']['scale']": (d,), "['k_norm']['scale']": (d,)})
    want = {"['embed']['embedding']": (vocab, d), "['head']": (d, vocab),
            "['final_norm']['scale']": (d,)}
    for i, mixer in enumerate((linear, linear, linear, full)):
        want.update({f"['block_{i}']{k}": v for k, v in block.items()})
        want.update({f"['block_{i}']['mixer']{k}": v
                     for k, v in mixer.items()})
    assert have == want
