"""Olmo-Hybrid on the normal path, at a tiny size on the CPU: `train.py -m
olmo_hybrid_7b --fake-data` through `build_trainer` and `Trainer.fit`, the
counters it leaves, and the repairs to `train_cli` that a rank-1 integer
input asked for. The model against its plain reference:
`tests/benchmark/test_olmo_hybrid_cell.py`."""
import dataclasses

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
from deep_vision_tpu.models import get_model
from deep_vision_tpu.obs.registry import get_registry

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
