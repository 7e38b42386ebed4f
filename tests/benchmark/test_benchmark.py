"""Tests of the benchmark's own yardstick (benchmark/), on the CPU.

The rehearsal cells (tests/benchmark/rehearsal.json) are tiny float32
models on the 8-device virtual mesh; they are never cells of
BENCHMARK.json and state no device number.
"""
import hashlib
import itertools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, flops, run, trace  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402
from benchmark.adapters import train as train_adapter  # noqa: E402
from benchmark.reference import resnet, steps, vit  # noqa: E402

REHEARSAL = os.path.join(ROOT, "tests", "benchmark", "rehearsal.json")
# the cells that wait outside BENCHMARK.json, a manifest of their own
WAITING = os.path.join(ROOT, "benchmark", "waiting.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def tiny_models():
    """The rehearsal configurations' models, registered as the program
    registers its own (width and depth cut; the code paths are the same)."""
    from deep_vision_tpu.models import register_model
    from deep_vision_tpu.models.resnet import ResNet
    from deep_vision_tpu.models.vit import ViT

    register_model("bench_tiny_resnet")(
        lambda num_classes=10, dtype=None, stem="s2d", **_: ResNet(
            stage_sizes=(1, 1), width=8, num_classes=num_classes, stem=stem,
            dtype=dtype))
    register_model("bench_tiny_vit")(
        lambda num_classes=10, dtype=None, **_: ViT(
            depth=2, dim=32, num_heads=2, patch=8, num_classes=num_classes,
            dtype=dtype))
    return {"resnet": ResNet(stage_sizes=(1, 1), width=8, num_classes=10,
                             stem="s2d"),
            "vit": ViT(depth=2, dim=32, num_heads=2, patch=8,
                       num_classes=10)}


def rehearsal_config(name):
    with open(os.path.join(ROOT, "tests", "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


# -- trace reduction ---------------------------------------------------------

def test_interval_union_counts_overlap_and_nesting_once():
    spans = [(0, 10), (5, 12), (6, 7), (20, 30), (30, 31), (40, 41)]
    assert trace.union_ns(spans) == 12 + 11 + 1
    assert trace.union_ns([]) == 0
    gaps = trace.gaps_ns(spans, 0, 50)
    assert [(s, e) for s, e, _ in gaps] == [(12, 20), (31, 40), (41, 50)]
    assert [before for _, _, before in gaps] == [1, 4, 5]


def test_idle_share_of_a_hand_made_device_plane():
    # an execution of the step that the session started inside, then three
    # whole ones, 100 ns apart; 60 ns of ops in each period (two
    # overlapping, one nested); a short other module between
    modules = [("step", 960, 10), ("step", 1000, 70), ("other", 1075, 5),
               ("step", 1100, 70), ("step", 1200, 70)]
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 960, 10)]
    for start in (1000, 1100, 1200):
        ops += [("%fusion.1 = f32[8] fusion(f32[8] %p)", start, 40),
                ("%copy.2 = f32[8] custom-call(f32[8] %q)", start + 30, 30),
                ("%nested = f32[] add()", start + 35, 5)]
    red = trace.reduce_device(modules, ops)
    assert red["step_module"] == "step" and red["periods"] == 2
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(120e-9)
    assert red["step_device_ms"] == pytest.approx(70e-6)
    assert red["op_s_per_step"]["fusion.1"] == pytest.approx(40e-9)
    assert red["gap_s_per_step"] == {"after:copy.2": pytest.approx(40e-9)}
    assert trace.reduce_device([("step", 0, 5)], ops) is None
    assert trace.reduce_device(modules[:3], ops) is None  # no whole period


def plane_of(first_start, first_length, whole, period=1000, length=990):
    """A device plane whose trace begins with one execution of the step at
    `first_start`, `first_length` long, then `whole` more, back to back but
    for the 10 ns between two."""
    modules = [("step", first_start, first_length)] + [
        ("step", period * (k + 1), length) for k in range(whole)]
    return modules, [("%fusion.1 = f32[8] fusion()", s, d)
                     for _, s, d in modules]


@pytest.mark.parametrize("first_start, first_length", [
    (700, 290),  # the session started inside it: its start is the session's
    (0, 990),    # a whole one
])
def test_the_slice_holds_whole_periods_only(first_start, first_length):
    red = trace.reduce_device(*plane_of(first_start, first_length, whole=20))
    assert red["periods"] == 19
    assert red["window_s"] / red["periods"] == pytest.approx(1000e-9)
    assert red["busy_s"] / red["periods"] == pytest.approx(990e-9)
    assert red["step_device_ms"] == pytest.approx(990e-6)
    # one period fewer, the same wall and busy time per period
    fewer = trace.reduce_device(*plane_of(first_start, first_length, whole=19))
    assert fewer["periods"] == 18
    assert fewer["window_s"] / 18 == pytest.approx(red["window_s"] / 19)
    assert fewer["gap_s_per_step"] == pytest.approx(red["gap_s_per_step"])


# -- FLOP count --------------------------------------------------------------

def test_flops_of_one_conv_and_one_dot_general():
    x = jax.ShapeDtypeStruct((2, 8, 8, 3), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, 3, 16), jnp.float32)
    conv = lambda x, w: jax.lax.conv_general_dilated(
        x, w, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert flops.flops_of(conv, x, w) == 2 * (2 * 4 * 4 * 16) * (3 * 3 * 3)
    a = jax.ShapeDtypeStruct((4, 5, 6), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 6, 7), jnp.float32)
    assert flops.flops_of(lambda a, b: jnp.einsum("bij,bjk->bik", a, b),
                          a, b) == 2 * 4 * 5 * 7 * 6
    # the backward pass of the strided conv: its input gradient multiplies
    # no inserted zeros, so forward + 2 backward = 3 x forward
    both = lambda x, w: jax.grad(lambda x, w: conv(x, w).sum(),
                                 argnums=(0, 1))(x, w)
    assert flops.flops_of(both, x, w) == 3 * flops.flops_of(conv, x, w)


def test_flops_of_resnet50_per_image():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "resnet50.json")) as f:
        config = json.load(f)
    traffic = {"kind": "resident_pool", "global_batch": 2, "pool_batches": 1}
    per_image = flops.train_step_flops(resnet, config, traffic_mod.batch_spec(
        traffic, config, (112, 112, 12))) / 2
    assert per_image == pytest.approx(24.6e9, rel=0.05)
    assert per_image == 24299077632.0  # PR 25's count, to the digit


def test_flops_of_vit_b16_per_image():
    with open(os.path.join(ROOT, "benchmark", "configs", "vit_b16.json")) as f:
        config = json.load(f)
    traffic = {"kind": "resident_pool", "global_batch": 2, "pool_batches": 1}
    per_image = flops.train_step_flops(vit, config, traffic_mod.batch_spec(
        traffic, config, (224, 224, 3))) / 2
    assert per_image == 104598687744.0  # PR 25's count, to the digit


class _Plain:
    """A reference of one matmul, counted by its jaxpr: the product, and
    its gradient to the weights (none to the batch)."""
    BATCH_COUPLED = False

    @staticmethod
    def init(cfg, key):
        return {"params": {"w": jnp.ones((cfg["dim"], cfg["dim"]))},
                "batch_stats": {}}

    @staticmethod
    def loss_fn(cfg, params, batch_stats, batch, q=lambda x: x):
        return jnp.sum(batch["x"] @ params["w"]), batch_stats


class _Written(_Plain):
    """The same with its count written down: a quarter of the jaxpr's, as
    where only a quarter of the experts held are routed to."""

    @staticmethod
    def step_flops(cfg, batch_spec):
        rows, dim = batch_spec["x"].shape
        return rows * dim * dim


def test_a_written_count_is_the_count_and_the_jaxpr_stands_in_for_none():
    cfg = {"dim": 8}
    spec = {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32)}
    assert flops.train_step_flops(_Plain, cfg, spec) == 2 * (2 * 4 * 8 * 8)
    assert flops.train_step_flops(_Written, cfg, spec) == 4 * 8 * 8


def benchmark_config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_flops_of_vit_b16_1024px_are_the_mathematics_not_the_recomputation():
    """`reference_remat` puts every block's forward into the jaxpr of
    `value_and_grad` twice; the count is taken with it off, and is three
    times the forward's, as the closed form has it."""
    config = benchmark_config("vit_b16_1024px")
    assert config["reference_remat"] is True
    traffic = {"kind": "resident_pool", "global_batch": 1, "pool_batches": 1}
    spec = traffic_mod.batch_spec(traffic, config, (1024, 1024, 3))
    counted = flops.train_step_flops(vit, config, spec)
    without = {k: v for k, v in config.items() if k != "reference_remat"}
    assert counted == flops.train_step_flops(vit, without, spec)
    variables = jax.eval_shape(lambda: vit.init(config, jax.random.PRNGKey(0)))
    forward = flops.flops_of(
        lambda p, b: vit.loss_fn(without, p, {}, b)[0], variables["params"],
        spec)
    # no gradient flows to the image: the patch embedding has one backward
    # product, every other matmul two
    t, d, patch_in = 4096, 768, 16 * 16 * 3
    embed = 2.0 * t * patch_in * d
    assert counted == 3 * forward - embed
    per_token = 2.0 * (4 * d * d + 2 * 4 * d * d)  # qkv, out, the MLP's two
    closed = 3 * (12 * (t * per_token + 4.0 * t * t * d) + 2.0 * d * 1000) \
        + 2 * embed
    assert counted == closed
    # the reference as the cell runs it does hold the blocks' forward twice
    # (but for each block's last product, which no gradient needs again)
    remat = flops.flops_of(
        lambda p, b: jax.value_and_grad(
            lambda p: vit.loss_fn(config, p, {}, b)[0])(p),
        variables["params"], spec)
    assert remat > counted + 0.8 * forward


@pytest.mark.parametrize("batch,tokens,heads,head_dim", [(2, 16, 3, 8),
                                                         (1, 64, 2, 16)])
def test_attention_flops_are_the_six_products_of_the_plain_form(
        batch, tokens, heads, head_dim):
    """The written count against the jaxpr's, of the reference's two score
    einsums and the softmax between them under `value_and_grad`."""
    def attention(q, k, v):
        s = jnp.einsum("bthk,bshk->bhts", q, k) * head_dim ** -0.5
        return jnp.sum(jnp.einsum("bhts,bshk->bthk",
                                  jax.nn.softmax(s, axis=-1), v))

    x = jax.ShapeDtypeStruct((batch, tokens, heads, head_dim), jnp.float32)
    counted = flops.flops_of(jax.value_and_grad(attention, argnums=(0, 1, 2)),
                             x, x, x)
    assert counted == flops.attention_flops(batch, tokens, heads, head_dim,
                                            depth=1)
    assert flops.attention_flops(batch, tokens, heads, head_dim, depth=5) \
        == 5 * counted
    assert flops.attention_bytes(batch, tokens, heads, head_dim, 5, 2) \
        == 5 * 8 * x.size * 2


def roofline_record(ops, cell=None, trace=True):
    config = {"input_shape": [64, 32, 3], "patch": 8, "num_heads": 2,
              "dim": 32, "depth": 3, "compute_dtype": "bfloat16"}
    return {"trace": {"op_s_per_step": ops} if trace else None,
            "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
            "config": config, "global_batch": 8, "chips": 4,
            "cell": {"attention_kernel_ops": ["flash_fwd", "flash_bwd_dq",
                                              "flash_bwd_dkv"]}
            if cell is None else cell}


def test_flash_roofline_reads_the_hand_worked_share_or_nothing():
    reader = run.load_py(os.path.join(ROOT, "benchmark", "metrics",
                                      "flash_roofline.py"))
    ops = {"flash_fwd.1": 1e-3, "flash_fwd.12": 2e-3, "flash_bwd_dq": 3e-3,
           "flash_bwd_dkv.7": 4e-3,
           # not the kernels: another op, and one that only shares letters
           "fusion.3": 5.0, "flash_fwd_other.2": 7.0, "flash_bwd_dqx": 9.0}
    # 2 rows a chip, 8 x 4 = 32 tokens, 2 heads of 16, 3 blocks:
    # 3 * 2 * 2 * 12 * 32^2 * 16 = 2,359,296 FLOP at 1e9/s = 2.359296 ms,
    # over 10 ms of kernels; the bytes (3 * 8 * 2 * 32 * 32 * 2 = 98,304 at
    # 1e9/s) are the lower roof
    assert reader.read(roofline_record(ops)) == pytest.approx(23.59296)
    # a short sequence is held to the memory roof: with FLOPs a thousand
    # times cheaper the bytes' 0.098304 ms over 10 ms
    cheap = roofline_record(ops)
    cheap["peaks"]["bf16_flops_per_s"] = 1e12
    assert reader.read(cheap) == pytest.approx(0.98304)
    # nothing to read: no trace, no peaks, no kernel op in the step (the
    # dense fall-back), a cell that names no ops
    assert reader.read(roofline_record(ops, trace=False)) is None
    assert reader.read({**roofline_record(ops), "peaks": None}) is None
    assert reader.read(roofline_record({"fusion.3": 5.0})) is None
    assert reader.read(roofline_record(ops, cell={})) is None


# -- the feed ----------------------------------------------------------------

def test_resident_pool_is_the_arrays_of_the_first_generator():
    """The hash is of PR 25's `make_pool` (commit 268d362) at these sizes
    and this seed: the same bits, so every limit set on them stands."""
    traffic = {"kind": "resident_pool", "global_batch": 4, "pool_batches": 3}
    config = {"num_classes": 10}
    pool = traffic_mod.make_pool(traffic, config, (8, 8, 3), 2 ** 31 + 11)
    digest = hashlib.sha256()
    for batch in pool:
        assert list(batch) == ["image", "label"]
        digest.update(batch["image"].tobytes())
        digest.update(batch["label"].tobytes())
    assert digest.hexdigest() == ("6ae0fe05ac6ea03f30d40de2f66bff10"
                                  "c517e4adc9c8fdadd188e0af260a2027")
    spec = traffic_mod.batch_spec(traffic, config, (8, 8, 3))
    assert {k: (v.shape, v.dtype) for k, v in spec.items()} == {
        k: (v.shape, v.dtype) for k, v in pool[0].items()}


TOKEN_TRAFFIC = {"kind": "token_pool", "global_batch": 8, "seq_len": 12,
                 "pool_batches": 3}
TOKEN_CONFIG = {"vocab_size": 32, "dim": 16, "compute_dtype": "float32",
                "optimizer": {"name": "adamw", "learning_rate": 1e-2,
                              "weight_decay": 1e-4},
                "schedule": None, "steps_per_epoch": 100}


def test_token_pool_gives_ids_below_the_vocabulary_from_the_seed():
    pool = traffic_mod.make_pool(TOKEN_TRAFFIC, TOKEN_CONFIG, None,
                                 2 ** 31 + 5)
    assert len(pool) == 3
    for batch in pool:
        assert list(batch) == ["tokens"]
        tokens = batch["tokens"]
        assert tokens.shape == (8, 12) and tokens.dtype == np.int32
        assert tokens.min() >= 0 and tokens.max() < 32
    assert len({b["tokens"].tobytes() for b in pool}) == 3  # all differ
    assert pool[0]["tokens"].max() > 16  # the whole range, not a part
    again = traffic_mod.make_pool(TOKEN_TRAFFIC, TOKEN_CONFIG, None,
                                  2 ** 31 + 5)
    other = traffic_mod.make_pool(TOKEN_TRAFFIC, TOKEN_CONFIG, None,
                                  2 ** 31 + 6)
    for a, b, c in zip(pool, again, other):
        assert np.array_equal(a["tokens"], b["tokens"])
        assert not np.array_equal(a["tokens"], c["tokens"])
    spec = traffic_mod.batch_spec(TOKEN_TRAFFIC, TOKEN_CONFIG, None)
    assert spec["tokens"].shape == (8, 12)
    assert spec["tokens"].dtype == np.int32
    with pytest.raises(ValueError, match="not a training feed"):
        traffic_mod.make_pool({**TOKEN_TRAFFIC, "kind": "open_loop"},
                              TOKEN_CONFIG, None, 1)


class _ToyTokens:
    """A token-sequence reference: embedding, one matmul, and the mean
    loss of every position's next token."""
    BATCH_COUPLED = False

    @staticmethod
    def init(cfg, key):
        k1, k2 = jax.random.split(key)
        v, d = cfg["vocab_size"], cfg["dim"]
        return {"params": {"embed": jax.random.normal(k1, (v, d)),
                           "out": jax.random.normal(k2, (d, v)) * d ** -0.5},
                "batch_stats": {}}

    @staticmethod
    def loss_fn(cfg, params, batch_stats, batch, q=lambda x: x):
        tokens = batch["tokens"]
        x = params["embed"][tokens[:, :-1]]
        logp = jax.nn.log_softmax(q(x) @ q(params["out"]))
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll), batch_stats


def toy_variables():
    """Made anew for every call of `run_steps`, which consumes them."""
    return _ToyTokens.init(TOKEN_CONFIG, jax.random.PRNGKey(7))


@pytest.mark.parametrize("how", ["whole", "row_blocks", "rows_halved"])
def test_run_steps_follows_a_token_reference_through_its_batch_dict(how):
    pool = traffic_mod.make_pool(TOKEN_TRAFFIC, TOKEN_CONFIG, None, 7)
    plain = steps.run_steps(_ToyTokens, TOKEN_CONFIG, toy_variables(), pool)
    assert len(plain["losses"]) == 3 and np.all(np.isfinite(plain["losses"]))
    assert plain["losses"][2] < plain["losses"][0]  # it does learn
    if how == "whole":  # the first step is the loss and gradient themselves
        with jax.default_matmul_precision("highest"):
            (loss, _), grad = jax.value_and_grad(
                lambda p: _ToyTokens.loss_fn(TOKEN_CONFIG, p, {}, pool[0]),
                has_aux=True)(toy_variables()["params"])
        got, want = plain, {"losses": [float(loss)], "grad": grad}
    elif how == "row_blocks":  # the mean over two equal blocks of rows
        got = steps.run_steps(_ToyTokens, TOKEN_CONFIG, toy_variables(), pool,
                              row_blocks=2)
        want = plain
    else:  # every array of the batch cut to its first rows
        got = steps.run_steps(_ToyTokens, TOKEN_CONFIG, toy_variables(), pool,
                              rows=4)
        want = steps.run_steps(
            _ToyTokens, TOKEN_CONFIG, toy_variables(),
            [{k: v[:4] for k, v in batch.items()} for batch in pool])
        assert got["losses"][0] != pytest.approx(plain["losses"][0], rel=1e-4)
    assert got["losses"][:len(want["losses"])] == pytest.approx(
        want["losses"], rel=1e-5)
    for key in set(want) - {"losses"}:
        for a, b in zip(jax.tree.leaves(got[key]), jax.tree.leaves(want[key])):
            assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
                jnp.linalg.norm(b))


# -- the budget: the training state and one float32 tree of the parameters ---

def parents_run_steps(module, cfg, variables, batches, control=False,
                      rows=None, row_blocks=1):
    """`steps.run_steps` as PR 28 had it, kept as the oracle of the loop
    that consumes its state: it donates nothing, and keeps the first
    parameters and the first gradient on the device through every step.
    The optimizer's arithmetic is `steps`' own."""
    q = steps.CONTROL_BELOW[cfg["compute_dtype"]] if control else (
        lambda x: x)

    def grad_block(params, stats, batch):
        (loss, new_stats), grads = jax.value_and_grad(
            lambda p: module.loss_fn(cfg, p, stats, batch, q),
            has_aux=True)(params)
        return loss, new_stats, grads

    @jax.jit
    def step(params, stats, opt, batch, lr, count):
        if rows is not None:
            batch = jax.tree.map(lambda x: x[:rows], batch)
        if row_blocks == 1:
            loss, new_stats, grads = grad_block(params, stats, batch)
        else:
            def body(acc, block):
                l, s, g = grad_block(params, stats, block)
                return jax.tree.map(lambda a, b: a + b / row_blocks,
                                    acc, (l, g)), s
            split = lambda x: x.reshape(row_blocks, -1, *x.shape[1:])
            zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
            (loss, grads), new_stats = jax.lax.scan(
                body, zero, jax.tree.map(split, batch))
            new_stats = jax.tree.map(lambda x: x[-1], new_stats)
        new_params, new_opt = steps._opt_update(cfg, params, grads, opt, lr,
                                                count)
        return new_params, new_stats, new_opt, loss, grads

    params, stats = variables["params"], variables["batch_stats"]
    first, opt = params, steps._opt_init(cfg, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for count, batch in enumerate(batches):
            params, stats, opt, loss, grads = step(
                params, stats, opt, batch,
                jnp.float32(steps.learning_rate(cfg, count)),
                jnp.float32(count))
            losses.append(float(loss))
            if count == 0:
                first_grad = grads
    return {"losses": losses, "grad": first_grad,
            "delta": jax.tree.map(lambda a, b: a - b, params, first)}


BF16_STATE = {**TOKEN_CONFIG, "optimizer_state_dtype": "bfloat16"}
SGD = {**TOKEN_CONFIG, "optimizer": {"name": "sgd", "learning_rate": 0.1,
                                     "momentum": 0.9, "weight_decay": 1e-4}}


@pytest.mark.parametrize("cfg", [TOKEN_CONFIG, BF16_STATE, SGD],
                         ids=["adamw", "adamw_bf16_state", "sgd"])
@pytest.mark.parametrize("how", [{}, {"row_blocks": 2}, {"rows": 4},
                                 {"control": True}],
                         ids=["whole", "row_blocks", "rows_halved", "control"])
def test_the_loop_that_consumes_its_state_reads_what_the_parents_read(cfg,
                                                                      how):
    pool = traffic_mod.make_pool(TOKEN_TRAFFIC, cfg, None, 7)
    want = parents_run_steps(_ToyTokens, cfg, toy_variables(), pool, **how)
    handed = toy_variables()
    got = steps.run_steps(_ToyTokens, cfg, handed, pool, **how)
    assert got["losses"] == want["losses"]  # to the bit, all of it
    for key in ("grad", "delta"):
        assert jax.tree.structure(got[key]) == jax.tree.structure(want[key])
        for a, b in zip(jax.tree.leaves(got[key]), jax.tree.leaves(want[key])):
            assert isinstance(a, jax.Array) and a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b)), (key, how)
    assert all(x.is_deleted() for x in jax.tree.leaves(handed))


def tree_bytes(tree):
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def alive_now():
    return {id(x) for x in jax.live_arrays()}


def alive_since(before):
    return [x for x in jax.live_arrays() if id(x) not in before]


class _Probed:
    """A feed that reads, as each batch is asked for, the bytes of the
    arrays then alive that were not alive when it was made."""

    def __init__(self, batches):
        self.batches, self.readings = batches, []
        self.before = alive_now()

    def __iter__(self):
        for batch in self.batches:
            self.readings.append(tree_bytes(alive_since(self.before)))
            yield batch


@pytest.mark.parametrize("cfg", [TOKEN_CONFIG, BF16_STATE],
                         ids=["float32_state", "bfloat16_state"])
def test_between_steps_the_device_holds_the_training_state_and_no_more(cfg):
    """Before steps 2 and 3 the loop keeps the parameters, the optimizer's
    state as stored and the batches; the parent's kept the first
    parameters and the first gradient beside them, two trees more."""
    pool = [jax.device_put(b) for b in
            traffic_mod.make_pool(TOKEN_TRAFFIC, cfg, None, 7)]
    params = toy_variables()["params"]
    state = tree_bytes(params) + tree_bytes(steps._opt_init(cfg, params))
    assert state == tree_bytes(params) * (
        2 if "optimizer_state_dtype" in cfg else 3)
    del params
    readings = {}
    for name, loop in (("change", steps.run_steps),
                       ("parent", parents_run_steps)):
        feed = _Probed(pool)
        out = loop(_ToyTokens, cfg, toy_variables(), feed)
        readings[name] = feed.readings[1:]
        del out
    assert all(r <= state + 1024 for r in readings["change"]), readings
    assert all(r >= state + 2 * tree_bytes(toy_variables()["params"])
               for r in readings["parent"]), readings


class _AdamState(tuple):
    _fields = ("count", "mu", "nu")
    mu = property(lambda self: self[1])


class _TraceState(tuple):
    _fields = ("trace",)
    trace = property(lambda self: self[0])


@pytest.mark.parametrize("name,stored", [
    ("sgd", "float32"), ("adamw", "float32"), ("adamw", "bfloat16")])
def test_first_gradient_norms_are_the_norms_of_the_tree_never_made(name,
                                                                   stored):
    """Against `compare.leaf_norms` of the float32 gradient tree that the
    parent's reading made on the device only to take its norms."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    shapes = {"a": (64, 48), "b": {"bias": (48,), "kernel": (3, 3, 8, 16)}}
    draw = lambda key, scale: jax.tree.map(
        lambda s: scale * jax.random.normal(key, s), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    params0 = jax.device_get(draw(keys[0], 1.0))
    moment = jax.tree.map(lambda x: x.astype(stored), draw(keys[1], 1e-2))
    config = {"optimizer": {"name": name, "weight_decay": 1e-2, "b1": 0.8}}
    if name == "sgd":
        state = (_TraceState((moment,)),)
        tree = jax.tree.map(lambda t, p: t.astype("float32") - 1e-2 * p,
                            moment, params0)
    else:
        state = (_AdamState((0, moment, None)), ())
        tree = jax.tree.map(lambda m: m.astype("float32") / (1 - 0.8), moment)
    got = train_adapter.first_gradient_norms(config, state, params0)
    assert got.dtype == np.float64 and got.shape == (3,)
    assert got == pytest.approx(compare.leaf_norms(tree), rel=1e-6)


# -- the plain references against the program's models -----------------------

@pytest.mark.parametrize("family,module,config_name,image_shape", [
    ("resnet", resnet, "tiny_resnet", (16, 16, 12)),
    ("vit", vit, "tiny_vit", (32, 32, 3)),
])
def test_reference_matches_the_programs_model(tiny_models, family, module,
                                              config_name, image_shape):
    from deep_vision_tpu.losses import classification_loss_fn

    # every BN scale 1 here, so that every leaf has a gradient to compare
    config = {**rehearsal_config(config_name), "tail_bn_scale": 1.0}
    model = tiny_models[family]
    variables = module.init(config, jax.random.PRNGKey(3))
    rng = np.random.RandomState(0)
    x = rng.rand(8, *image_shape).astype(np.float32)
    y = rng.randint(0, 10, (8,)).astype(np.int32)
    stats = variables["batch_stats"]

    def program_loss(params):
        out = model.apply({"params": params, **({"batch_stats": stats}
                                                if stats else {})},
                          x, train=True,
                          mutable=["batch_stats"] if stats else False)
        logits = out[0] if stats else out
        return classification_loss_fn(logits, {"label": y})[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(program_loss))(
            variables["params"])
        (lr, _), gr = jax.jit(jax.value_and_grad(
            lambda p: module.loss_fn(config, p, stats,
                                     {"image": x, "label": y}),
            has_aux=True))(variables["params"])
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * float(
            jnp.linalg.norm(b)) + 1e-7


def test_a_recomputed_block_changes_no_mathematics():
    """`"reference_remat": true` against the same configuration without
    it: the loss and every leaf of the gradient, and through `run_steps`
    (one image a row block) each step's loss, the first gradient and the
    parameters' change."""
    config = rehearsal_config("tiny_vit_tokens")
    plain = {k: v for k, v in config.items() if k != "reference_remat"}
    rng = np.random.RandomState(0)
    batch = {"image": rng.rand(8, 64, 64, 3).astype(np.float32),
             "label": rng.randint(0, 10, (8,)).astype(np.int32)}
    params = vit.init(config, jax.random.PRNGKey(3))["params"]
    grad = lambda cfg: jax.jit(jax.value_and_grad(
        lambda p: vit.loss_fn(cfg, p, {}, batch)[0]))(params)
    with jax.default_matmul_precision("highest"):
        (lr, gr), (lp, gp) = grad(config), grad(plain)
    assert float(lr) == pytest.approx(float(lp), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gr)[0],
                            jax.tree.leaves(gp)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(
            jnp.linalg.norm(b)) + 1e-9, jax.tree_util.keystr(path)
    # the jaxprs differ: the recomputing one holds its blocks under remat
    text = lambda cfg: str(jax.make_jaxpr(jax.grad(
        lambda p: vit.loss_fn(cfg, p, {}, batch)[0]))(params))
    assert ("checkpoint" in text(config) or "remat" in text(config))
    assert "checkpoint" not in text(plain) and "remat" not in text(plain)
    follow = lambda cfg: steps.run_steps(
        vit, cfg, vit.init(cfg, jax.random.PRNGKey(3)), [batch] * 3,
        row_blocks=cfg["reference_row_blocks"])
    got, want = follow(config), follow(plain)
    assert got["losses"] == pytest.approx(want["losses"], rel=1e-6)
    for key in ("grad", "delta"):
        for a, b in zip(jax.tree.leaves(got[key]),
                        jax.tree.leaves(want[key])):
            assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
                jnp.linalg.norm(b)) + 1e-9, key


def test_a_fault_that_keeps_fewer_rows_than_row_blocks_keeps_whole_rows():
    """Half of a batch of 8 left out under `reference_row_blocks` 8: four
    blocks of one row, and the reading is the reference's over those four
    rows alone."""
    config = rehearsal_config("tiny_vit_tokens")
    _, _, traffic = run.resolve(run.load_manifest(REHEARSAL),
                                "tiny_vit_tokens_train")
    pool = traffic_mod.make_pool(traffic, config, (64, 64, 3), 5)
    devices = jax.devices()[:1]
    halved = train_adapter.reference_steps(config, pool, 5, devices, rows=4)
    cut = [{k: v[:4] for k, v in batch.items()} for batch in pool]
    want = train_adapter.reference_steps(
        {**config, "reference_row_blocks": 1}, cut, 5, devices)
    whole = train_adapter.reference_steps(config, pool, 5, devices)
    assert halved["losses"] == pytest.approx(want["losses"], rel=1e-5)
    assert halved["losses"][0] != pytest.approx(whole["losses"][0], rel=1e-4)


# -- the manifest ------------------------------------------------------------

@pytest.mark.parametrize("path", [os.path.join(ROOT, "BENCHMARK.json"),
                                  REHEARSAL, WAITING])
def test_manifest_is_consistent(path):
    m = run.load_manifest(path)
    configs = {c["name"]: c for c in m["configs"]}
    cells = {c["name"]: c for c in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert len(cells) == len(m["workloads"]) and "setup_s" in e2e
    assert len({(c["config"], c["traffic"]) for c in cells.values()}) \
        == len(cells)
    for entry in configs.values():
        assert os.path.exists(os.path.join(ROOT, entry["file"]))
        assert any(c["config"] == entry["name"] for c in cells.values())
    if path != REHEARSAL:
        four = [c for c in cells.values() if c["chips"] == 4]
        assert all(c["chips"] in (1, 4) for c in cells.values())
        assert len(four) <= max(1, len(cells) // 4)
    for cell in cells.values():
        assert cell["config"] in configs and len(cell["why"]) <= 200
        resolved, _, traffic = run.resolve(m, cell["name"])  # files parse
        # every number compared has a limit of the cell's own, and is held
        assert set(resolved["limits"]) == set(compare.NUMBERS)
        assert all(0 < v < 1 for v in resolved["limits"].values())
        assert "limits" not in traffic
    for metric in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert set(metric.get("workloads", cells)) <= set(cells)
        run.find_file(m, "metrics", metric["name"] + ".py")
    for metric in m["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
    for metric in m["per_layer"]:
        assert metric["moves"] in e2e
        moved = e2e[metric["moves"]]
        for name in metric.get("workloads", cells):
            assert run.applies(moved, name)
    for name in list(configs) + list(cells) + [c["traffic"]
                                               for c in cells.values()]:
        assert NAME.match(name)


def test_a_waiting_cell_is_no_cell_of_the_benchmark_and_is_ready_to_enter():
    """`benchmark/waiting.json` runs its cells through the same harness
    (`--manifest`); what it holds beside them is BENCHMARK.json's own, so
    that admitting a cell is moving its entries over and nothing else."""
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    waiting = run.load_manifest(WAITING)
    names = lambda m, group: [x["name"] for x in m[group]]
    for group in ("configs", "workloads"):
        assert not set(names(real, group)) & set(names(waiting, group))
    assert "flash_roofline" not in names(real, "per_layer")
    for key in ("paths", "run_seconds", "end_to_end"):
        assert waiting[key] == real[key]
    own = [x for x in waiting["per_layer"] if x not in real["per_layer"]]
    assert names({"own": own}, "own") == ["flash_roofline"]
    assert [x for x in waiting["per_layer"] if x not in own] \
        == real["per_layer"]
    assert waiting["command"][-2:] == [
        "--manifest", os.path.relpath(WAITING, ROOT)]


@pytest.mark.parametrize("path,own", [
    (WAITING, "vit_b16_train_1024px"),
    (REHEARSAL, "tiny_vit_tokens_train")])
def test_flash_roofline_is_read_in_its_own_cell_and_no_other(path, own):
    m = run.load_manifest(path)
    metric = {x["name"]: x for x in m["per_layer"]}["flash_roofline"]
    assert metric["layer"] == "kernels" and metric["unit"] == "%"
    read_in = [c["name"] for c in m["workloads"]
               if run.applies(metric, c["name"])]
    assert read_in == [own]
    # its cell's file names the ops, and its configuration the recomputation
    cell, config, traffic = run.resolve(m, own)
    assert cell["attention_kernel_ops"] == ["flash_fwd", "flash_bwd_dq",
                                            "flash_bwd_dkv"]
    assert config["reference_remat"] is True
    assert config["reference_row_blocks"] == traffic["global_batch"] == 8
    # the other cells' references are the parent's: no recomputation
    for c in m["workloads"]:
        if c["name"] != own:
            assert "reference_remat" not in run.resolve(m, c["name"])[1]


def test_run_refuses_a_real_cell_without_the_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "resnet50_train_b128", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert done.returncode == run.NO_CHIP
    assert "{" not in done.stdout  # no result line


# -- a whole run, rehearsed; and the same with the timed path broken ---------

def rehearse(cell):
    return run.run_cell(run.load_manifest(REHEARSAL), cell, 2 ** 31 + 11,
                        0.3, 0, require_chip=False)


@pytest.mark.parametrize("cell", ["tiny_resnet_train", "tiny_vit_train",
                                  "tiny_vit_tokens_train"])
def test_rehearsal_run_is_correct(tiny_models, cell):
    result = rehearse(cell)
    assert result["correct"], (result["compared"], result["faults"])
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"img_per_s_chip", "step_ms_p95",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    # whether the window stalled, from every step interval of it
    window = result["window"]
    assert window["steps"] == result["attempted"]
    assert 0 < window["interval_median_ms"] <= window["interval_max_ms"]
    assert window["longest"][0][1] == window["interval_max_ms"]
    assert all(0 <= step < window["steps"] for step, _ in window["longest"])
    assert 0 <= window["intervals_over_3x_median"] < window["steps"]
    # both peak readings, before the reference and after the comparison
    device = result["device"]
    assert device["memory_peak_bytes_after"] >= device["memory_peak_bytes"]


def test_the_distance_sees_a_leaf_whose_norm_is_right_and_elements_not():
    """`delta_distance`, which `readings.py` reads beside the numbers
    compared: two leaves of four elements, one of them rotated, so that
    its norm is the reference's and its elements are not; and an element
    under the rule on the reference's gradient counts for nothing."""
    ref = {"a": jnp.array([3.0, 0.0, 4.0, 9.0]),
           "b": jnp.array([1.0, 2.0, 2.0, 0.0])}
    got = {"a": jnp.array([0.0, 3.0, 4.0, -9.0]), "b": ref["b"]}
    # every gradient element 1, but `a`'s last: under a thousandth
    grad = {"a": jnp.array([1.0, 1.0, 1.0, 1e-6]), "b": jnp.ones(4)}
    program = {"losses": [1.0], "delta": got,
               "grad_norms": compare.leaf_norms(grad)}
    reference = {"losses": [1.0], "grad": grad, "delta": ref}
    values = compare.gaps(program, reference, True, distance=True)
    assert values["delta_gap"] == 0.0
    # |(3, -3, 0)| over |(3, 0, 4)|
    assert values["delta_distance"] == pytest.approx(18 ** 0.5 / 5)
    assert values["worst_leaves"]["delta_distance"] == "['a']"
    assert "delta_distance" not in compare.gaps(program, reference, True)
    # no run is judged by it
    limits = {"loss_gap": 1e-4, "grad_gap": 1e-3, "delta_gap": 1e-3}
    assert compare.judge(values, limits)[0]
    # without the rule (an optimizer that does not normalise) all count
    assert compare.gaps(program, reference, False, distance=True)[
        "delta_distance"] == pytest.approx((18 + 18 ** 2) ** 0.5
                                           / (25 + 81) ** 0.5)


def test_the_window_summary_names_the_stall():
    """A window of 40 steps of 100 ms with one gap of 2.1 s before step 17
    and one slow step under three times the median."""
    intervals = [0.1] * 40
    intervals[17], intervals[30] = 2.1, 0.25
    assert train_adapter.window_summary(intervals) == {
        "steps": 40, "interval_median_ms": pytest.approx(100.0),
        "interval_max_ms": pytest.approx(2100.0),
        "intervals_over_3x_median": 1,
        "longest": [[17, pytest.approx(2100.0)], [30, pytest.approx(250.0)],
                    [0, pytest.approx(100.0)], [1, pytest.approx(100.0)],
                    [2, pytest.approx(100.0)]]}
    assert train_adapter.window_summary([0.1, 0.1, 0.1])[
        "intervals_over_3x_median"] == 0


def test_a_state_kept_in_bfloat16_is_kept_so_by_program_and_reference(
        tiny_models, monkeypatch):
    """`optimizer_state_dtype` in the configuration's file reaches
    `build_trainer(opt_state_dtype=)` and the reference's own moments."""
    from deep_vision_tpu import train_cli

    kept = {"program": [], "reference": []}
    build, update = train_cli.build_trainer, steps._opt_update

    def spy_program(*args, **kw):
        trainer = build(*args, **kw)
        kept["program"] += jax.tree.leaves(
            train_adapter._find(trainer.state.opt_state, "mu"))
        return trainer

    def spy_reference(*args):
        new_params, opt = update(*args)
        kept["reference"] += jax.tree.leaves(opt)
        return new_params, opt

    monkeypatch.setattr(train_cli, "build_trainer", spy_program)
    monkeypatch.setattr(steps, "_opt_update", spy_reference)
    result = rehearse("tiny_vit_bf16state_train")
    assert result["correct"], (result["compared"], result["faults"])
    for side, leaves in kept.items():
        assert leaves and all(x.dtype == jnp.bfloat16 for x in leaves), side


@pytest.mark.parametrize("config_name,shape", [
    ("tiny_vit_bf16state", (32, 32, 3)), ("tiny_resnet", (16, 16, 12))])
def test_first_steps_leave_no_spare_parameter_tree_on_the_device(
        tiny_models, config_name, shape):
    """After `first_steps` the readings are on the host and every new array
    of a parameter's shape lies in the memory of the trainer's own state:
    no copy of the seeded variables, of `params0` or of a gradient is left
    (on the CPU a fetched leaf stays alive as a view of its own buffer)."""
    config = rehearsal_config(config_name)
    with open(os.path.join(ROOT, "tests", "benchmark", "traffic",
                           "b16_pool4.json")) as f:
        traffic = json.load(f)
    trainer, journal, _ = train_adapter.build_trainer(
        config, traffic["global_batch"])
    pool = traffic_mod.make_pool(traffic, config, shape, 5)
    module = train_adapter.reference_module(config)
    before = alive_now()
    handed = [module.init(config, train_adapter.seed_key(5))]
    shapes = [x.shape for x in jax.tree.leaves(handed[0]["params"])]
    got = train_adapter.first_steps(trainer, journal, config, pool,
                                    handed.pop())
    try:
        assert all(isinstance(x, np.ndarray)
                   for x in jax.tree.leaves(got["delta"]))
        assert [x.shape for x in jax.tree.leaves(got["delta"])] == shapes
        assert got["grad_norms"].shape == (len(shapes),)
        alive = [x for x in alive_since(before) if x.shape in shapes]
        memory = lambda x: {s.data.unsafe_buffer_pointer()
                            for s in x.addressable_shards}
        own = set().union(*map(memory, jax.tree.leaves(trainer.state)))
        assert len(alive) >= len(shapes)
        assert all(memory(x) <= own for x in alive)
    finally:
        trainer.close()


def _state_unchanged(impl):
    def broken(self, state, batch):
        _, metrics = impl(self, state, batch)
        return state, metrics
    return broken


def _rows_left_out(share):
    def wrap(impl):
        def broken(self, state, batch):
            keep = batch[self.input_key].shape[0] // share
            return impl(self, state, jax.tree.map(lambda x: x[:keep], batch))
        return broken
    return wrap


@pytest.mark.parametrize("fault,wrap", [
    ("state_unchanged", _state_unchanged),
    ("half_of_the_batch_left_out", _rows_left_out(2)),
    ("exchange_between_chips_left_out", _rows_left_out(8)),
])
def test_a_broken_timed_path_is_not_correct(tiny_models, monkeypatch, fault,
                                            wrap):
    """Each fault a training cell can have, planted under `Trainer.fit`:
    the step returns its state unchanged; half of the batch is left out
    and the mean taken over the rest; only one of the 8 devices' rows
    count, as with the gradient exchange left out."""
    from deep_vision_tpu.train.trainer import Trainer

    monkeypatch.setattr(Trainer, "_train_step_impl",
                        wrap(Trainer._train_step_impl))
    result = rehearse("tiny_resnet_train")
    compared = result["compared"]
    assert not result["correct"], (fault, compared)
    assert any(c["value"] > c["limit"] for c in compared.values())
    if fault == "state_unchanged":  # both read 1: the upper reading
        assert compared["grad_gap"]["value"] == pytest.approx(1, abs=0.05)
        assert compared["delta_gap"]["value"] == pytest.approx(1, abs=1e-6)


def test_the_first_gradient_is_read_after_exactly_one_closed_step(
        tiny_models, monkeypatch):
    """`first_steps` reads the optimizer's state between two `fit` calls,
    the first of one batch: a loop that pulls its feed ahead of the step
    (prefetch) cannot move the reading."""
    from deep_vision_tpu.train.trainer import Trainer

    fed, real_fit = [], Trainer.fit

    def fit_pulling_ahead(self, train_data_fn, *a, **kw):
        feed = iter(train_data_fn())
        ahead = list(itertools.islice(feed, 4))  # pulled before any step
        fed.append(len(ahead))
        return real_fit(self, lambda: itertools.chain(ahead, feed), *a, **kw)

    monkeypatch.setattr(Trainer, "fit", fit_pulling_ahead)
    result = rehearse("tiny_resnet_train")
    assert fed[:2] == [1, train_adapter.COMPARED_STEPS - 1]
    assert result["correct"], result["compared"]


def test_every_number_is_held_to_its_limit():
    values = {"loss_gap": 1e-5, "grad_gap": 0.03, "delta_gap": 0.01}
    limits = {"loss_gap": 1e-4, "grad_gap": 0.3, "delta_gap": 0.1}
    ok, compared = compare.judge(values, limits)
    assert ok and compared["grad_gap"] == {"value": 0.03, "limit": 0.3}
    for name in compare.NUMBERS:
        assert not compare.judge({**values, name: 0.5}, limits)[0]
        assert not compare.judge({**values, name: float("nan")}, limits)[0]
        # a number the cell's file does not name, or leaves null, fails
        assert not compare.judge(values, {**limits, name: None})[0]
        assert not compare.judge(
            values, {k: v for k, v in limits.items() if k != name})[0]


@pytest.mark.parametrize("cell", ["tiny_resnet_train", "tiny_vit_train",
                                  "tiny_vit_bf16state_train",
                                  "tiny_vit_tokens_train"])
def test_the_lower_precision_control_fails(cell):
    """The reference computed in bfloat16, the precision below the float32
    these rehearsal configurations state, put in the program's place and
    judged at the cell's limits."""
    cell, config, traffic = run.resolve(run.load_manifest(REHEARSAL), cell)
    shape = (16, 16, 12) if config["reference"] == "resnet" \
        else tuple(config["input_shape"])
    pool = traffic_mod.make_pool(traffic, config, shape, 5)
    devices = jax.devices()[:1]
    reference = train_adapter.reference_steps(config, pool, 5, devices)
    control = train_adapter.reference_steps(config, pool, 5, devices,
                                            control=True)
    normalised = train_adapter.normalised_update(config)
    same, _ = compare.judge(compare.gaps(
        train_adapter.as_program(reference), reference, normalised),
        cell["limits"])
    values = compare.gaps(train_adapter.as_program(control), reference,
                          normalised, distance=True)
    ok, compared = compare.judge(values, cell["limits"])
    assert same and not ok, compared
    # elements lie at least as far apart as their norms do
    assert values["delta_distance"] >= values["delta_gap"] > 0
