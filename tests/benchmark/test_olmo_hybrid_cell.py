"""The `olmo_hybrid_7b` configuration and its cell `olmo_hybrid_7b_train`,
on the CPU at a tiny size: the program's model against the plain reference
(`benchmark/reference/olmo_hybrid.py`), the written FLOP count against the
jaxpr's, the cell's four files rehearsed through `run.py` with a manifest
written under `tmp_path`, and the three metric files that wait outside
`BENCHMARK.json`.

Tolerances. Program and reference are float32 here and compute the same
mathematics in another order (the delta rule in chunks against token by
token, the loss in blocks against rows), so they part by float32 rounding:
logits 1e-6 of their largest, per-leaf gradients 1e-6 to 1e-5 of a leaf's
norm. The limits, 1e-4, stand ten times over that and a hundred times under
what bfloat16 anywhere reads (4e-3 a product).
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, flops, run  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402
from benchmark.adapters import train as train_adapter  # noqa: E402
from benchmark.reference import olmo_hybrid as reference  # noqa: E402

CELL = "olmo_hybrid_7b_train"
NEW_METRICS = [
    {"name": "delta_rule_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "kernels"},
    {"name": "delta_rule_roofline_pct", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels"},
    {"name": "tok_per_s_chip", "unit": "tokens/s/chip", "better": "higher",
     "source": "program_counter", "layer": "host loop"},
]
# the tiny size: the reference's names, then the program's for the same
TINY = {"hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 2,
        "linear_num_key_heads": 2, "linear_num_value_heads": 2,
        "linear_key_head_dim": 8, "linear_value_head_dim": 16,
        "vocab_size": 64, "num_hidden_layers": 4}
TINY_KWARGS = {"hidden_size": 32, "intermediate_size": 48,
               "num_attention_heads": 2, "linear_num_heads": 2,
               "linear_key_head_dim": 8, "linear_value_head_dim": 16,
               "vocab_size": 64, "num_hidden_layers": 4}


def real_config():
    manifest = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    return run.resolve(manifest, CELL)


def tiny_config(**over):
    """The real configuration's file with its widths cut, in float32."""
    _, config, _ = real_config()
    return {**config, **TINY, "model_kwargs": dict(TINY_KWARGS),
            "compute_dtype": "float32", "input_shape": [16], **over}


def two_layers(**over):
    """The same with one layer of each kind, where a test compiles the
    reference's whole step several times."""
    kinds = ["linear_attention", "full_attention"]
    return tiny_config(
        layer_types=kinds, num_hidden_layers=2, model_kwargs={
            **TINY_KWARGS, "layer_types": kinds, "num_hidden_layers": 2},
        **over)


def tiny_model(**over):
    from deep_vision_tpu.models import get_model

    return get_model("olmo_hybrid_7b", **{**TINY_KWARGS, **over})


def apart(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


# -- the configuration's file and the manifest's entries ---------------------

def test_the_file_holds_every_published_width_and_says_what_it_cut():
    cell, config, traffic = real_config()
    published = {
        "model_type": "olmo_hybrid", "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 12544)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert "8 stages" in config["deployment"]
    assert set(config["assumed"]) >= {"rope", "norm_placement", "qk_norm",
                                      "linear_attention", "recipe", "init"}
    # what the program is told is what the reference reads
    assert config["model_kwargs"] == {"num_hidden_layers": 4,
                                      "vocab_size": 12544}
    assert (config["task"], config["input_shape"]) == ("causal_lm", [2048])
    assert config["optimizer_state_dtype"] == "bfloat16"
    assert config["reference_row_blocks"] == 1
    entry = {c["name"]: c for c in run.load_manifest(os.path.join(
        ROOT, "BENCHMARK.json"))["configs"]}["olmo_hybrid_7b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == ("https://huggingface.co/allenai/"
                               "Olmo-Hybrid-7B/blob/main/config.json")
    assert (cell["traffic"], cell["chips"]) == ("tok2048_b2_pool4", 1)
    assert traffic == {**traffic, "kind": "token_pool", "global_batch": 2,
                       "seq_len": 2048, "pool_batches": 4}
    assert cell["delta_rule_ops"]


def test_the_program_at_the_files_sizes_is_the_references_tree():
    """Leaf names, shapes and the parameter count at the real size, from
    shapes alone: nothing of that size is made."""
    from deep_vision_tpu.models import get_model

    _, config, _ = real_config()
    model = get_model(config["model"], **config["model_kwargs"])
    mine = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 64), jnp.int32)))["params"]
    theirs = jax.eval_shape(
        lambda: reference.init(config, jax.random.PRNGKey(0)))["params"]
    shapes = lambda tree: jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                                       dict(tree))
    assert shapes(mine) == shapes(theirs)
    count = sum(x.size for x in jax.tree.leaves(theirs))
    assert count == 928_862_196  # 11.15 GB at 12 bytes in a step
    assert count * 12 < 0.7 * 16e9 + 1e8


# -- the model against the reference -----------------------------------------

@pytest.fixture(scope="module")
def seeded():
    config = tiny_config(input_shape=[128])
    variables = reference.init(config, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                                config["vocab_size"])
    return config, variables, tokens


def test_logits_are_the_references(seeded):
    """128 tokens: two chunks of the program's 64 against 128 single
    tokens of the reference; logits, not the tokens they would choose."""
    from deep_vision_tpu.losses import causal_lm

    config, variables, tokens = seeded
    with jax.default_matmul_precision("highest"):
        got = causal_lm.logits(tiny_model().apply(
            {"params": variables["params"]}, tokens))
        want, _ = reference.forward(config, variables, tokens)
    assert got.shape == (2, 128, 64) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_loss_and_every_leafs_gradient_are_the_references(seeded):
    from deep_vision_tpu.losses.causal_lm import causal_lm_loss_fn

    config, variables, tokens = seeded
    model, batch = tiny_model(), {"tokens": tokens}
    with jax.default_matmul_precision("highest"):
        # blocks of 32 tokens: four a row, each recomputed
        loss, grads = jax.value_and_grad(lambda p: causal_lm_loss_fn(
            model.apply({"params": p}, tokens), batch, block_tokens=32)[0])(
                variables["params"])
        want, want_grads = jax.value_and_grad(
            lambda p: reference.loss_fn(config, p, {}, batch)[0])(
                variables["params"])
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    named = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(named) == 68  # 3 x 18 + 11 + embedding, final norm, head
    for (path, got), ref in zip(named, jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(ref)) > 0, path
        assert apart(got, ref) < 1e-4, jax.tree_util.keystr(path)


def test_recomputation_and_the_python_loop_change_no_mathematics(seeded):
    """The reference as the cell runs it (blocks, rows and spans of the
    recurrence under `jax.checkpoint`, a `scan` over tokens) against the
    same written out plainly, as the FLOP count's jaxpr holds it."""
    _, _, tokens = seeded
    config = two_layers()
    plain = {**config, "reference_remat": False, "reference_unroll": True}
    variables = reference.init(config, jax.random.PRNGKey(4))
    batch = {"tokens": tokens[:, :8]}
    loss = lambda cfg: float(reference.loss_fn(
        cfg, variables["params"], {}, batch)[0])
    assert loss(config) == pytest.approx(loss(plain), rel=1e-6)


@pytest.mark.parametrize("held", [0, 3, 7])
def test_the_eight_slices_logits_side_by_side_are_the_uncut_heads(held):
    """The vocabulary's share: a chip that holds rows `held * 8 .. + 8` of
    a 64-row embedding and the same columns of the head, fed ids of its
    slice, gives the uncut model's hidden states, and the eight chips'
    logits side by side are the uncut reference's."""
    from deep_vision_tpu.losses import causal_lm

    config = tiny_config()
    variables = reference.init(config, jax.random.PRNGKey(5))
    params = variables["params"]
    lo = held * 8
    ids = jax.random.randint(jax.random.PRNGKey(held), (2, 16), 0, 8)
    share = {**params,
             "embed": {"embedding": params["embed"]["embedding"][lo:lo + 8]},
             "head": params["head"][:, lo:lo + 8]}
    with jax.default_matmul_precision("highest"):
        out = tiny_model(vocab_size=8).apply({"params": share}, ids)
        want, _ = reference.forward(config, variables, ids + lo)
        side_by_side = jnp.concatenate([causal_lm.logits(
            {**out, "head": params["head"][:, j:j + 8]})
            for j in range(0, 64, 8)], axis=-1)
    assert float(jnp.max(jnp.abs(side_by_side - want))) < 1e-5
    np.testing.assert_array_equal(causal_lm.logits(out),
                                  side_by_side[..., lo:lo + 8])


# -- the written counts ------------------------------------------------------

@pytest.mark.parametrize("rows,tokens", [(2, 6), (1, 9)])
def test_step_flops_is_the_jaxprs_count_where_both_can_be_taken(rows,
                                                                tokens):
    """A few tokens, the recurrence a Python loop, nothing recomputed, the
    scores counted whole as a jaxpr of the plain form holds them."""
    plain = tiny_config(reference_remat=False, reference_unroll=True)
    spec = {"tokens": jax.ShapeDtypeStruct((rows, tokens), jnp.int32)}
    variables = jax.eval_shape(
        lambda: reference.init(plain, jax.random.PRNGKey(0)))
    counted = flops.flops_of(
        lambda p, b: jax.value_and_grad(
            lambda p: reference.loss_fn(plain, p, {}, b)[0])(p),
        variables["params"], spec)
    assert counted == reference.step_flops(plain, spec, scores="whole")
    # the mask's zeros are what the cell's count leaves out
    masked = reference.step_flops(plain, spec)
    pairs_left_out = tokens * tokens - tokens * (tokens + 1) // 2
    assert counted - masked == rows * 2 * 12 * pairs_left_out * 16
    assert flops.train_step_flops(reference, plain, spec) == masked


def test_the_cells_step_is_22_tflop_by_the_written_count():
    _, config, traffic = real_config()
    spec = traffic_mod.batch_spec(traffic, config, (2048,))
    total = flops.train_step_flops(reference, config, spec)
    recurrence = reference.delta_rule_flops(config, 2, 2048)
    scores = 2 * 30 * 12 * (2048 * 2049 // 2) * 128
    assert recurrence == 3 * 4096 * 30 * 18 * 192 * 96 == 122_305_904_640
    assert total == 21_954_558_689_280.0
    assert total - recurrence - scores == pytest.approx(21.639e12, rel=1e-4)
    # q, k, v, g, b, o and their gradients, bfloat16: the longer roof
    assert reference.delta_rule_bytes(config, 2, 2048, 2) \
        == 3 * 4096 * 30 * 2 * (2 * 96 + 2 * 192 + 2) * 2
    assert reference.delta_rule_bytes(config, 2, 2048, 2) / 819e9 \
        > recurrence / 197e12


# -- the cell's files, rehearsed ---------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """A manifest of its own under tmp_path: the cell's configuration at
    the tiny size with a layer of each kind, a traffic file of 8 rows (a row a device of the CPU
    mesh), tight limits, and the three metrics that wait, each with a
    `workloads` list."""
    root = tmp_path_factory.mktemp("olmo_rehearsal")
    for sub in ("configs", "cells", "traffic"):
        os.makedirs(root / sub)
    cell, _, traffic = real_config()
    with open(root / "configs" / "tiny_olmo.json", "w") as f:
        json.dump(two_layers(), f)
    with open(root / "traffic" / "tok16_b8_pool4.json", "w") as f:
        json.dump({**traffic, "global_batch": 8, "seq_len": 16}, f)
    with open(root / "cells" / "tiny_olmo_train.json", "w") as f:
        json.dump({"limits": {"loss_gap": 1e-4, "grad_gap": 4e-3,
                              "delta_gap": 1e-3},
                   "delta_rule_ops": cell["delta_rule_ops"]}, f)
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    manifest = {
        **real, "paths": real["paths"] + [str(root)],
        "configs": [{"name": "tiny_olmo", "source": "a CPU rehearsal",
                     "file": str(root / "configs" / "tiny_olmo.json"),
                     "reduced": [], "why": "rehearsal"}],
        "workloads": [{"name": "tiny_olmo_train", "config": "tiny_olmo",
                       "traffic": "tok16_b8_pool4", "chips": 8,
                       "why": "rehearsal"}],
        "per_layer": [{**m, "workloads": ["tiny_olmo_train"]}
                      if "workloads" in m else m for m in real["per_layer"]]
        + [{**m, "moves": "img_per_s_chip", "workloads": ["tiny_olmo_train"]}
           for m in NEW_METRICS]}
    path = root / "manifest.json"
    with open(path, "w") as f:
        json.dump(manifest, f)
    return str(path)


def test_the_cell_rehearsed_through_run_py_is_correct(rehearsal):
    """Three steps through `Trainer.fit` with AdamW's moments stored in
    bfloat16, against the reference's three, at the judge's limits."""
    from deep_vision_tpu.obs.registry import get_registry

    manifest = run.load_manifest(rehearsal)
    result = run.run_cell(manifest, "tiny_olmo_train", 2 ** 31 + 11, 0.3, 0,
                          require_chip=False)
    assert result["correct"], (result["compared"], result["faults"])
    assert set(result["metrics"]) == {"img_per_s_chip", "step_ms_p95",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    counters = {(m.name, tuple(sorted(m.labels.items()))): m.value
                for m in get_registry().metrics() if m.kind == "counter"}
    assert counters[("sequence_mixer_sites_total",
                     (("kind", "linear"),))] >= 1
    assert counters[("sequence_mixer_sites_total", (("kind", "full"),))] >= 1
    assert counters[("train_tokens_total", ())] >= 3 * 8 * 16


def test_the_lower_precision_control_and_half_the_rows_fail(rehearsal):
    cell, config, traffic = run.resolve(run.load_manifest(rehearsal),
                                        "tiny_olmo_train")
    pool = traffic_mod.make_pool(traffic, config, (16,), 5)
    devices = jax.devices()[:1]
    steps = lambda **kw: train_adapter.reference_steps(config, pool, 5,
                                                       devices, **kw)
    whole = steps()
    judged = lambda other: compare.judge(compare.gaps(
        train_adapter.as_program(other), whole, True), cell["limits"])
    assert judged(whole)[0]
    for planted in ({"control": True}, {"rows": 4}):
        ok, compared = judged(steps(**planted))
        assert not ok, (planted, compared)


def record(ops, cell=None):
    _, config, _ = real_config()
    return {"trace": {"op_s_per_step": ops} if ops is not None else None,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "config": config, "global_batch": 2, "chips": 1, "steps": 100,
            "window_s": 20.0,
            "batch_spec": {"tokens": jax.ShapeDtypeStruct((2, 2048),
                                                          jnp.int32)},
            "cell": {"name": "tiny_olmo_train",
                     "delta_rule_ops": ["while", "gdn_fwd"]}
            if cell is None else cell}


def waiting_alone(path):
    """The rehearsal's manifest with the three waiting metrics alone."""
    manifest = run.load_manifest(path)
    names = {m["name"] for m in NEW_METRICS}
    return {**manifest, "per_layer": [m for m in manifest["per_layer"]
                                      if m["name"] in names]}


def test_the_waiting_metrics_read_their_hand_worked_numbers_or_nothing(
        rehearsal):
    manifest = waiting_alone(rehearsal)
    ops = {"while": 0.010, "while.7": 0.020, "gdn_fwd.2": 0.002,
           # not the rule's: another op, and one that shares letters
           "fusion.3": 5.0, "while_loop.1": 7.0, "gdn_fwd_other": 9.0}
    got = run.read_metrics(manifest, "per_layer", record(ops))
    assert got["delta_rule_ms"] == {"value": pytest.approx(32.0),
                                    "unit": "ms"}
    # 852,197,... bytes over 819 GB/s = 1.0406 ms, over 32 ms of ops
    least = 3 * 4096 * 30 * 2 * 578 * 2 / 819e9
    assert least == pytest.approx(1.0406e-3, rel=1e-3)
    assert got["delta_rule_roofline_pct"]["value"] == pytest.approx(
        least / 0.032 * 100)
    assert got["delta_rule_roofline_pct"]["unit"] == "%"
    # nothing to read: no trace, a cell that names no ops, a step with none
    for rec in (record(None), record(ops, cell={"name": "tiny_olmo_train"}),
                record({"fusion.3": 5.0})):
        left = run.read_metrics(manifest, "per_layer", rec)
        assert "delta_rule_ms" not in left
        assert "delta_rule_roofline_pct" not in left
    # a metric with a `workloads` list is read in its own cells alone
    other = record(ops)
    other["cell"] = {**other["cell"], "name": "resnet50_train_b128"}
    assert not set(run.read_metrics(manifest, "per_layer", other)) & {
        m["name"] for m in NEW_METRICS}


def test_tokens_a_second_are_the_programs_count_over_the_window(rehearsal):
    """`train_tokens_total` over `train_steps_total` (any feed of this
    process so far: the same 8 x 16 a step) times the window's steps."""
    from deep_vision_tpu.obs.registry import get_registry

    manifest = waiting_alone(rehearsal)
    counters = {m.name: m for m in get_registry().metrics()
                if not m.labels}
    if "train_tokens_total" not in counters:
        pytest.skip("no token step has run in this process yet")
    per_step = counters["train_tokens_total"].value \
        / counters["train_steps_total"].value
    got = run.read_metrics(manifest, "per_layer", record({"while": 1.0}))
    assert got["tok_per_s_chip"]["value"] == pytest.approx(
        100 * per_step / 20.0)
    assert got["tok_per_s_chip"]["unit"] == "tokens/s/chip"


def test_the_new_metrics_are_files_not_yet_listed():
    """They enter `BENCHMARK.json` with the `benchmark` PR that admits the
    waiting cell (`test_benchmark.py` holds the two manifests' `per_layer`
    equal until then): until then a file each, and this test."""
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in real["per_layer"] + real["end_to_end"]}
    for metric in NEW_METRICS:
        assert metric["name"] not in listed
        assert callable(run.load_py(run.find_file(
            real, "metrics", metric["name"] + ".py")).read)
