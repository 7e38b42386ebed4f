"""The `solar_open2_250b` configuration and its cell `solar_open2_250b_train`,
on the CPU at a tiny size: the configuration's file against the published
config, the program at the file's sizes against the reference's variable
tree, the written FLOP count against the jaxpr's where every expert is held
and chosen, the cell's files rehearsed through `run.py` with a manifest
written under `tmp_path`, and the three metric files that wait outside
`BENCHMARK.json` (with `delta_rule_ms` and `delta_rule_roofline_pct`, which
read this cell through its own file).
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, flops, run  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402
from benchmark.adapters import train as train_adapter  # noqa: E402
from benchmark.reference import solar_open2 as reference  # noqa: E402

CELL = "solar_open2_250b_train"
NEW_METRICS = [
    {"name": "expert_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "kernels"},
    {"name": "expert_roofline_pct", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels"},
    {"name": "routed_pairs_per_step", "unit": "1", "better": "higher",
     "source": "program_counter", "layer": "expert layer"},
]
DELTA_METRICS = [
    {"name": "delta_rule_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "kernels"},
    {"name": "delta_rule_roofline_pct", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels"},
]
# the tiny size: the reference's names, then the program's for the same
TINY = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 8, "moe_intermediate_size": 16, "n_routed_experts": 4,
        "router_experts": 16, "num_experts_per_tok": 4, "vocab_size": 64,
        "num_hidden_layers": 4, "held_offset": 4,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                               "num_heads": 2, "num_kv_heads": None}}
TINY_KWARGS = {"hidden_size": 32, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 8, "linear_num_heads": 2,
               "linear_head_dim": 8, "moe_intermediate_size": 16,
               "n_routed_experts": 4, "router_experts": 16,
               "num_experts_per_tok": 4, "vocab_size": 64,
               "num_hidden_layers": 4, "held_offset": 4}


def real_config():
    manifest = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    return run.resolve(manifest, CELL)


def tiny_config(**over):
    """The real configuration's file with its widths cut, in float32."""
    _, config, _ = real_config()
    return {**config, **TINY, "model_kwargs": dict(TINY_KWARGS),
            "compute_dtype": "float32", "input_shape": [16], **over}


# -- the configuration's file and the manifest's entries ---------------------

# the published config.json, as the model-configs catalog reads it
CATALOG = {'model_type': 'solar_open2', 'partial_rotary_factor': 1,
           'linear_attn_config': {'short_conv_kernel_size': 4, 'head_dim': 128,
                                  'num_heads': 64, 'num_kv_heads': None},
           'hidden_size': 4096, 'num_hidden_layers': 48,
           'num_attention_heads': 64, 'head_dim': 128,
           'num_key_value_heads': 8, 'vocab_size': 196608,
           'intermediate_size': 10240, 'moe_intermediate_size': 1280,
           'rms_norm_eps': 1e-05, 'rope_theta': 10000,
           'tie_word_embeddings': False, 'max_position_embeddings': 1048576,
           'first_k_dense_replace': 0, 'use_rope': False, 'gqa_interval': 3,
           'gqa_layers': [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
           'use_gqa_gate': True, 'kda_use_full_proj': False,
           'kda_allow_neg_eigval': True, 'n_routed_experts': 320,
           'n_shared_experts': 1, 'norm_topk_prob': True,
           'routed_scaling_factor': 1, 'num_experts_per_tok': 8}

PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "hidden_size": 4096, "head_dim": 128, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}
REDUCED = {"num_hidden_layers": (4, 48), "vocab_size": (24576, 196608),
           "n_routed_experts": (8, 320), "num_attention_heads": (32, 64),
           "num_key_value_heads": (4, 8)}


def test_the_file_holds_every_published_width_and_says_what_it_cut():
    cell, config, traffic = real_config()
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 32,
        "num_kv_heads": None}
    assert config["reduced"] == ["num_hidden_layers", "vocab_size",
                                 "n_routed_experts", "num_attention_heads",
                                 "num_key_value_heads", "linear_attn_config"]
    for key, (here, published) in REDUCED.items():
        assert (config[key], config["published"][key]) == (here, published)
    assert config["published"]["linear_attn_config"]["num_heads"] == 64
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert (config["router_experts"], config["held_offset"]) == (320, 0)
    assert "40 chips" in config["deployment"]
    assert set(config["assumed"]) >= {"norm_placement", "gqa", "kda", "moe",
                                      "recipe", "init", "sequence"}
    # what the program is told is what the reference reads
    assert config["model_kwargs"] == {
        "num_hidden_layers": 4, "vocab_size": 24576,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "linear_num_heads": 32, "n_routed_experts": 8,
        "router_experts": 320, "held_offset": 0}
    assert (config["task"], config["input_shape"]) == ("causal_lm", [2048])
    assert config["optimizer_state_dtype"] == "bfloat16"
    assert config["reference_row_blocks"] == 1
    entry = {c["name"]: c for c in run.load_manifest(os.path.join(
        ROOT, "BENCHMARK.json"))["configs"]}["solar_open2_250b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == ("https://huggingface.co/upstage/"
                               "Solar-Open2-250B/blob/main/config.json")
    assert (cell["traffic"], cell["chips"]) == ("tok2048_b2_pool4", 1)
    assert traffic == {**traffic, "kind": "token_pool", "global_batch": 2,
                       "seq_len": 2048}
    assert cell["delta_rule_ops"] and cell["expert_kernel_ops"]


def test_the_file_is_the_published_config_but_for_what_it_lists():
    """Every number of the published config under its own key, nested
    groups whole; a key that differs is in `reduced`, its published value
    under `published`."""
    _, config, _ = real_config()
    differs = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert all(config["published"][k] == CATALOG[k] for k in differs)


def test_the_program_at_the_files_sizes_is_the_references_tree():
    """Leaf names, shapes and the parameter count at the real size, from
    shapes alone: nothing of that size is made."""
    from deep_vision_tpu.models import get_model

    _, config, _ = real_config()
    model = get_model(config["model"], **config["model_kwargs"])
    mine = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 64), jnp.int32)))
    theirs = jax.eval_shape(
        lambda: reference.init(config, jax.random.PRNGKey(0)))
    shapes = lambda tree: jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                                       dict(tree))
    assert shapes(mine) == shapes(theirs)
    count = sum(x.size for x in jax.tree.leaves(theirs["params"]))
    # 3 KDA mixers of 69.4 M, a GQA mixer of 54.5 M, 4 MoE layers of
    # 142.9 M, 1/8 of the embedding and head 201.3 M, norms
    assert count == 1_035_547_104
    assert count * 12 < 12.5e9


def test_the_registered_recipe_is_the_cells_share():
    """`train.py -m solar_open2_250b` trains what the cell measures."""
    from deep_vision_tpu.configs import get_config

    _, config, traffic = real_config()
    recipe = get_config("solar_open2_250b")
    kwargs = {k: v for k, v in config["model_kwargs"].items()
              if k not in ("router_experts", "held_offset")}
    assert recipe.model_kwargs == kwargs
    assert (recipe.task, recipe.input_shape) == ("causal_lm", (2048,))
    assert recipe.batch_size == traffic["global_batch"]
    assert recipe.optimizer == config["optimizer"]


# -- the written counts ------------------------------------------------------

def all_held(**over):
    """Every expert held and chosen: 4 experts, 4 a token."""
    return tiny_config(n_routed_experts=4, router_experts=4, held_offset=0,
                       reference_remat=False, reference_unroll=True, **over)


@pytest.mark.parametrize("rows,tokens", [(2, 6), (1, 9)])
def test_step_flops_is_the_jaxprs_count_where_both_can_be_taken(rows,
                                                                tokens):
    """A few tokens, the recurrence a Python loop, nothing recomputed,
    every expert held and chosen (so the expected pairs are every pair),
    the scores counted whole as a jaxpr of the plain form holds them."""
    plain = all_held()
    spec = {"tokens": jax.ShapeDtypeStruct((rows, tokens), jnp.int32)}
    variables = jax.eval_shape(
        lambda: reference.init(plain, jax.random.PRNGKey(0)))
    counted = flops.flops_of(
        lambda p, s, b: jax.value_and_grad(
            lambda p: reference.loss_fn(plain, p, s, b)[0])(p),
        variables["params"], variables["batch_stats"], spec)
    assert counted == reference.step_flops(plain, spec, scores="whole")
    masked = reference.step_flops(plain, spec)
    pairs_left_out = tokens * tokens - tokens * (tokens + 1) // 2
    # one GQA layer, 4 heads of 8
    assert counted - masked == rows * 4 * 12 * pairs_left_out * 8
    assert flops.train_step_flops(reference, plain, spec) == masked


def test_the_cells_step_is_the_written_count():
    _, config, traffic = real_config()
    spec = traffic_mod.batch_spec(traffic, config, (2048,))
    total = flops.train_step_flops(reference, config, spec)
    recurrence = reference.delta_rule_flops(config, 2, 2048)
    assert recurrence == 3 * 4096 * 32 * 18 * 128 * 128
    scores = 2 * 32 * 12 * (2048 * 2049 // 2) * 128
    routed = 6 * 4 * 3 * 4096 * 1280 * 4096 * 8 * 8 / 320
    assert reference.expert_flops(config, 4 * 4096 * 8 * 8 / 320) == routed
    # KDA projections 5.11 T, head 2.47, shared experts 1.55, GQA
    # projections 1.34, routed 0.31, scores 0.21, router 0.13, recurrence
    # 0.12
    assert total == 11_231_305_924_608.0
    assert total - recurrence - scores > 0.95 * total
    # q, k, g, v, o and b and their gradients, bfloat16: the longer roof
    assert reference.delta_rule_bytes(config, 2, 2048, 2) \
        == 3 * 4096 * 32 * 2 * (5 * 128 + 1) * 2
    assert reference.delta_rule_bytes(config, 2, 2048, 2) / 819e9 \
        > recurrence / 197e12


# -- the cell's files, rehearsed ---------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """A manifest of its own under tmp_path: the cell's configuration at
    the tiny size, a traffic file of 8 rows (a row a device of the CPU
    mesh), tight limits, and the metrics that wait, each with a
    `workloads` list."""
    root = tmp_path_factory.mktemp("solar_rehearsal")
    for sub in ("configs", "cells", "traffic"):
        os.makedirs(root / sub)
    cell, _, traffic = real_config()
    with open(root / "configs" / "tiny_solar.json", "w") as f:
        json.dump(tiny_config(), f)
    with open(root / "traffic" / "tok16_b8_pool4.json", "w") as f:
        json.dump({**traffic, "global_batch": 8, "seq_len": 16}, f)
    with open(root / "cells" / "tiny_solar_train.json", "w") as f:
        json.dump({"limits": {"loss_gap": 1e-4, "grad_gap": 4e-3,
                              "delta_gap": 1e-3},
                   "delta_rule_ops": cell["delta_rule_ops"],
                   "expert_kernel_ops": cell["expert_kernel_ops"]}, f)
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    manifest = {
        **real, "paths": real["paths"] + [str(root)],
        "configs": [{"name": "tiny_solar", "source": "a CPU rehearsal",
                     "file": str(root / "configs" / "tiny_solar.json"),
                     "reduced": [], "why": "rehearsal"}],
        "workloads": [{"name": "tiny_solar_train", "config": "tiny_solar",
                       "traffic": "tok16_b8_pool4", "chips": 8,
                       "why": "rehearsal"}],
        "per_layer": [{**m, "workloads": ["tiny_solar_train"]}
                      if "workloads" in m else m for m in real["per_layer"]]
        + [{**m, "moves": "img_per_s_chip", "workloads": ["tiny_solar_train"]}
           for m in NEW_METRICS + DELTA_METRICS]}
    path = root / "manifest.json"
    with open(path, "w") as f:
        json.dump(manifest, f)
    return str(path)


def test_the_cell_rehearsed_through_run_py_is_correct(rehearsal):
    """Three steps through `Trainer.fit` with AdamW's moments stored in
    bfloat16 and the routers' biases moved after each, against the
    reference's three, at the judge's limits; the expert layer's counters
    are the program's."""
    from deep_vision_tpu.obs.registry import get_registry

    manifest = run.load_manifest(rehearsal)
    result = run.run_cell(manifest, "tiny_solar_train", 2 ** 31 + 13, 0.3, 0,
                          require_chip=False)
    assert result["correct"], (result["compared"], result["faults"])
    assert set(result["metrics"]) == {"img_per_s_chip", "step_ms_p95",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    counters = {(m.name, tuple(sorted(m.labels.items()))): m.value
                for m in get_registry().metrics() if m.kind == "counter"}
    assert counters[("sequence_mixer_sites_total", (("kind", "kda"),))] >= 3
    assert counters[("moe_sites_total", ())] >= 4
    steps = counters[("train_steps_total", ())]
    pairs = counters[("moe_routed_pairs_total", ())]
    # 8 x 16 tokens a step, 4 choices of 16 experts, 4 held, 4 layers:
    # 2,048 pairs expected in all, at most 4 x 512 held
    assert 0 < pairs <= steps * 4 * 8 * 16 * 4
    assert 0 < counters[("moe_held_load_max_total", ())] <= pairs


def test_the_lower_precision_control_and_half_the_rows_fail(rehearsal):
    cell, config, traffic = run.resolve(run.load_manifest(rehearsal),
                                        "tiny_solar_train")
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
            "cell": {"name": "tiny_solar_train",
                     "delta_rule_ops": ["while", "gdn_inverse"],
                     "expert_kernel_ops": ["gmm", "tgmm"]}
            if cell is None else cell}


def waiting_alone(path):
    """The rehearsal's manifest with the waiting metrics alone."""
    manifest = run.load_manifest(path)
    names = {m["name"] for m in NEW_METRICS + DELTA_METRICS}
    return {**manifest, "per_layer": [m for m in manifest["per_layer"]
                                      if m["name"] in names]}


class _Counters:
    """A registry of two counters, in place of the process's."""

    def __init__(self, **values):
        class C:
            labels = {}

            def __init__(self, name, value):
                self.name, self.value = name, value
        self._metrics = [C(k, v) for k, v in values.items()]

    def metrics(self):
        return self._metrics


def test_the_waiting_metrics_read_their_hand_worked_numbers_or_nothing(
        rehearsal, monkeypatch):
    from deep_vision_tpu.obs import registry

    manifest = waiting_alone(rehearsal)
    ops = {"gmm": 0.001, "gmm.7": 0.002, "tgmm.3": 0.003, "while.2": 0.010,
           "gdn_inverse.4": 0.002,
           # not the experts': another op, and ones that share letters
           "fusion.3": 5.0, "gmm_other": 7.0, "tgmm2.1": 9.0}
    monkeypatch.setattr(registry, "get_registry", lambda: _Counters(
        moe_routed_pairs_total=3276.8 * 10, train_steps_total=10.0))
    got = run.read_metrics(manifest, "per_layer", record(ops))
    assert got["expert_ms"] == {"value": pytest.approx(6.0), "unit": "ms"}
    assert got["routed_pairs_per_step"]["value"] == pytest.approx(3276.8)
    # at 3,276.8 pairs the bytes are the roof: the four layers' 8 held
    # experts read twice and written once, 3 x 4096 x 1280 x 3 values each,
    # and the pairs' rows, bf16, over 819 GB/s
    _, config, _ = real_config()
    least = (9 * 4096 * 1280 * 8 * 4 + 3 * 3276.8 * (2 * 4096 + 3 * 1280)) \
        * 2 / 819e9
    assert least > reference.expert_flops(config, 3276.8) / 197e12
    assert got["expert_roofline_pct"]["value"] == pytest.approx(
        least / 0.006 * 100)
    assert got["delta_rule_ms"]["value"] == pytest.approx(12.0)
    # nothing to read: no trace, a cell that names no ops, a step with none
    for rec in (record(None), record(ops, cell={"name": "tiny_solar_train"}),
                record({"fusion.3": 5.0})):
        left = run.read_metrics(manifest, "per_layer", rec)
        assert "expert_ms" not in left and "expert_roofline_pct" not in left
    # a program with no such counter (the parent's) reads nothing, and
    # raises nothing
    monkeypatch.setattr(registry, "get_registry", lambda: _Counters(
        train_steps_total=10.0))
    left = run.read_metrics(manifest, "per_layer", record(ops))
    assert "routed_pairs_per_step" not in left
    assert "expert_roofline_pct" not in left and "expert_ms" in left


def test_the_new_metrics_are_files_not_yet_listed():
    """They enter `BENCHMARK.json` with a `benchmark` PR
    (`test_benchmark.py` holds `waiting.json`'s `per_layer` equal to it
    until then): until then a file each, and this test."""
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in real["per_layer"] + real["end_to_end"]}
    for metric in NEW_METRICS:
        assert metric["name"] not in listed
        assert callable(run.load_py(run.find_file(
            real, "metrics", metric["name"] + ".py")).read)
