"""The `laguna_xs2` configuration and its cell `laguna_xs2_train`, on the CPU
at a tiny size: the configuration's file against the published config, the
program at the file's sizes against the reference's variable tree, the
written FLOP count against the jaxpr's where every expert is held and
chosen, the cell's files rehearsed through `run.py` with a manifest written
under `tmp_path`, and the two windowed-attention metric files, which wait
outside `BENCHMARK.json` as the expert layer's do.
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
from benchmark.reference import laguna as reference  # noqa: E402

CELL = "laguna_xs2_train"
NEW_METRICS = [
    {"name": "window_attention_ms", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "kernels"},
    {"name": "window_attention_roofline_pct", "unit": "%",
     "better": "higher", "source": "device_trace", "layer": "kernels"},
]
LAYERS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
# the tiny size: the reference's names, which the program's are too
TINY = {"hidden_size": 32, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 64, "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16, "num_experts": 4,
        "router_experts": 16, "num_experts_per_tok": 4, "vocab_size": 64,
        "num_hidden_layers": 5, "held_offset": 4, "sliding_window": 8,
        "layer_types": LAYERS, "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6]}


def real_config():
    manifest = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    return run.resolve(manifest, CELL)


def tiny_config(**over):
    """The real configuration's file with its widths cut, in float32."""
    _, config, _ = real_config()
    return {**config, **TINY, "model_kwargs": {**config["model_kwargs"],
                                               **TINY},
            "compute_dtype": "float32", "input_shape": [32], **over}


def catalog_row():
    """The published config.json, as the model-configs catalog holds it."""
    path = os.path.join(ROOT, "tests", "fixtures", "laguna_xs2_config.json")
    with open(path) as f:
        return json.load(f)


# -- the configuration's file and the manifest's entries ---------------------

def test_the_file_is_the_published_config_but_for_what_it_lists():
    """Every number of the published config under its own key, nested
    groups whole; a key that differs is in `reduced`, its published value
    under `published`."""
    cell, config, traffic = real_config()
    published = catalog_row()
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert all(config["published"][k] == published[k] for k in differs)
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 32, 12544)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    # the cut: the leading dense layer and one whole period after it
    assert config["layer_types"][:5] == LAYERS
    assert config["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert (config["router_experts"], config["held_offset"]) == (256, 0)
    assert "8 chips" in config["deployment"]
    assert set(config["assumed"]) >= {"norm_placement", "gate", "attention",
                                      "rope", "moe", "recipe", "init",
                                      "sequence"}
    assert config["model_kwargs"] == {
        "num_hidden_layers": 5, "vocab_size": 12544, "num_experts": 32,
        "router_experts": 256, "held_offset": 0, "stand_in": True,
        # the program's from the same file as the reference's
        "rope_parameters": config["rope_parameters"],
        "moe_routed_scaling_factor": config["moe_routed_scaling_factor"]}
    assert config["stand_in"] is True and "stand_in" in config["deployment"]
    assert (config["task"], config["input_shape"]) == ("causal_lm", [4096])
    assert config["optimizer_state_dtype"] == "bfloat16"
    entry = {c["name"]: c for c in run.load_manifest(os.path.join(
        ROOT, "BENCHMARK.json"))["configs"]}["laguna_xs2"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == ("https://huggingface.co/poolside/Laguna-XS.2/"
                               "blob/main/config.json")
    assert (cell["traffic"], cell["chips"]) == ("tok4096_b2_pool4", 1)
    assert traffic == {**traffic, "kind": "token_pool", "global_batch": 2,
                       "seq_len": 4096, "pool_batches": 4}
    assert cell["window_kernel_ops"] == [
        "flash_window_fwd", "flash_window_bwd_dq", "flash_window_bwd_dkv"]
    assert cell["expert_kernel_ops"] == ["gmm", "tgmm"]


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
    assert shapes(mine["params"]) == shapes(theirs["params"])
    count = sum(x.size for x in jax.tree.leaves(theirs["params"]))
    # 1/8 of the embedding and head 51.4 M; layer 0 full attention 29.5 M
    # and dense 50.3 M; three sliding layers' attention 37.9 M each; four
    # MoE layers of 104.3 M (32 experts 100.7 M, shared 3.1 M, router
    # 0.5 M); norms
    assert count == 691_623_936
    assert count * 12 < 8.31e9


def test_the_registered_recipe_is_the_cells_share():
    """`train.py -m laguna_xs2` trains what the cell measures: the file's
    arguments that the recipe leaves out are the registered model's
    defaults."""
    import inspect

    from deep_vision_tpu.configs import get_config
    from deep_vision_tpu.models import laguna

    _, config, traffic = real_config()
    recipe = get_config("laguna_xs2")
    defaults = {name: p.default for name, p in inspect.signature(
        laguna.laguna_xs2).parameters.items()}
    left = ("router_experts", "held_offset", "moe_routed_scaling_factor")
    kwargs = {k: v for k, v in config["model_kwargs"].items()
              if k not in left + ("rope_parameters",)}
    assert recipe.model_kwargs == kwargs
    assert all(config["model_kwargs"][k] == defaults[k] for k in left)
    rope = config["model_kwargs"]["rope_parameters"]
    assert defaults["rope_parameters"] is None and all(
        rope[kind] == laguna.ROPE_PARAMETERS[kind]
        for kind in (laguna.FULL, laguna.SLIDING))
    assert (recipe.task, recipe.input_shape) == ("causal_lm", (4096,))
    assert recipe.batch_size == traffic["global_batch"]
    assert recipe.optimizer == config["optimizer"]


# -- the written counts ------------------------------------------------------

def all_held(**over):
    """Every expert held and chosen: 4 experts, 4 a token."""
    return tiny_config(num_experts=4, router_experts=4, held_offset=0,
                       reference_remat=False, reference_unroll=True, **over)


@pytest.mark.parametrize("rows,tokens", [(2, 12), (1, 9)])
def test_step_flops_is_the_jaxprs_count_where_both_can_be_taken(rows,
                                                                tokens):
    """A few tokens, nothing recomputed, every expert held and chosen (so
    the expected pairs are every pair), the scores counted whole as a
    jaxpr of the plain form holds them; then the masks' pairs taken out."""
    plain = all_held()
    spec = {"tokens": jax.ShapeDtypeStruct((rows, tokens), jnp.int32)}
    variables = jax.eval_shape(
        lambda: reference.init(plain, jax.random.PRNGKey(0)))
    counted = flops.flops_of(
        lambda p, b: jax.value_and_grad(
            lambda p: reference.loss_fn(plain, p, {}, b)[0])(p),
        variables["params"], spec)
    assert counted == reference.step_flops(plain, spec, scores="whole")
    masked = reference.step_flops(plain, spec)
    causal_out = tokens * tokens - tokens * (tokens + 1) // 2
    window_out = tokens * tokens - reference.window_pairs(tokens, 8)
    # two full layers of 6 heads, three sliding ones of 8, heads of 16
    assert counted - masked == rows * 12 * 16 * (
        2 * 6 * causal_out + 3 * 8 * window_out)
    assert flops.train_step_flops(reference, plain, spec) == masked


def test_the_cells_step_is_the_written_count():
    _, config, traffic = real_config()
    spec = traffic_mod.batch_spec(traffic, config, (4096,))
    total = flops.train_step_flops(reference, config, spec)
    assert total == config["step_flops"] == 21_521_454_071_808
    # the windows' pairs, and the sliding layers' scores at 12 d a pair
    assert reference.window_pairs(4096, 512) == 512 * 513 // 2 + 3584 * 512
    window = reference.window_attention_flops(config, 2, 4096)
    assert window == 3 * 2 * 64 * 12 * 1_966_336 * 128
    full = 2 * 2 * 48 * 12 * (4096 * 4097 // 2) * 128
    # token-linear 17.89 T (of it the experts' 65,536 pairs a layer 4.95 T),
    # full layers' scores 2.47 T, windows' 1.16 T
    pairs = 4 * 8192 * 8  # every choice computed here (`stand_in`)
    assert total - window - full == pytest.approx(17.89e12, rel=1e-3)
    # the MXU is the roof of the windowed kernels: 5.9 ms against 2.2
    assert window / 197e12 > reference.window_attention_bytes(
        config, 2, 4096, 2) / 819e9
    assert reference.expert_flops(config, pairs) == 18 * 2048 * 512 * pairs
    assert reference.expert_flops(config, pairs) == pytest.approx(4.95e12,
                                                                  rel=1e-3)
    # without the stand-in a held expert's expected share, 1/8 of the pairs
    assert flops.train_step_flops(
        reference, {**config, "stand_in": False}, spec) == pytest.approx(
        total - 7 / 8 * reference.expert_flops(config, pairs))


# -- the cell's files, rehearsed ---------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """A manifest of its own under tmp_path: the cell's configuration at
    the tiny size, a traffic file of 8 rows (a row a device of the CPU
    mesh), tight limits, and the metrics that wait, each with a
    `workloads` list."""
    root = tmp_path_factory.mktemp("laguna_rehearsal")
    for sub in ("configs", "cells", "traffic"):
        os.makedirs(root / sub)
    cell, _, traffic = real_config()
    with open(root / "configs" / "tiny_laguna.json", "w") as f:
        json.dump(tiny_config(), f)
    with open(root / "traffic" / "tok32_b8_pool4.json", "w") as f:
        json.dump({**traffic, "global_batch": 8, "seq_len": 32}, f)
    with open(root / "cells" / "tiny_laguna_train.json", "w") as f:
        json.dump({"limits": {"loss_gap": 1e-4, "grad_gap": 4e-3,
                              "delta_gap": 1e-3},
                   "window_kernel_ops": cell["window_kernel_ops"],
                   "expert_kernel_ops": cell["expert_kernel_ops"]}, f)
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    manifest = {
        **real, "paths": real["paths"] + [str(root)],
        "configs": [{"name": "tiny_laguna", "source": "a CPU rehearsal",
                     "file": str(root / "configs" / "tiny_laguna.json"),
                     "reduced": [], "why": "rehearsal"}],
        "workloads": [{"name": "tiny_laguna_train", "config": "tiny_laguna",
                       "traffic": "tok32_b8_pool4", "chips": 8,
                       "why": "rehearsal"}],
        "per_layer": [{**m, "workloads": ["tiny_laguna_train"]}
                      if "workloads" in m else m for m in real["per_layer"]]
        + [{**m, "moves": "img_per_s_chip", "workloads": ["tiny_laguna_train"]}
           for m in NEW_METRICS]}
    path = root / "manifest.json"
    with open(path, "w") as f:
        json.dump(manifest, f)
    return str(path)


def test_the_cell_rehearsed_through_run_py_is_correct(rehearsal):
    """Three steps through `Trainer.fit` with AdamW's moments stored in
    bfloat16 against the reference's three, at the judge's limits; the
    expert layer's counters are the program's."""
    from deep_vision_tpu.obs.registry import get_registry

    def counted():
        return {(m.name, tuple(sorted(m.labels.items()))): m.value
                for m in get_registry().metrics() if m.kind == "counter"}

    before = counted()
    manifest = run.load_manifest(rehearsal)
    result = run.run_cell(manifest, "tiny_laguna_train", 2 ** 31 + 17, 0.3,
                          0, require_chip=False)
    assert result["correct"], (result["compared"], result["faults"])
    assert set(result["metrics"]) == {"img_per_s_chip", "step_ms_p95",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    counters = counted()
    grew = lambda key: counters[key] - before.get(key, 0)
    assert grew(("rotary_sites_total", (("kind", "yarn"),))) >= 2
    assert grew(("rotary_sites_total", (("kind", "default"),))) >= 3
    steps = grew(("train_steps_total", ()))
    pairs = grew(("moe_routed_pairs_total", ()))
    # 8 x 32 tokens a step, 4 choices of 16 experts, 4 held, 4 MoE layers:
    # every choice computed by the held expert in its place (`stand_in`),
    # in every step the fit and its warm-up ran
    assert steps > 0 and pairs >= steps * 4 * 8 * 32 * 4
    assert pairs % (4 * 8 * 32 * 4) == 0


def test_the_lower_precision_control_and_half_the_rows_fail(rehearsal):
    cell, config, traffic = run.resolve(run.load_manifest(rehearsal),
                                        "tiny_laguna_train")
    pool = traffic_mod.make_pool(traffic, config, (32,), 5)
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


def record(ops, cell=None, reference_name="laguna"):
    _, config, _ = real_config()
    return {"trace": {"op_s_per_step": ops} if ops is not None else None,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "config": {**config, "reference": reference_name},
            "global_batch": 2, "chips": 1,
            "batch_spec": {"tokens": jax.ShapeDtypeStruct((2, 4096),
                                                          jnp.int32)},
            "cell": {"name": "tiny_laguna_train",
                     "window_kernel_ops": ["flash_window_fwd",
                                           "flash_window_bwd_dq",
                                           "flash_window_bwd_dkv"]}
            if cell is None else cell}


def waiting_alone(path):
    manifest = run.load_manifest(path)
    names = {m["name"] for m in NEW_METRICS}
    return {**manifest, "per_layer": [m for m in manifest["per_layer"]
                                      if m["name"] in names]}


def test_the_window_metrics_read_their_hand_worked_numbers_or_nothing(
        rehearsal):
    manifest = waiting_alone(rehearsal)
    ops = {"flash_window_fwd": 0.002, "flash_window_fwd.3": 0.002,
           "flash_window_bwd_dq.1": 0.004, "flash_window_bwd_dkv.7": 0.006,
           # not the windowed kernels': the full layers' causal ones, and
           # names that share letters
           "flash_fwd.2": 5.0, "flash_bwd_dq.1": 5.0, "fusion.3": 5.0,
           "flash_window_fwd_other": 7.0}
    got = run.read_metrics(manifest, "per_layer", record(ops))
    assert got["window_attention_ms"] == {"value": pytest.approx(14.0),
                                          "unit": "ms"}
    # the MXU's roof: 1.16 TFLOP over 197 TFLOP/s in 14 ms
    _, config, _ = real_config()
    least = 3 * 2 * 64 * 12 * 1_966_336 * 128 / 197e12
    assert got["window_attention_roofline_pct"]["value"] == pytest.approx(
        least / 0.014 * 100)
    # nothing to read: no trace, a cell that names no ops, a step with none
    # (the parent's program, whose attention kernels are all causal)
    for rec in (record(None), record(ops, cell={"name": "tiny_laguna_train"}),
                record({"flash_fwd.2": 5.0, "fusion.3": 5.0})):
        assert run.read_metrics(manifest, "per_layer", rec) == {}
    # a reference that writes no windowed count reads no share
    left = run.read_metrics(manifest, "per_layer",
                            record(ops, reference_name="solar_open2"))
    assert set(left) == {"window_attention_ms"}


def test_the_new_metrics_are_files_not_yet_listed():
    """They enter `BENCHMARK.json` with a `benchmark` PR
    (`test_benchmark.py` holds `waiting.json`'s `per_layer` equal to it
    until then): until then a file each, and this test. The manifest's
    other limits hold with the new cell in it."""
    real = run.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in real["per_layer"] + real["end_to_end"]}
    for metric in NEW_METRICS:
        assert metric["name"] not in listed
        assert callable(run.load_py(run.find_file(
            real, "metrics", metric["name"] + ".py")).read)
    cells = real["workloads"]
    assert len(cells) <= 24 and len(real["configs"]) <= 24
    four = [c for c in cells if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert [c["name"] for c in cells].count(CELL) == 1
