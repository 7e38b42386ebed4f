"""Experiment config registry: configs are data, selected by name.

The reference keeps each paper's recipe in per-model `training_config` dicts
chosen by the `-m` CLI flag (ResNet/pytorch/train.py:26-215,
LeNet/pytorch/train.py:15-32, ResNet/tensorflow/train.py:21-62,
MobileNet/tensorflow/train.py:7-14, module constants at
YOLO/tensorflow/train.py:13-17 and CycleGAN/tensorflow/train.py:14-21).
This registry carries the union of all of them — one shared schema, every
hyperparameter value preserved (the paper-recipe comments in the reference
map to the fields here).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass
class ExperimentConfig:
    name: str
    # classification | detection | pose | centernet | causal_lm (Trainer);
    # dcgan | cyclegan (their own trainers)
    task: str
    model: str
    model_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    input_shape: Tuple[int, ...] = (224, 224, 3)  # causal_lm: (tokens,)
    num_classes: int = 1000
    batch_size: int = 128  # global batch (reference: per-replica x replicas)
    epochs: int = 90
    optimizer: Dict[str, Any] = dataclasses.field(
        default_factory=lambda: {"name": "sgd", "learning_rate": 0.01}
    )
    schedule: Optional[Dict[str, Any]] = None  # make_schedule kwargs
    plateau: Optional[Dict[str, Any]] = None  # ReduceLROnPlateau kwargs
    plateau_metric: str = "top1"
    dataset: Dict[str, Any] = dataclasses.field(
        default_factory=lambda: {"kind": "fake"}
    )
    loss_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    eval_crop: int = 224
    train_resize: int = 256

    def __post_init__(self):
        # One LR policy per recipe, as in every reference config
        # (ResNet/pytorch/train.py:26-215 picks either a torch scheduler OR
        # plateau, never both). Allowing both would be a silent no-op:
        # inject_hyperparams re-evaluates a scheduled LR every step,
        # overwriting whatever absolute value the plateau wrote between
        # epochs (train/trainer.py _set_lr).
        if self.schedule is not None and self.plateau is not None:
            raise ValueError(
                f"config '{self.name}' sets both 'schedule' and 'plateau': "
                "a scheduled learning rate is re-evaluated inside the jitted "
                "step and would silently override plateau scaling — pick one "
                "LR policy"
            )


CONFIG_REGISTRY: Dict[str, ExperimentConfig] = {}


def register_config(cfg: ExperimentConfig) -> ExperimentConfig:
    CONFIG_REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ExperimentConfig:
    if name not in CONFIG_REGISTRY:
        raise KeyError(f"unknown config '{name}'; have {sorted(CONFIG_REGISTRY)}")
    return dataclasses.replace(CONFIG_REGISTRY[name])  # copy: callers mutate


# -- classifiers (ImageNet unless noted) ------------------------------------

register_config(ExperimentConfig(
    # LeNet/pytorch/train.py:15-32: Adam 1e-3, plateau(max, 0.1), batch 64
    name="lenet5", task="classification", model="lenet5",
    input_shape=(32, 32, 1), num_classes=10, batch_size=64, epochs=50,
    optimizer={"name": "adam", "learning_rate": 1e-3},
    plateau={"factor": 0.1, "mode": "max"},
    dataset={"kind": "mnist"},
))

for _name, _model, _bs, _wd in (
    # ResNet/pytorch/train.py:26-48 (alexnet1/2): SGD .01/.9/5e-4, plateau
    ("alexnet1", "alexnet1", 128, 5e-4),
    ("alexnet2", "alexnet2", 128, 5e-4),
):
    register_config(ExperimentConfig(
        name=_name, task="classification", model=_model,
        batch_size=_bs, epochs=90,
        optimizer={"name": "sgd", "learning_rate": 0.01, "momentum": 0.9,
                   "weight_decay": _wd},
        plateau={"factor": 0.1, "mode": "max"},
        dataset={"kind": "imagenet"},
    ))

for _name, _model, _bs in (("vgg16", "vgg16", 128), ("vgg19", "vgg19", 64)):
    # ResNet/pytorch/train.py:50-92: SGD .01/.9/5e-4, StepLR(10, 0.5)
    register_config(ExperimentConfig(
        name=_name, task="classification", model=_model,
        batch_size=_bs, epochs=90,
        optimizer={"name": "sgd", "learning_rate": 0.01, "momentum": 0.9,
                   "weight_decay": 5e-4},
        schedule={"kind": "step", "step_size_epochs": 10, "gamma": 0.5},
        dataset={"kind": "imagenet"},
    ))

register_config(ExperimentConfig(
    # ResNet/pytorch/train.py:94-140: SGD .01/.9/2e-4, poly decay sqrt
    name="inception1", task="classification", model="inception1",
    batch_size=128, epochs=90,
    optimizer={"name": "sgd", "learning_rate": 0.01, "momentum": 0.9,
               "weight_decay": 2e-4},
    schedule={"kind": "poly", "power": 0.5, "total_epochs": 60},
    dataset={"kind": "imagenet"},
    loss_kwargs={"aux_weight": 0.3},
))

register_config(ExperimentConfig(
    # finished properly here; reference stub is 6 lines (inception_v3.py)
    name="inception3", task="classification", model="inception3",
    input_shape=(299, 299, 3), batch_size=128, epochs=100,
    optimizer={"name": "rmsprop", "learning_rate": 0.045, "alpha": 0.9,
               "eps": 1.0},
    schedule={"kind": "step", "step_size_epochs": 2, "gamma": 0.94},
    dataset={"kind": "imagenet"}, train_resize=320, eval_crop=299,
))

for _name, _model, _mkw in (
    ("resnet34", "resnet34", {}),
    # flagship: space-to-depth stem (math-equal to conv7, ~3% faster on TPU;
    # models/resnet.py SpaceToDepthStem) — the program of the benchmark's
    # resnet50 cells
    ("resnet50", "resnet50", {"stem": "s2d"}),
    ("resnet152", "resnet152", {}), ("resnet50v2", "resnet50v2", {}),
):
    # ResNet/pytorch/train.py:142-215: SGD .1/.9/1e-4, batch 256, plateau(max)
    register_config(ExperimentConfig(
        name=_name, task="classification", model=_model,
        model_kwargs=_mkw, batch_size=256, epochs=90,
        optimizer={"name": "sgd", "learning_rate": 0.1, "momentum": 0.9,
                   "weight_decay": 1e-4},
        plateau={"factor": 0.1, "mode": "max"},
        dataset={"kind": "imagenet"},
    ))

register_config(ExperimentConfig(
    # ResNet/pytorch/train.py:185-214: RMSprop .045/alpha .9/eps 1, StepLR(2,.94)
    name="mobilenet1", task="classification", model="mobilenet1",
    model_kwargs={"alpha": 1.0}, batch_size=128, epochs=90,
    optimizer={"name": "rmsprop", "learning_rate": 0.045, "alpha": 0.9,
               "eps": 1.0},
    schedule={"kind": "step", "step_size_epochs": 2, "gamma": 0.94},
    dataset={"kind": "imagenet"},
))

register_config(ExperimentConfig(
    # implemented for real here (reference ships a 0-byte file, SURVEY.md §2.9);
    # recipe from the ShuffleNet paper: SGD, linear decay
    name="shufflenet1", task="classification", model="shufflenet1",
    model_kwargs={"groups": 3}, batch_size=256, epochs=90,
    optimizer={"name": "sgd", "learning_rate": 0.1, "momentum": 0.9,
               "weight_decay": 4e-5},
    schedule={"kind": "poly", "power": 1.0, "total_epochs": 90},
    dataset={"kind": "imagenet"},
))

# -- detection / pose / generative ------------------------------------------

register_config(ExperimentConfig(
    # YOLO/tensorflow/train.py:13-17,46-47: Adam 1e-3, batch 16/replica,
    # 416 input, 80 classes (COCO), manual plateau on val loss :56-68
    name="yolov3_coco", task="detection", model="yolov3",
    input_shape=(416, 416, 3), num_classes=80, batch_size=16, epochs=300,
    optimizer={"name": "adam", "learning_rate": 1e-3},
    plateau={"factor": 0.3, "patience": 5, "mode": "min"},
    plateau_metric="loss",
    dataset={"kind": "records", "schema": "coco"},
))

register_config(ExperimentConfig(
    name="yolov3_voc", task="detection", model="yolov3",
    input_shape=(416, 416, 3), num_classes=20, batch_size=16, epochs=300,
    optimizer={"name": "adam", "learning_rate": 1e-3},
    plateau={"factor": 0.3, "patience": 5, "mode": "min"},
    plateau_metric="loss",
    dataset={"kind": "records", "schema": "voc"},
))

register_config(ExperimentConfig(
    # Hourglass/tensorflow/main.py:21-43 defaults: Adam, 64x64x16 heatmaps
    name="hourglass_mpii", task="pose", model="hourglass",
    model_kwargs={"num_stack": 4, "num_heatmap": 16},
    input_shape=(256, 256, 3), num_classes=16, batch_size=16, epochs=100,
    optimizer={"name": "adam", "learning_rate": 2.5e-4},
    plateau={"factor": 0.5, "patience": 5, "mode": "min"},
    plateau_metric="loss",
    dataset={"kind": "records", "schema": "mpii"},
))

register_config(ExperimentConfig(
    # ObjectsAsPoints completed (reference never finished the losses,
    # train.py:35): paper recipe Adam 1.25e-4
    name="centernet_coco", task="centernet", model="objects_as_points",
    model_kwargs={"num_stack": 2},
    input_shape=(512, 512, 3), num_classes=80, batch_size=32, epochs=140,
    optimizer={"name": "adam", "learning_rate": 1.25e-4},
    schedule={"kind": "step", "step_size_epochs": 90, "gamma": 0.1},
    dataset={"kind": "records", "schema": "coco"},
))

register_config(ExperimentConfig(
    # DCGAN/tensorflow/main.py:13-17,42-53: Adam 1e-4, batch 256, MNIST
    name="dcgan_mnist", task="dcgan", model="dcgan",
    input_shape=(28, 28, 1), batch_size=256, epochs=50,
    optimizer={"name": "adam", "learning_rate": 1e-4},
    dataset={"kind": "mnist"},
))

register_config(ExperimentConfig(
    # CycleGAN/tensorflow/train.py:14-21,126-131: Adam 2e-4 beta1 .5,
    # batch 1, 200 epochs, linear decay after 100
    name="cyclegan", task="cyclegan", model="cyclegan",
    input_shape=(256, 256, 3), batch_size=1, epochs=200,
    optimizer={"name": "adam", "learning_rate": 2e-4, "b1": 0.5},
    schedule={"kind": "linear_decay", "hold_epochs": 100, "total_epochs": 200},
    dataset={"kind": "records", "schema": "image_only"},
))

# -- attention family (net-new; no reference counterpart) -------------------

for _name, _model, _mkw in (
    ("vit_s16", "vit_s16", {}),
    ("vmoe_s16", "vmoe_s16", {}),
):
    # AdamW recipe (ViT paper, app. B.1 scaled to single-host): decoupled
    # weight decay, linear warmup + cosine decay via the schedule registry
    register_config(ExperimentConfig(
        name=_name, task="classification", model=_model,
        model_kwargs=_mkw, batch_size=256, epochs=90,
        optimizer={"name": "adamw", "learning_rate": 1e-3,
                   "weight_decay": 1e-4},
        schedule={"kind": "cosine", "warmup_epochs": 5,
                  "total_epochs": 90},
        dataset={"kind": "imagenet"},
    ))

# -- token-sequence decoders (net-new; no reference counterpart) -------------

register_config(ExperimentConfig(
    # allenai/Olmo-Hybrid-7B at its published widths and depth; the recipe
    # is OLMo 2's (arXiv:2501.00656): AdamW 3e-4, betas 0.9 / 0.95, decay
    # 0.1, warm-up then cosine. Two sequences of 2048 tokens a chip is what
    # 16 GB holds of it beside a pipeline stage's state; token data has no
    # reader yet, so the dataset is the fake one (ROADMAP B2)
    name="olmo_hybrid_7b", task="causal_lm", model="olmo_hybrid_7b",
    input_shape=(2048,), batch_size=2, epochs=1,
    optimizer={"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
               "b2": 0.95, "weight_decay": 0.1},
    schedule={"kind": "cosine", "warmup_epochs": 5, "total_epochs": 90},
    dataset={"kind": "fake"},
))

register_config(ExperimentConfig(
    # upstage/Solar-Open2-250B at its published widths, as one chip of the
    # 40 that share a 4-layer stage holds it: experts 0-7 of 320 (the
    # router keeps all 320), half the heads (2-way tensor parallel), an
    # eighth of the vocabulary. The recipe is OLMo 2's, as olmo_hybrid_7b's
    # (the row gives none); two sequences of 2048 tokens is what 16 GB
    # holds beside the stage's 12.4 GB training state
    name="solar_open2_250b", task="causal_lm", model="solar_open2_250b",
    model_kwargs={"num_hidden_layers": 4, "vocab_size": 24576,
                  "num_attention_heads": 32, "num_key_value_heads": 4,
                  "linear_num_heads": 32, "n_routed_experts": 8},
    input_shape=(2048,), batch_size=2, epochs=1,
    optimizer={"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
               "b2": 0.95, "weight_decay": 0.1},
    schedule={"kind": "cosine", "warmup_epochs": 5, "total_epochs": 90},
    dataset={"kind": "fake"},
))

register_config(ExperimentConfig(
    # poolside/Laguna-XS.2 at its published widths, as one chip of the 8
    # that share each layer by expert parallelism holds it: the leading
    # dense layer and one period [sliding x 3, full] of 39, experts 0-31 of
    # 256 (the router keeps all 256; the 224 held elsewhere computed by
    # the held expert in their place, `stand_in`), every head, an eighth
    # of the vocabulary. The recipe is OLMo 2's, as the other decoders'
    # (the row gives none); two sequences of 4096 tokens, the pre-training
    # context
    name="laguna_xs2", task="causal_lm", model="laguna_xs2",
    model_kwargs={"num_hidden_layers": 5, "vocab_size": 12544,
                  "num_experts": 32, "stand_in": True},
    input_shape=(4096,), batch_size=2, epochs=1,
    optimizer={"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
               "b2": 0.95, "weight_decay": 0.1},
    schedule={"kind": "cosine", "warmup_epochs": 5, "total_epochs": 90},
    dataset={"kind": "fake"},
))
