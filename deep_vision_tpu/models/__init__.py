"""Model zoo registry.

Every model the reference ships (README.md:5 table) plus the ones it left
broken (ShuffleNet V1, Inception V3, ObjectsAsPoints — SURVEY.md §2.9) which
are implemented properly here. Models register by name so configs select them
the way `training_config['model']` did (ResNet/pytorch/train.py:26-215).
"""
from __future__ import annotations

from typing import Callable, Dict

MODEL_REGISTRY: Dict[str, Callable] = {}


def register_model(name: str):
    def deco(fn):
        MODEL_REGISTRY[name] = fn
        return fn

    return deco


def get_model(name: str, **kwargs):
    if name not in MODEL_REGISTRY:
        raise KeyError(f"unknown model '{name}'; have {sorted(MODEL_REGISTRY)}")
    return MODEL_REGISTRY[name](**kwargs)


# importing the modules populates the registry
from deep_vision_tpu.models import lenet  # noqa: E402,F401
from deep_vision_tpu.models import alexnet  # noqa: E402,F401
from deep_vision_tpu.models import vgg  # noqa: E402,F401
from deep_vision_tpu.models import inception  # noqa: E402,F401
from deep_vision_tpu.models import resnet  # noqa: E402,F401
from deep_vision_tpu.models import mobilenet  # noqa: E402,F401
from deep_vision_tpu.models import shufflenet  # noqa: E402,F401
from deep_vision_tpu.models import yolov3  # noqa: E402,F401
from deep_vision_tpu.models import hourglass  # noqa: E402,F401
from deep_vision_tpu.models import centernet  # noqa: E402,F401
from deep_vision_tpu.models import dcgan  # noqa: E402,F401
from deep_vision_tpu.models import cyclegan  # noqa: E402,F401
from deep_vision_tpu.models import vit  # noqa: E402,F401
from deep_vision_tpu.models import olmo_hybrid  # noqa: E402,F401
from deep_vision_tpu.models import solar_open2  # noqa: E402,F401
from deep_vision_tpu.models import laguna  # noqa: E402,F401
