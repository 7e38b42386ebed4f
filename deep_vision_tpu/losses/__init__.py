from deep_vision_tpu.losses.causal_lm import causal_lm_loss_fn
from deep_vision_tpu.losses.classification import (
    cross_entropy_loss,
    classification_loss_fn,
)
from deep_vision_tpu.losses.heatmap import (
    centernet_focal_loss,
    centernet_loss_fn,
    hourglass_loss_fn,
)
from deep_vision_tpu.losses.yolo import (
    yolo_loss_fn,
    yolo_loss_per_scale,
    yolo_train_loss_fn,
)
from deep_vision_tpu.losses import gan

__all__ = [
    "causal_lm_loss_fn",
    "cross_entropy_loss",
    "classification_loss_fn",
    "centernet_focal_loss",
    "centernet_loss_fn",
    "hourglass_loss_fn",
    "yolo_loss_fn",
    "yolo_loss_per_scale",
    "yolo_train_loss_fn",
    "gan",
]
