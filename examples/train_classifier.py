"""Quickstart: train a classifier end to end (the LeNet.ipynb analog).

The reference walks this flow in per-model notebooks
(LeNet/pytorch/LeNet.ipynb, VGG/pytorch/VGG16.ipynb); here it is an
executable script against the library API. Swap the model name for any
registered classifier (resnet50, vit_s16, ...) — the Trainer, loss, and
checkpointing are shared across the whole zoo.

    python examples/train_classifier.py [--model lenet5] [--epochs 3]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


import argparse
import tempfile

import jax.numpy as jnp
import numpy as np

from deep_vision_tpu.core import CheckpointManager
from deep_vision_tpu.losses import classification_loss_fn
from deep_vision_tpu.models import get_model
from deep_vision_tpu.train import Trainer, build_optimizer


def quadrant_data(n=256, size=32, seed=0):
    """Synthetic 4-class stand-in for MNIST: class = brightest quadrant."""
    rng = np.random.RandomState(seed)
    images = rng.rand(n, size, size, 1).astype(np.float32) * 0.1
    labels = rng.randint(0, 4, size=n)
    half = size // 2
    for i, l in enumerate(labels):
        r, c = divmod(l, 2)
        images[i, r * half:(r + 1) * half, c * half:(c + 1) * half, 0] += 0.9
    return images, labels


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="lenet5")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--ckpt-dir", default=None)
    args = p.parse_args()

    images, labels = quadrant_data()

    def batches():
        for i in range(0, len(images) - 32 + 1, 32):
            yield {"image": images[i:i + 32], "label": labels[i:i + 32]}

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="dv_example_")
    trainer = Trainer(
        get_model(args.model, num_classes=4),
        build_optimizer("adam", 1e-3),
        classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)),
        checkpoint_manager=CheckpointManager(ckpt_dir),
        ema_decay=0.99,  # evaluate with EMA weights
    )
    trainer.fit(batches, batches, epochs=args.epochs)
    metrics = trainer.eval_step({"image": images[:64], "label": labels[:64]})
    print(f"final top-1 {float(metrics['top1']):.3f}  (checkpoints in {ckpt_dir})")


if __name__ == "__main__":
    main()
