"""Real photographs end-to-end: converter -> records -> DataLoader ->
fine-tune -> labeled inference overlays.

The script form of the reference's classify-a-real-photo demo
(`ResNet50.ipynb`: load a real image, run the classifier, show the label),
driven through every real subsystem instead of a notebook shortcut: the
three license-clean photographs in `tests/fixtures/real_photos/` go through
the ImageNet converter into record shards, the DataLoader decodes and
augments the actual JPEG bytes, a zoo classifier fine-tunes to the three
classes with the Trainer, and `tools/infer.py --render` restores the
checkpoint and writes `*_classified.jpg` display copies with the predicted
label drawn.

    python examples/real_photo_demo.py                # ~2-4 min on CPU
    python examples/real_photo_demo.py --model resnet50 --steps 80

Committed sample outputs: `output/demo_real_*_classified.jpg`.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
FIXTURES = os.path.join(REPO, "tests", "fixtures", "real_photos")
PHOTOS = ("grace_hopper.jpg", "china.jpg", "flower.jpg")
SYNSETS = ("n10000001", "n10000002", "n10000003")
# model class index i = converter label i+1 mapped down by the dataset;
# index 0..2 after the records round trip
NAMES = ("Grace Hopper (US Navy portrait)",
         "pagoda (Summer Palace)",
         "orange dahlia")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="mobilenet1",
                   help="any classification config name (configs registry)")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--out", default=os.path.join(REPO, "examples", "output"))
    p.add_argument("--workdir", default=None,
                   help="records + checkpoint dir (default: a temp dir)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deep_vision_tpu.configs import get_config
    from deep_vision_tpu.core import CheckpointManager
    from deep_vision_tpu.data import Compose, DataLoader, RecordDataset
    from deep_vision_tpu.data import transforms as T
    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.tools import infer
    from deep_vision_tpu.tools.converters import (
        build_shards,
        imagenet_annotations,
        imagenet_example,
    )
    from deep_vision_tpu.train import Trainer, build_optimizer

    cfg = get_config(args.model)
    assert cfg.task == "classification", "pick a classification config"
    work = args.workdir or tempfile.mkdtemp(prefix="real_photo_demo_")
    os.makedirs(work, exist_ok=True)

    # 1. real JPEGs -> the converter's flattened layout -> record shards
    flat = os.path.join(work, "flat")
    os.makedirs(flat, exist_ok=True)
    for synset, photo in zip(SYNSETS, PHOTOS):
        shutil.copy(os.path.join(FIXTURES, photo),
                    os.path.join(flat, f"{synset}_{photo}".replace(".jpg",
                                                                   ".JPEG")))
    synsets_txt = os.path.join(work, "synsets.txt")
    with open(synsets_txt, "w") as f:
        f.write("".join(s + "\n" for s in SYNSETS))
    records = os.path.join(work, "records")
    build_shards(imagenet_annotations(flat, synsets_txt), imagenet_example,
                 records, "train", num_shards=1)

    # 2. the real input pipeline over the records (decode + augment + batch)
    crop = cfg.eval_crop
    chain = Compose([
        T.Rescale(cfg.train_resize), T.RandomHorizontalFlip(),
        T.RandomCrop(crop), T.ToFloatNormalize(expand_gray_to_rgb=True),
    ])
    loader = DataLoader(RecordDataset(records + "/*", "imagenet"),
                        batch_size=3, transform=chain, shuffle=True,
                        drop_remainder=True)

    # 3. fine-tune to the three classes (memorization recipe: Adam, no
    # schedule — the demo's point is the path, not the recipe)
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      **cfg.model_kwargs)
    tx = build_optimizer("adam", args.lr)
    sample = jnp.ones((2, crop, crop, 3), jnp.float32)
    if cfg.model_kwargs.get("stem") == "s2d":
        sample = jnp.ones((2, crop // 2, crop // 2, 12), jnp.float32)
    ckpt_dir = os.path.join(work, "ckpt")
    trainer = Trainer(model, tx, classification_loss_fn, sample,
                      checkpoint_manager=CheckpointManager(ckpt_dir))

    def batches():
        s2d = cfg.model_kwargs.get("stem") == "s2d"
        for batch in loader:
            img = batch["image"]
            if s2d:
                from deep_vision_tpu.data.transforms import space_to_depth

                img = np.stack([space_to_depth(im) for im in img])
            yield {"image": jnp.asarray(img),
                   "label": jnp.asarray(batch["label"])}

    # one loader pass = one 3-image batch, so epochs == optimizer steps;
    # fit() checkpoints through the manager as it goes
    trainer.fit(batches, eval_data_fn=None, epochs=args.steps,
                save_every=args.steps)
    final = trainer.evaluate(batches(), epoch=args.steps)
    print(f"fine-tuned {args.model} {args.steps} steps: "
          f"loss={float(final['loss']):.4f} top1={float(final['top1']):.2f}")
    if float(final["top1"]) < 1.0:
        print("warning: did not fully memorize; overlays may be mislabeled")

    # 4. the inference CLI restores the checkpoint and renders the overlays
    names_txt = os.path.join(work, "names.txt")
    with open(names_txt, "w") as f:
        f.write("".join(n + "\n" for n in NAMES))
    os.makedirs(args.out, exist_ok=True)
    srcs = []
    for photo in PHOTOS:  # demo_real_* output names, distinct from inputs
        dst = os.path.join(work, "demo_real_" + photo)
        shutil.copy(os.path.join(FIXTURES, photo), dst)
        srcs.append(dst)
    rc = infer.main(["-m", args.model, "-c", ckpt_dir, "-o", args.out,
                     "--render", "--labels", names_txt, *srcs])
    print(f"overlays in {args.out}/demo_real_*_classified.jpg")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
