"""Image-in, result-out inference CLI for every task family.

The script form of the reference's demo surfaces — per-model notebooks
(ResNet50.ipynb, demo_mscoco.ipynb, demo_hourglass_pose.ipynb — SURVEY.md §4)
and the CycleGAN inference script (CycleGAN/tensorflow/inference.py:11-70:
restore checkpoint, run the generator over a folder, save outputs):

    python -m deep_vision_tpu.tools.infer -m resnet50 -c ck/ img1.jpg img2.jpg
    python -m deep_vision_tpu.tools.infer -m yolov3_voc -c ck/ street.jpg
    python -m deep_vision_tpu.tools.infer -m hourglass_mpii -c ck/ person.jpg
    python -m deep_vision_tpu.tools.infer -m cyclegan -c ck/ photo.jpg -o out/

Classification prints top-5; detection prints NMS'd boxes (and writes a
..._boxes.txt sidecar); pose prints per-joint (x, y, score); GAN configs run
the generator and save translated JPEGs next to the inputs (or under -o).
"""
from __future__ import annotations

import argparse
import os
from typing import List, Optional

import numpy as np


def _load_image(path: str, size: int, normalize: str, rescale: int = 0):
    """Decode + the EXACT eval chain training used (train_cli eval_tf):
    mismatched normalization silently wrecks predictions, so the chains here
    mirror build_dataloaders' eval branches per `normalize` mode."""
    from deep_vision_tpu.data.datasets import decode_image
    from deep_vision_tpu.data import transforms as T

    with open(path, "rb") as f:
        img = decode_image(f.read())
    sample = {"image": img}
    rng = np.random.default_rng(0)
    if normalize == "imagenet":  # torch chain (train_cli eval_tf)
        chain = [T.Rescale(rescale or size + 32), T.CenterCrop(size),
                 T.ToFloatNormalize(expand_gray_to_rgb=True)]
    elif normalize == "imagenet_tf":  # the 0-255 mean-subtraction chain
        chain = [T.Rescale(rescale or size + 32), T.CenterCrop(size),
                 T.ToFloat(expand_gray_to_rgb=True, scale=False),
                 T.MeanSubtract()]
    elif normalize == "unit":  # [0,1]
        chain = [T.Resize(size), T.ToFloat(expand_gray_to_rgb=True)]
    else:  # [-1,1] (GANs)
        chain = [T.Resize(size), T.ToFloat(expand_gray_to_rgb=True),
                 T.Normalize(mean=[0.5] * 3, std=[0.5] * 3)]
    for t in chain:
        sample = t(sample, rng)
    return sample["image"]


# MPII skeleton: limb edges drawn between joint indices (r-leg, l-leg,
# spine/head, r-arm, l-arm) — the demo overlay of
# demo_hourglass_pose.ipynb as data
POSE_SKELETON = ((0, 1), (1, 2), (2, 6), (3, 6), (3, 4), (4, 5), (6, 7),
                 (7, 8), (8, 9), (10, 11), (11, 12), (12, 7), (13, 7),
                 (13, 14), (14, 15))
_PALETTE = ((255, 99, 71), (60, 179, 113), (65, 105, 225), (255, 215, 0),
            (186, 85, 211), (0, 206, 209), (255, 140, 0), (154, 205, 50))


def _write_jpeg(dst: str, rgb_u8: np.ndarray) -> None:
    """RGB uint8 -> JPEG on disk; cv2 when present, PIL otherwise (cv2 is
    optional everywhere in this package)."""
    try:
        import cv2

        if not cv2.imwrite(dst, rgb_u8[..., ::-1]):  # RGB -> BGR for cv2
            raise IOError(f"cv2.imwrite returned False for {dst}")
    except Exception:  # cv2 may fail at load time with OSError, not ImportError
        from PIL import Image

        Image.fromarray(rgb_u8).save(dst, quality=95)


def _reload_rgb(path: str, size: int) -> np.ndarray:
    """The display copy: decoded + resized, NOT normalized."""
    from deep_vision_tpu.data.datasets import decode_image
    from deep_vision_tpu.data import transforms as T

    with open(path, "rb") as f:
        img = decode_image(f.read())
    s = T.Resize(size)({"image": img}, np.random.default_rng(0))
    return np.ascontiguousarray(s["image"][..., :3])


def draw_detections(image: np.ndarray, boxes, scores, classes,
                    class_names=None) -> np.ndarray:
    """Box + label overlay on an RGB uint8 image; normalized [x1,y1,x2,y2]
    boxes (the rendered-output parity of demo_mscoco.ipynb)."""
    import cv2

    out = image.copy()
    h, w = out.shape[:2]
    for b, s, c in zip(boxes, scores, classes):
        color = _PALETTE[int(c) % len(_PALETTE)]
        x1, y1 = int(b[0] * w), int(b[1] * h)
        x2, y2 = int(b[2] * w), int(b[3] * h)
        cv2.rectangle(out, (x1, y1), (x2, y2), color, 2)
        name = (class_names[int(c)] if class_names
                and 0 <= int(c) < len(class_names) else f"class {int(c)}")
        label = f"{name} {float(s):.2f}"
        (tw, th), _ = cv2.getTextSize(label, cv2.FONT_HERSHEY_SIMPLEX, 0.5, 1)
        ty = y1 - 4 if y1 - th - 8 >= 0 else y2 + th + 4
        cv2.rectangle(out, (x1, ty - th - 4), (x1 + tw + 2, ty + 2), color, -1)
        cv2.putText(out, label, (x1 + 1, ty - 2), cv2.FONT_HERSHEY_SIMPLEX,
                    0.5, (255, 255, 255), 1, cv2.LINE_AA)
    return out


def draw_classification(image: np.ndarray, label: str,
                        prob: float) -> np.ndarray:
    """Top-1 label banner on an RGB uint8 image (the rendered-output parity
    of ResNet50.ipynb's classify-a-real-photo demo). PIL text, so the
    classification overlay stays cv2-free like the rest of this path."""
    from PIL import Image, ImageDraw

    im = Image.fromarray(image)
    d = ImageDraw.Draw(im, "RGBA")
    h = max(20, image.shape[0] // 14)
    d.rectangle([0, 0, image.shape[1], h], fill=(0, 0, 0, 190))
    d.text((8, max(3, h // 4)), f"{label}  {prob:.2f}",
           fill=(255, 255, 255, 255))
    return np.asarray(im)


def draw_pose(image: np.ndarray, kpts, score_threshold: float = 0.1,
              skeleton=POSE_SKELETON) -> np.ndarray:
    """Joint dots + skeleton limbs; kpts (J, 3) = normalized x, y, score
    (the rendered-output parity of demo_hourglass_pose.ipynb)."""
    import cv2

    out = image.copy()
    h, w = out.shape[:2]
    pts = [(int(x * w), int(y * h)) if s >= score_threshold else None
           for x, y, s in np.asarray(kpts, np.float32)]
    for e, (a, b) in enumerate(skeleton):
        if a < len(pts) and b < len(pts) and pts[a] and pts[b]:
            cv2.line(out, pts[a], pts[b], _PALETTE[e % len(_PALETTE)], 2,
                     cv2.LINE_AA)
    for p in pts:
        if p:
            cv2.circle(out, p, 3, (255, 255, 255), -1, cv2.LINE_AA)
            cv2.circle(out, p, 3, (30, 30, 30), 1, cv2.LINE_AA)
    return out


def _restore_variables(model, sample, ckpt_dir: Optional[str]):
    import jax
    import jax.numpy as jnp

    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        jnp.asarray(sample), train=False,
    )
    if not ckpt_dir:
        print("warning: no -c checkpoint; running with fresh-init weights")
        return variables
    from deep_vision_tpu.core.checkpoint import CheckpointManager

    return CheckpointManager(ckpt_dir).restore_variables()


def main(argv: Optional[List[str]] = None) -> int:
    from deep_vision_tpu.configs import CONFIG_REGISTRY, get_config

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("-m", "--model", required=True, choices=sorted(CONFIG_REGISTRY))
    p.add_argument("-c", "--checkpoint", default=None)
    p.add_argument("-o", "--output-dir", default=None,
                   help="GAN outputs / detection sidecars go here "
                        "(default: alongside inputs)")
    p.add_argument("--score-threshold", type=float, default=0.3)
    p.add_argument("--preprocessing", default="torch", choices=["torch", "tf"],
                   help="must match how the checkpoint was trained "
                        "(train.py --preprocessing)")
    p.add_argument("--render", action="store_true",
                   help="classification configs: also write a "
                        "<name>_classified.jpg display copy with the top-1 "
                        "label drawn")
    p.add_argument("--labels", default=None,
                   help="class-name file, one name per line, line i = model "
                        "class index i (the converter's imagenet labels are "
                        "1-based with 0 = background)")
    p.add_argument("images", nargs="+")
    args = p.parse_args(argv)

    from deep_vision_tpu.core.excache import place_compile_cache

    place_compile_cache()  # before anything compiles
    import jax.numpy as jnp

    from deep_vision_tpu.models import get_model

    cfg = get_config(args.model)
    size = cfg.input_shape[0]

    # class names apply to classification (top-5 lines, --render banner)
    # AND detection (printed lines + box overlay labels)
    names = None
    if args.labels:
        with open(args.labels) as fh:
            names = [line.strip() for line in fh if line.strip()]
    elif cfg.dataset.get("schema") == "voc":
        # the 20 VOC names are fixed by the dataset (interop constants,
        # like the anchor priors): the demo output shows "person 0.92",
        # not "class 14", with no flag needed
        from deep_vision_tpu.tools.converters import VOC_CLASSES

        names = list(VOC_CLASSES)

    def name_of(i: int) -> str:
        return names[i] if names and 0 <= i < len(names) else f"class {i}"

    def outpath(src: str, suffix: str) -> str:
        base = os.path.basename(src)
        root, _ = os.path.splitext(base)
        d = args.output_dir or os.path.dirname(src) or "."
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, root + suffix)

    if cfg.task == "classification":
        if cfg.dataset.get("kind") == "imagenet":
            mode = "imagenet_tf" if args.preprocessing == "tf" else "imagenet"
            batch = np.stack([
                _load_image(f, cfg.eval_crop, mode, rescale=cfg.train_resize)
                for f in args.images
            ])
        else:
            # small-input configs (mnist-style): resize to the config's
            # input_shape; collapse to grayscale when it wants one channel
            batch = np.stack([
                _load_image(f, size, "unit") for f in args.images
            ])
            if cfg.input_shape[2] == 1:
                luma = np.array([0.299, 0.587, 0.114], np.float32)
                batch = (batch @ luma)[..., None]
                batch = (batch - 0.1307) / 0.3081  # the mnist chain's stats
        if cfg.model_kwargs.get("stem") == "s2d":
            from deep_vision_tpu.data.transforms import space_to_depth

            batch = np.stack([space_to_depth(im) for im in batch])
        kwargs = dict(cfg.model_kwargs)
        model = get_model(cfg.model, num_classes=cfg.num_classes, **kwargs)
        variables = _restore_variables(model, batch[:1], args.checkpoint)
        logits = np.asarray(
            model.apply(variables, jnp.asarray(batch), train=False),
            np.float32,
        )
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        for f, pr in zip(args.images, probs):
            top = np.argsort(pr)[::-1][:5]
            picks = " ".join(f"{name_of(int(i))}: {pr[i]:.3f}" for i in top)
            print(f"{f}: {picks}")
            if args.render:
                k = int(top[0])
                drawn = draw_classification(
                    _reload_rgb(f, size), name_of(k), float(pr[k])
                )
                dst = outpath(f, "_classified.jpg")
                _write_jpeg(dst, drawn)
                print(f"  wrote {dst}")
        return 0

    if cfg.task in ("detection", "centernet"):
        from deep_vision_tpu.inference import (
            make_centernet_detector,
            make_yolo_detector,
        )

        batch = np.stack([
            _load_image(f, size, "unit") for f in args.images
        ])
        model = get_model(cfg.model, num_classes=cfg.num_classes,
                          **cfg.model_kwargs)
        variables = _restore_variables(model, batch[:1], args.checkpoint)
        detect = (
            make_yolo_detector(model, score_threshold=args.score_threshold)
            if cfg.task == "detection"
            else make_centernet_detector(
                model, score_threshold=args.score_threshold
            )
        )
        out = {k: np.asarray(v) for k, v in
               detect(variables, jnp.asarray(batch)).items()}
        try:  # overlay rendering needs cv2, which is optional everywhere
            import cv2
        except Exception:
            cv2 = None
            print("note: opencv not installed; skipping _detected.jpg "
                  "overlays (text sidecars still written)")
        for i, f in enumerate(args.images):
            n = int(out["num"][i])
            print(f"{f}: {n} detections")
            lines = []
            for j in range(n):
                b = out["boxes"][i, j]
                line = (f"  {name_of(int(out['classes'][i, j]))} "
                        f"score {float(out['scores'][i, j]):.3f} "
                        f"box [{b[0]:.3f} {b[1]:.3f} {b[2]:.3f} {b[3]:.3f}]")
                print(line)
                lines.append(line.strip())
            with open(outpath(f, "_boxes.txt"), "w") as fh:
                fh.write("\n".join(lines) + "\n")
            if cv2 is not None:
                # rendered overlay beside the sidecar (demo_mscoco.ipynb
                # parity)
                drawn = draw_detections(
                    _reload_rgb(f, size), out["boxes"][i, :n],
                    out["scores"][i, :n], out["classes"][i, :n],
                    class_names=names,
                )
                dst = outpath(f, "_detected.jpg")
                cv2.imwrite(dst, drawn[..., ::-1])  # RGB -> BGR
                print(f"  -> {dst}")
        return 0

    if cfg.task == "pose":
        from deep_vision_tpu.inference import make_pose_estimator

        batch = np.stack([
            _load_image(f, size, "unit") for f in args.images
        ])
        model = get_model(cfg.model, **cfg.model_kwargs)
        variables = _restore_variables(model, batch[:1], args.checkpoint)
        estimate = make_pose_estimator(model)
        kpts = np.asarray(estimate(variables, jnp.asarray(batch)))
        try:
            import cv2
        except Exception:
            cv2 = None
            print("note: opencv not installed; skipping _pose.jpg overlays")
        for f, kp in zip(args.images, kpts):
            print(f"{f}:")
            for j, (x, y, s) in enumerate(kp):
                print(f"  joint {j}: x={x:.3f} y={y:.3f} score={s:.3f}")
            if cv2 is not None:
                # skeleton overlay (demo_hourglass_pose.ipynb parity)
                drawn = draw_pose(_reload_rgb(f, size), kp)
                dst = outpath(f, "_pose.jpg")
                cv2.imwrite(dst, drawn[..., ::-1])
                print(f"  -> {dst}")
        return 0

    if cfg.task in ("dcgan", "cyclegan"):
        if cfg.task == "dcgan":
            model = get_model("dcgan_generator")
            z = np.random.RandomState(0).randn(len(args.images), 100)
            variables = _restore_variables(model, z[:1].astype(np.float32),
                                           args.checkpoint)
            imgs = np.asarray(model.apply(
                variables, jnp.asarray(z, jnp.float32), train=False
            ), np.float32)
        else:
            batch = np.stack([
                _load_image(f, size, "gan") for f in args.images
            ])
            model = get_model("cyclegan_generator")
            variables = _restore_variables(model, batch[:1], args.checkpoint)
            imgs = np.asarray(
                model.apply(variables, jnp.asarray(batch), train=False),
                np.float32,
            )
        for f, im in zip(args.images, imgs):
            u8 = np.clip((im + 1.0) * 127.5, 0, 255).astype(np.uint8)
            dst = outpath(f, "_generated.jpg")
            _write_jpeg(dst, u8)
            print(f"{f} -> {dst}")
        return 0

    raise ValueError(f"unsupported task {cfg.task!r}")


if __name__ == "__main__":
    raise SystemExit(main())
