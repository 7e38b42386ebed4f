"""Detection inference + model export (the demo_mscoco.ipynb analog).

The reference's YOLO demo notebook (YOLO/tensorflow/demo_mscoco.ipynb) runs
image -> model -> decode -> NMS -> boxes; its CycleGAN converter
(CycleGAN/tensorflow/convert.py) exports to TFLite. Both flows here, against
the library API: the jitted YoloPredictor, then StableHLO export with a
numeric round-trip check.

    python examples/detect_and_export.py [--out /tmp/yolo.stablehlo]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


import argparse

import jax
import jax.numpy as jnp
import numpy as np

from deep_vision_tpu.inference import make_yolo_detector
from deep_vision_tpu.models import get_model
from deep_vision_tpu.tools.export import export_model, load_exported


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="/tmp/yolov3.stablehlo")
    p.add_argument("--image-size", type=int, default=128)
    args = p.parse_args()

    model = get_model("yolov3", num_classes=4)
    img = np.random.RandomState(0).rand(
        1, args.image_size, args.image_size, 3).astype(np.float32)
    x = jnp.asarray(img)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)

    # image batch -> decoded, class-aware-NMS'd boxes, all jitted. The
    # detector donates its image argument (inference.py), and the export
    # round-trip below still needs x — hand the detector its own copy
    detect = make_yolo_detector(model, score_threshold=0.1)
    det = detect(variables, jnp.asarray(img))
    n = int(det["num"][0])
    print(f"detections: {n} boxes "
          f"(scores {np.asarray(det['scores'][0, :max(n, 1)]).round(3)})")

    # portable StableHLO artifact + numeric round-trip
    exported = export_model(model, variables, x)
    with open(args.out, "wb") as f:
        f.write(exported.serialize())
    restored = load_exported(args.out)
    ref = model.apply(variables, x, train=False)
    got = restored.call(x)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, ref))
    print(f"export round-trip: {args.out}  max err {err:.2e}")


if __name__ == "__main__":
    main()
