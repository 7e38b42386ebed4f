"""End-to-end inference path + detection/pose quality metrics.

Covers model -> decode -> NMS -> boxes for a user,
and mAP/PCKh computed on synthetic fixtures with known answers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # jit-heavy: excluded from the fast tier (`-m "not slow"`)

from deep_vision_tpu.core.detection_metrics import (

    DetectionEvaluator,
    pck,
    pckh,
)


class TestDetectionEvaluator:
    def test_perfect_detections_map_1(self):
        ev = DetectionEvaluator(num_classes=3)
        rng = np.random.RandomState(0)
        for _ in range(4):
            boxes = rng.rand(5, 2) * 0.4
            boxes = np.concatenate([boxes, boxes + 0.3], -1)
            classes = rng.randint(0, 3, size=5)
            ev.add(boxes, np.ones(5) * 0.9, classes, boxes, classes)
        out = ev.compute(iou_threshold=0.5)
        assert out["mAP"] == pytest.approx(1.0)

    def test_all_wrong_class_map_0(self):
        ev = DetectionEvaluator(num_classes=2)
        boxes = np.array([[0.1, 0.1, 0.4, 0.4]])
        ev.add(boxes, [0.9], [1], boxes, [0])
        out = ev.compute()
        assert out["mAP"] == 0.0

    def test_half_precision_known_ap(self):
        # 1 GT box; 2 detections: the higher-scored one misses, the lower hits.
        # all-point AP = precision at recall 1 = 1/2.
        ev = DetectionEvaluator(num_classes=1)
        gt = np.array([[0.1, 0.1, 0.5, 0.5]])
        preds = np.array([[0.6, 0.6, 0.9, 0.9], [0.1, 0.1, 0.5, 0.5]])
        ev.add(preds, [0.9, 0.8], [0, 0], gt, [0])
        out = ev.compute(iou_threshold=0.5)
        assert out["mAP"] == pytest.approx(0.5)

    def test_duplicate_detection_is_fp(self):
        # two detections on one GT: second match counts as FP (VOC protocol)
        ev = DetectionEvaluator(num_classes=1)
        gt = np.array([[0.1, 0.1, 0.5, 0.5]])
        preds = np.stack([gt[0], gt[0]])
        ev.add(preds, [0.9, 0.8], [0, 0], gt, [0])
        out = ev.compute(iou_threshold=0.5)
        # AP: TP at rank 1 (P=1, R=1), FP at rank 2 -> all-point AP = 1.0
        assert out["mAP"] == pytest.approx(1.0)
        # but precision fell; 11-point also 1.0 since max precision at R>=t is 1
        # instead verify the FP lowered nothing incorrectly:
        assert out["ap_per_class"][0] == pytest.approx(1.0)

    def test_padded_rows_ignored(self):
        ev = DetectionEvaluator(num_classes=1)
        gt = np.array([[0.1, 0.1, 0.5, 0.5], [0, 0, 0, 0]])
        preds = np.array([[0.1, 0.1, 0.5, 0.5], [0, 0, 0, 0]])
        ev.add(preds, [0.9, 0.0], [0, -1], gt, [0, 0])
        out = ev.compute()
        assert out["mAP"] == pytest.approx(1.0)

    def test_coco_sweep_monotone(self):
        ev = DetectionEvaluator(num_classes=1)
        gt = np.array([[0.1, 0.1, 0.5, 0.5]])
        # slightly offset box: IoU ~ 0.68 -> hits at 0.5, misses at 0.9
        pred = np.array([[0.13, 0.13, 0.53, 0.53]])
        ev.add(pred, [0.9], [0], gt, [0])
        out = ev.compute_coco()
        assert out["mAP@.5"] == pytest.approx(1.0)
        assert 0.0 < out["mAP@[.5:.95]"] < 1.0


class TestPck:
    def test_exact_keypoints(self):
        gt = np.random.RandomState(0).rand(3, 16, 2)
        vis = np.ones((3, 16), bool)
        out = pckh(gt, gt, vis, head_sizes=np.full(3, 0.1))
        assert out["PCKh@0.5"] == pytest.approx(1.0)

    def test_known_fraction(self):
        gt = np.zeros((1, 4, 2))
        pred = np.zeros((1, 4, 2))
        pred[0, :2, 0] = 0.04  # within 0.5 * 0.1
        pred[0, 2:, 0] = 0.2  # outside
        out = pck(pred, gt, np.ones((1, 4), bool), [0.1], alpha=0.5)
        assert out["PCK@0.5"] == pytest.approx(0.5)
        assert out["per_joint"][0] == pytest.approx(1.0)
        assert out["per_joint"][3] == pytest.approx(0.0)

    def test_invisible_excluded(self):
        gt = np.zeros((1, 2, 2))
        pred = np.ones((1, 2, 2))  # both wrong
        vis = np.array([[True, False]])
        out = pck(pred, gt, vis, [1.0])
        assert out["num_visible"] == 1


class TestYoloInference:
    def test_decode_and_nms_shapes(self):
        """Tiny YoloV3 -> decode -> NMS end-to-end, fixed shapes out."""
        from deep_vision_tpu.inference import make_yolo_detector
        from deep_vision_tpu.models import get_model

        model = get_model("yolov3", num_classes=4)
        x = jnp.zeros((2, 64, 64, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        detect = make_yolo_detector(model, max_detections=10,
                                    score_threshold=0.05)
        out = detect(variables, x)
        assert out["boxes"].shape == (2, 10, 4)
        assert out["scores"].shape == (2, 10)
        assert out["classes"].shape == (2, 10)
        assert out["num"].shape == (2,)
        assert int(out["num"].max()) <= 10
        # padding convention: classes -1 where invalid
        invalid = np.asarray(out["scores"]) == 0
        assert np.all(np.asarray(out["classes"])[invalid] == -1)

    def test_synthetic_peak_detected(self):
        """Craft raw head outputs with one hot box; decode+NMS must find it."""
        from deep_vision_tpu.inference import yolo_decode_outputs
        from deep_vision_tpu.ops.anchors import YOLO_ANCHORS, YOLO_ANCHOR_MASKS
        from deep_vision_tpu.ops.nms import non_maximum_suppression

        g = 4
        c = 3
        outputs = []
        for _ in range(3):
            outputs.append(np.full((1, g, g, 3, 5 + c), -8.0, np.float32))
        # scale 0, cell (1, 2), anchor 1, class 2: strong positive
        outputs[0][0, 1, 2, 1, 4] = 8.0  # objectness
        outputs[0][0, 1, 2, 1, 5 + 2] = 8.0
        outputs[0][0, 1, 2, 1, 0:2] = 0.0  # sigmoid -> 0.5: center of cell
        outputs[0][0, 1, 2, 1, 2:4] = 0.0  # wh = anchor size
        outputs = [jnp.asarray(o) for o in outputs]
        boxes, scores = yolo_decode_outputs(outputs)
        best_c = jnp.argmax(scores, -1)
        best_s = jnp.max(scores, -1)
        ob, os_, oc, n = non_maximum_suppression(
            boxes, best_s, best_c, max_detections=5, score_threshold=0.5
        )
        assert int(n[0]) == 1
        assert int(oc[0, 0]) == 2
        box = np.asarray(ob[0, 0])
        cx, cy = (box[0] + box[2]) / 2, (box[1] + box[3]) / 2
        assert cx == pytest.approx((2 + 0.5) / g, abs=1e-5)
        assert cy == pytest.approx((1 + 0.5) / g, abs=1e-5)
        anchor = YOLO_ANCHORS[YOLO_ANCHOR_MASKS[0][1]]
        assert box[2] - box[0] == pytest.approx(anchor[0], rel=1e-4)

    def test_e2e_map_on_fixture(self):
        """Detector output -> evaluator: mAP on a crafted fixture is 1.0."""
        from deep_vision_tpu.core.detection_metrics import DetectionEvaluator
        from deep_vision_tpu.ops.nms import non_maximum_suppression

        gt_boxes = np.array([[0.2, 0.2, 0.6, 0.6], [0.1, 0.6, 0.3, 0.9]])
        gt_classes = np.array([0, 1])
        # detector candidates: GT boxes + jittered dupes at lower score
        cand = np.concatenate([gt_boxes, gt_boxes + 0.01], 0)[None]
        scores = np.array([[0.9, 0.95, 0.6, 0.55]])
        classes = np.array([[0, 1, 0, 1]])
        ob, os_, oc, n = non_maximum_suppression(
            jnp.asarray(cand), jnp.asarray(scores), jnp.asarray(classes),
            max_detections=4, iou_threshold=0.5, score_threshold=0.3,
        )
        ev = DetectionEvaluator(num_classes=2)
        ev.add(np.asarray(ob[0]), np.asarray(os_[0]), np.asarray(oc[0]),
               gt_boxes, gt_classes)
        out = ev.compute(iou_threshold=0.5)
        assert int(n[0]) == 2  # NMS removed the jittered dupes
        assert out["mAP"] == pytest.approx(1.0)


class TestCenternetInference:
    def test_peak_decode(self):
        from deep_vision_tpu.inference import centernet_decode

        h = w = 8
        c = 2
        heat = np.full((1, h, w, c), -8.0, np.float32)
        heat[0, 3, 5, 1] = 8.0  # single confident peak
        wh = np.zeros((1, h, w, 2), np.float32)
        wh[0, 3, 5] = [2.0, 4.0]  # in feature-map cells
        off = np.zeros((1, h, w, 2), np.float32)
        off[0, 3, 5] = [0.5, 0.5]
        out = centernet_decode(
            {"heatmap": jnp.asarray(heat), "wh": jnp.asarray(wh),
             "offset": jnp.asarray(off)},
            max_detections=5, score_threshold=0.5,
        )
        assert int(out["num"][0]) == 1
        assert int(out["classes"][0, 0]) == 1
        box = np.asarray(out["boxes"][0, 0])
        assert (box[0] + box[2]) / 2 == pytest.approx((5 + 0.5) / w)
        assert (box[1] + box[3]) / 2 == pytest.approx((3 + 0.5) / h)
        assert box[2] - box[0] == pytest.approx(2.0 / w)
        assert box[3] - box[1] == pytest.approx(4.0 / h)

    def test_model_wiring(self):
        from deep_vision_tpu.inference import make_centernet_detector
        from deep_vision_tpu.models import get_model

        model = get_model("objects_as_points", num_classes=3, num_stack=1)
        x = jnp.zeros((1, 128, 128, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        detect = make_centernet_detector(model, max_detections=8)
        out = detect(variables, x)
        assert out["boxes"].shape == (1, 8, 4)
        assert out["num"].shape == (1,)


class TestPoseInference:
    def test_heatmap_argmax(self):
        from deep_vision_tpu.inference import heatmaps_to_keypoints

        hm = np.zeros((1, 16, 16, 2), np.float32)
        hm[0, 4, 7, 0] = 1.0
        hm[0, 12, 2, 1] = 0.8
        kpts = np.asarray(heatmaps_to_keypoints(jnp.asarray(hm)))
        assert kpts.shape == (1, 2, 3)
        assert kpts[0, 0, 0] == pytest.approx(7 / 16)
        assert kpts[0, 0, 1] == pytest.approx(4 / 16)
        assert kpts[0, 1, 2] == pytest.approx(0.8)

    def test_pose_estimator_wiring(self):
        from deep_vision_tpu.inference import make_pose_estimator
        from deep_vision_tpu.models import get_model

        model = get_model("hourglass", num_stack=1, num_heatmap=4)
        x = jnp.zeros((1, 64, 64, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        estimate = make_pose_estimator(model)
        kpts = estimate(variables, x)
        assert kpts.shape == (1, 4, 3)
