"""CLI: `python train.py -m <config> [-c <ckpt>]` — the reference's entry
contract (argparse mains at ResNet/pytorch/train.py:541-562, resume-by-flag
at :293-307) over the shared config registry.

`--fake-data` swaps in synthetic datasets of the exact task shapes — the
fleshed-out version of the CPU fake-data harness the reference kept commented
out (CycleGAN/tensorflow/train.py:338-342) — so every config trains end to
end on any host, TPU or CPU.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys as _sys
import threading
from typing import List, Optional

import numpy as np

from deep_vision_tpu.configs import CONFIG_REGISTRY, ExperimentConfig, get_config
from deep_vision_tpu.obs.trace import span, spans, split_wall


def model_input_shape(cfg: ExperimentConfig):
    """The shape the MODEL consumes: cfg.input_shape after any host-side
    layout transform (stem='s2d' ships (H/2, W/2, 4C), models/resnet.py).
    A token sequence's is rank 1, `(T,)`, and has no layout to change."""
    if cfg.model_kwargs.get("stem") == "s2d":
        h, w, c = cfg.input_shape
        return (h // 2, w // 2, 4 * c)
    return tuple(cfg.input_shape)


def model_input(cfg: ExperimentConfig):
    """(the key of the batch's array the model is applied to, its dtype)."""
    if cfg.task == "causal_lm":
        return "tokens", np.int32
    return "image", np.float32


def sample_input(cfg: ExperimentConfig):
    """Two rows of the model's input in its own dtype, to initialise on."""
    import jax.numpy as jnp

    return jnp.ones((2, *model_input_shape(cfg)), model_input(cfg)[1])


# -- fake datasets -----------------------------------------------------------

def _fake_classification(cfg: ExperimentConfig, n_batches: int):
    rng = np.random.RandomState(0)
    h, w, c = model_input_shape(cfg)
    return [
        {
            "image": rng.rand(cfg.batch_size, h, w, c).astype(np.float32),
            "label": rng.randint(0, cfg.num_classes, (cfg.batch_size,)).astype(np.int32),
        }
        for _ in range(n_batches)
    ]


def _fake_tokens(cfg: ExperimentConfig, n_batches: int):
    """Token sequences, ids uniform below the model's vocabulary."""
    from deep_vision_tpu.models import get_model

    rng = np.random.RandomState(0)
    vocab = get_model(cfg.model, **cfg.model_kwargs).vocab_size
    shape = (cfg.batch_size, *model_input_shape(cfg))
    return [{"tokens": rng.randint(0, vocab, shape).astype(np.int32)}
            for _ in range(n_batches)]


def _fake_detection(cfg: ExperimentConfig, n_batches: int, max_boxes=20):
    rng = np.random.RandomState(0)
    h, w, c = cfg.input_shape
    out = []
    for _ in range(n_batches):
        boxes = np.zeros((cfg.batch_size, max_boxes, 4), np.float32)
        classes = np.zeros((cfg.batch_size, max_boxes), np.int32)
        for b in range(cfg.batch_size):
            n = rng.randint(1, 5)
            x1 = rng.uniform(0, 0.6, n)
            y1 = rng.uniform(0, 0.6, n)
            boxes[b, :n, 0], boxes[b, :n, 1] = x1, y1
            boxes[b, :n, 2] = x1 + rng.uniform(0.1, 0.35, n)
            boxes[b, :n, 3] = y1 + rng.uniform(0.1, 0.35, n)
            classes[b, :n] = rng.randint(0, cfg.num_classes, n)
        out.append(
            {
                "image": rng.rand(cfg.batch_size, h, w, c).astype(np.float32),
                "boxes": boxes,
                "classes": classes,
            }
        )
    return out


def _fake_pose(cfg: ExperimentConfig, n_batches: int, hm_size=64):
    from deep_vision_tpu.data.labels import make_pose_heatmaps

    rng = np.random.RandomState(0)
    h, w, c = cfg.input_shape
    out = []
    for _ in range(n_batches):
        hms, kps, viss = [], [], []
        for _b in range(cfg.batch_size):
            s = {
                "keypoints": rng.rand(cfg.num_classes, 2).astype(np.float32),
                "visibility": np.ones((cfg.num_classes,), np.float32),
            }
            hms.append(make_pose_heatmaps(s, size=hm_size,
                                          num_joints=cfg.num_classes)["heatmap"])
            kps.append(s["keypoints"])
            viss.append(s["visibility"])
        out.append(
            {
                "image": rng.rand(cfg.batch_size, h, w, c).astype(np.float32),
                "heatmap": np.stack(hms),
                "keypoints": np.stack(kps),
                "visibility": np.stack(viss),
            }
        )
    return out


def _fake_centernet(cfg: ExperimentConfig, n_batches: int):
    from deep_vision_tpu.data.labels import make_centernet_targets

    det = _fake_detection(cfg, n_batches)
    out_size = cfg.input_shape[0] // 4
    out = []
    for batch in det:
        tgts = [
            make_centernet_targets(
                {"boxes": batch["boxes"][b], "classes": batch["classes"][b]},
                out_size=out_size, num_classes=cfg.num_classes,
            )
            for b in range(len(batch["image"]))
        ]
        out.append(
            {
                "image": batch["image"],
                # raw boxes ride along like the real pipeline's (PadBoxes
                # stays in the sample dict) — --eval-only mAP needs them
                "boxes": batch["boxes"],
                "classes": batch["classes"],
                "heatmap": np.stack([t["heatmap"] for t in tgts]),
                "wh": np.stack([t["wh"] for t in tgts]),
                "offset": np.stack([t["offset"] for t in tgts]),
                "mask": np.stack([t["mask"] for t in tgts]),
            }
        )
    return out


# -- real datasets -----------------------------------------------------------

def build_dataloaders(cfg: ExperimentConfig, data_dir: str, fake: bool,
                      fake_batches: int, num_workers: int,
                      preprocessing: str = "torch", num_procs: int = 0,
                      bad_record_budget=None, host_shard=None):
    """Returns (train_fn, eval_fn) thunks yielding batch dicts per epoch.

    `preprocessing` selects the ImageNet normalization chain: "torch" is the
    torchvision-stats chain (ResNet/pytorch/train.py:315-331); "tf" is the
    TF "ResNet preprocessing" 0-255 mean-subtraction variant
    (ResNet/tensorflow/data_load.py:158-193).

    `bad_record_budget` (records.BadRecordBudget) applies only to the
    record-backed kinds: corrupt/undecodable records are skipped and
    dead-lettered under its bound instead of killing the epoch. One budget
    object is shared by the train and eval datasets — the bound is per
    run, not per split.

    `host_shard` ((shard_index, num_shards), i.e. `multihost.host_shard()`)
    feeds per-host sharded loading on the record-backed TRAIN loaders:
    each host reads only its disjoint shard slice, and because the value
    comes from the CURRENT rendezvous generation, an elastic 3->2 resize
    re-derives the slices for free (resilience/rendezvous.py). Eval
    loaders stay unsharded — every host evaluates the full split.
    """
    if fake or cfg.dataset.get("kind") == "fake":
        maker = {
            "classification": _fake_classification,
            "detection": _fake_detection,
            "pose": _fake_pose,
            "centernet": _fake_centernet,
            "dcgan": _fake_classification,
            "cyclegan": _fake_classification,
            "causal_lm": _fake_tokens,
        }[cfg.task]
        data = maker(cfg, fake_batches)
        return (lambda: data), (lambda: data)

    from deep_vision_tpu.data import DataLoader, Compose, MnistDataset, RecordDataset
    from deep_vision_tpu.data import transforms as T
    from deep_vision_tpu.data.datasets import ImageFolderDataset
    from deep_vision_tpu.data.labels import MakeCenternetTargets, MakePoseHeatmaps

    kind = cfg.dataset["kind"]
    if kind == "mnist":
        train_ds = MnistDataset(
            os.path.join(data_dir, "train-images-idx3-ubyte"),
            os.path.join(data_dir, "train-labels-idx1-ubyte"),
        )
        eval_ds = MnistDataset(
            os.path.join(data_dir, "t10k-images-idx3-ubyte"),
            os.path.join(data_dir, "t10k-labels-idx1-ubyte"),
        )
        tf_ = Compose([T.ToFloat(), T.Normalize(mean=[0.1307], std=[0.3081])])
        train = DataLoader(train_ds, cfg.batch_size, tf_, shuffle=True,
                           num_workers=num_workers, name="train")
        evl = DataLoader(eval_ds, cfg.batch_size, tf_, num_workers=num_workers,
                         name="val")
        return (lambda: train), (lambda: evl)

    if kind == "imagenet":
        # records if present, else flattened folder (data_load.py:14-69)
        rec_glob = os.path.join(data_dir, "tfrecord_train", "*")
        import glob as _g

        if preprocessing == "tf":
            # TF chain: aspect resize -> crop -> flip -> 0-255 mean-sub, no
            # rescaling (preprocess_image, ResNet/tensorflow/data_load.py:158-193)
            train_tf = Compose([
                T.Rescale(cfg.train_resize), T.RandomHorizontalFlip(),
                T.RandomCrop(cfg.eval_crop),
                T.ToFloat(expand_gray_to_rgb=True, scale=False),
                T.MeanSubtract(),
            ])
            eval_tf = Compose([
                T.Rescale(cfg.train_resize), T.CenterCrop(cfg.eval_crop),
                T.ToFloat(expand_gray_to_rgb=True, scale=False),
                T.MeanSubtract(),
            ])
        else:
            train_tf = Compose([
                T.Rescale(cfg.train_resize), T.RandomHorizontalFlip(),
                T.RandomCrop(cfg.eval_crop),
                T.ColorJitter(0.4, 0.4, 0.4),
                T.ToFloatNormalize(expand_gray_to_rgb=True),
            ])  # transforms.Compose at ResNet/pytorch/train.py:315-331
            eval_tf = Compose([
                T.Rescale(cfg.train_resize), T.CenterCrop(cfg.eval_crop),
                T.ToFloatNormalize(expand_gray_to_rgb=True),
            ])
        if cfg.model_kwargs.get("stem") == "s2d":
            # host half of the MLPerf stem trick (models/resnet.py
            # SpaceToDepthStem): lay images out (H/2, W/2, 12) on the host
            train_tf = Compose([train_tf, T.SpaceToDepth()])
            eval_tf = Compose([eval_tf, T.SpaceToDepth()])
        if _g.glob(rec_glob):
            train_ds = RecordDataset(rec_glob, "imagenet", shuffle_shards=True,
                                     bad_record_budget=bad_record_budget)
            eval_ds = RecordDataset(
                os.path.join(data_dir, "tfrecord_val", "*"), "imagenet",
                bad_record_budget=bad_record_budget,
            )
            train = DataLoader(train_ds, cfg.batch_size, train_tf, shuffle=True,
                               shuffle_buffer=10000, num_workers=num_workers,
                               num_procs=num_procs, name="train",
                               host_shard=host_shard)
        else:
            train_ds = ImageFolderDataset(os.path.join(data_dir, "train_flatten"))
            eval_ds = ImageFolderDataset(os.path.join(data_dir, "val_flatten"))
            # forwarding num_procs surfaces the folder dataset's lack of
            # .split as a clear TypeError instead of silently ignoring it
            train = DataLoader(train_ds, cfg.batch_size, train_tf, shuffle=True,
                               num_workers=num_workers, num_procs=num_procs,
                               name="train")
        evl = DataLoader(eval_ds, cfg.batch_size, eval_tf, num_workers=num_workers,
                         name="val")
        return (lambda: train), (lambda: evl)

    if kind == "records":
        schema = cfg.dataset["schema"]
        size = cfg.input_shape[0]
        # eval chains carry no random augments (the imagenet split above does
        # the same): plateau schedules key on val metrics, which must be
        # deterministic for a fixed checkpoint
        if cfg.task == "detection":
            train_chain = [T.RandomHorizontalFlip(), T.RandomCropWithBoxes(),
                           T.Resize(size), T.ToFloat(), T.PadBoxes(100)]
            eval_chain = [T.Resize(size), T.ToFloat(), T.PadBoxes(100)]
        elif cfg.task == "pose":
            # keypoint-driven person crop + the reference's scale
            # augmentation (random margin, preprocess.py:18-20) + the
            # CORRECTED left/right-swapping flip its disabled version lacked
            train_chain = [T.CropRoi(margin=(0.1, 0.3)),
                           T.RandomHorizontalFlip(
                               keypoint_swap_pairs=T.MPII_FLIP_PAIRS),
                           T.Resize(size), T.ToFloat(),
                           MakePoseHeatmaps(num_joints=cfg.num_classes)]
            eval_chain = [T.CropRoi(margin=0.2),  # fixed margin, as eval
                          T.Resize(size), T.ToFloat(),
                          MakePoseHeatmaps(num_joints=cfg.num_classes)]
        elif cfg.task == "centernet":
            targets = MakeCenternetTargets(size // 4, cfg.num_classes)
            train_chain = [T.RandomHorizontalFlip(), T.Resize(size),
                           T.ToFloat(), T.PadBoxes(100), targets]
            eval_chain = [T.Resize(size), T.ToFloat(), T.PadBoxes(100), targets]
        else:  # image_only (GANs): scale to [-1, 1]
            train_chain = [T.Resize(size), T.ToFloat(),
                           T.Normalize(mean=[0.5] * cfg.input_shape[2],
                                       std=[0.5] * cfg.input_shape[2])]
            eval_chain = train_chain
        train_ds = RecordDataset(
            os.path.join(data_dir, cfg.dataset.get("train_glob", "train*")),
            schema, shuffle_shards=True,
            bad_record_budget=bad_record_budget,
        )
        eval_ds = RecordDataset(
            os.path.join(data_dir, cfg.dataset.get("val_glob", "val*")), schema,
            bad_record_budget=bad_record_budget,
        )
        train = DataLoader(train_ds, cfg.batch_size, Compose(train_chain),
                           shuffle=True, num_workers=num_workers,
                           num_procs=num_procs, drop_remainder=True,
                           name="train", host_shard=host_shard)
        evl = DataLoader(eval_ds, cfg.batch_size, Compose(eval_chain),
                         num_workers=num_workers, drop_remainder=True,
                         name="val")
        return (lambda: train), (lambda: evl)

    raise ValueError(f"unknown dataset kind {kind!r}")


# -- trainer assembly --------------------------------------------------------

def _steps_per_epoch(cfg: ExperimentConfig, train_fn) -> int:
    data = train_fn()
    try:
        return len(data)
    except TypeError:
        return 1000  # streaming: nominal epoch length


def _build_schedule(cfg: ExperimentConfig, steps_per_epoch: int):
    from deep_vision_tpu.train.optimizers import make_schedule

    base_lr = cfg.optimizer["learning_rate"]
    if cfg.schedule is None:
        return base_lr
    kw = dict(cfg.schedule)
    kind = kw.pop("kind")
    if "step_size_epochs" in kw:
        kw["step_size"] = kw.pop("step_size_epochs") * steps_per_epoch
    if "total_epochs" in kw:
        kw["total_steps"] = kw.pop("total_epochs") * steps_per_epoch
    if "hold_epochs" in kw:
        kw["hold_steps"] = kw.pop("hold_epochs") * steps_per_epoch
    if "warmup_epochs" in kw:
        kw["warmup_steps"] = kw.pop("warmup_epochs") * steps_per_epoch
    return make_schedule(kind, base_lr, **kw)


#: the `setup` event's names for the spans inside `setup/build_trainer`
#: (innermost first); `other` is what none of them covers
SETUP_BUCKETS = {"setup/imports": "imports", "setup/init_state": "init_state"}


def _setup_journaled(build):
    """`build` under the span `setup/build_trainer`; then where its wall
    went, over the `setup/*` spans inside it: one `setup` event in the
    trainer's journal and one line on stderr."""
    @functools.wraps(build)
    def wrapper(*args, **kw):
        import jax  # noqa: F401  (a span is recorded once jax is loaded)

        with span("setup/build_trainer") as whole:
            trainer = build(*args, **kw)
        split = split_wall(
            spans(since_ns=whole.start_ns, thread=threading.get_ident()),
            whole.start_ns, whole.end_ns, SETUP_BUCKETS)
        split_s = {k: round(v * 1e-9, 3) for k, v in split.items()}
        total_s = round((whole.end_ns - whole.start_ns) * 1e-9, 3)
        if trainer.journal is not None:
            trainer.journal.write("setup", build_trainer_s=total_s,
                                  split_s=split_s)
        print(f"[setup] build_trainer {total_s:.1f} s: " + ", ".join(
            f"{k} {v:.1f}" for k, v in split_s.items()),
            file=_sys.stderr, flush=True)
        return trainer

    return wrapper


@_setup_journaled
def build_trainer(cfg: ExperimentConfig, train_fn, ckpt_dir: Optional[str],
                  tb_dir: Optional[str] = None,
                  profile_dir: Optional[str] = None,
                  checkify_errors: bool = False,
                  ema_decay: Optional[float] = None,
                  journal=None,
                  telemetry_sample_every: int = 16,
                  health=None,
                  autoprof=None,
                  multistep: int = 1,
                  device_prefetch: int = 0,
                  opt_state_dtype: Optional[str] = None,
                  backend_supervisor=None,
                  data_loader=None,
                  steps_per_epoch: Optional[int] = None,
                  executable_cache=None,
                  sharding_rules=None,
                  telemetry=None):
    with span("setup/imports"):
        from deep_vision_tpu.core import CheckpointManager
        from deep_vision_tpu.core.metrics import MetricLogger
        from deep_vision_tpu.losses import (
            causal_lm_loss_fn,
            centernet_loss_fn,
            classification_loss_fn,
            hourglass_loss_fn,
            yolo_train_loss_fn,
        )
        from deep_vision_tpu.models import get_model
        from deep_vision_tpu.obs.registry import get_registry
        from deep_vision_tpu.train import Trainer, build_optimizer
        from deep_vision_tpu.train.optimizers import ReduceLROnPlateau

    # a --data-service stream has no len(): the caller passes its epoch
    # window so LR schedules are built for the steps that actually run
    # (the streaming fallback of 1000 would stretch a cosine ~16x)
    steps = (steps_per_epoch if steps_per_epoch is not None
             else _steps_per_epoch(cfg, train_fn))
    opt_kw = dict(cfg.optimizer)
    name = opt_kw.pop("name")
    opt_kw.pop("learning_rate")
    lr = _build_schedule(cfg, steps)
    wd = opt_kw.pop("weight_decay", 0.0)
    tx = build_optimizer(name, lr, weight_decay=wd, decay_bn_bias=True,
                         state_dtype=opt_state_dtype, **opt_kw)

    if cfg.task == "classification":
        model = get_model(cfg.model, num_classes=cfg.num_classes, **cfg.model_kwargs)
        loss_fn = functools.partial(classification_loss_fn, **cfg.loss_kwargs)
        plateau_metric = cfg.plateau_metric
    elif cfg.task == "detection":
        model = get_model(cfg.model, num_classes=cfg.num_classes, **cfg.model_kwargs)
        size = cfg.input_shape[0]
        loss_fn = functools.partial(
            yolo_train_loss_fn,
            grid_sizes=(size // 32, size // 16, size // 8),
            num_classes=cfg.num_classes, **cfg.loss_kwargs,
        )
        plateau_metric = cfg.plateau_metric
    elif cfg.task == "pose":
        model = get_model(cfg.model, **cfg.model_kwargs)
        loss_fn = functools.partial(hourglass_loss_fn, **cfg.loss_kwargs)
        plateau_metric = cfg.plateau_metric
    elif cfg.task == "centernet":
        model = get_model(cfg.model, num_classes=cfg.num_classes, **cfg.model_kwargs)
        loss_fn = functools.partial(centernet_loss_fn, **cfg.loss_kwargs)
        plateau_metric = cfg.plateau_metric
    elif cfg.task == "causal_lm":
        model = get_model(cfg.model, **cfg.model_kwargs)
        loss_fn = functools.partial(causal_lm_loss_fn, **cfg.loss_kwargs)
        plateau_metric = "loss"
    elif cfg.task in ("dcgan", "cyclegan"):
        raise ValueError(f"task {cfg.task!r} uses a GAN trainer "
                         "(build_gan_trainer), not Trainer")
    else:
        raise ValueError(
            f"unknown task {cfg.task!r}: Trainer has classification, "
            "detection, pose, centernet and causal_lm")

    plateau = ReduceLROnPlateau(**cfg.plateau) if cfg.plateau else None
    # journal-wired: quarantines and sidecar retries become typed events
    ckpt = CheckpointManager(ckpt_dir, journal=journal) if ckpt_dir else None
    tb = None
    if tb_dir:
        from deep_vision_tpu.core.tensorboard import SummaryWriter

        tb = SummaryWriter(tb_dir)
    # loggers always carry the registry (and the train logger the journal):
    # stdout/TensorBoard/Prometheus/JSONL all fan out from one log call.
    # The val logger stays journal-free — Trainer.evaluate writes the typed
    # 'eval' event, a journal-wired val logger would duplicate it.
    logger = MetricLogger(tb_writer=tb, name="train",
                          registry=get_registry(), journal=journal)
    eval_logger = MetricLogger(tb_writer=tb, name="val", print_every=0,
                               registry=get_registry())
    return Trainer(
        model, tx, loss_fn, sample_input(cfg),
        input_key=model_input(cfg)[0], plateau=plateau,
        plateau_metric=plateau_metric, checkpoint_manager=ckpt,
        logger=logger, eval_logger=eval_logger, profile_dir=profile_dir,
        checkify_errors=checkify_errors, ema_decay=ema_decay,
        journal=journal, lr_schedule=lr,
        telemetry_sample_every=telemetry_sample_every,
        health=health, autoprof=autoprof,
        multistep=multistep, device_prefetch=device_prefetch,
        backend_supervisor=backend_supervisor,
        data_loader=data_loader,
        executable_cache=executable_cache,
        sharding_rules=sharding_rules,
        telemetry=telemetry,
    )


def build_gan_trainer(cfg: ExperimentConfig, journal=None,
                      telemetry_sample_every: int = 32, health=None,
                      autoprof=None):
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import build_optimizer
    from deep_vision_tpu.train.gan import CycleGanTrainer, DcganTrainer

    opt_kw = dict(cfg.optimizer)
    name = opt_kw.pop("name")
    lr = opt_kw.pop("learning_rate")
    if cfg.task == "dcgan":
        return DcganTrainer(
            get_model("dcgan_generator"),
            get_model("dcgan_discriminator"),
            build_optimizer(name, lr, **opt_kw),
            build_optimizer(name, lr, **opt_kw),
            image_shape=cfg.input_shape,
            journal=journal,
            telemetry_sample_every=telemetry_sample_every,
            health=health,
            autoprof=autoprof,
        )
    tx_fn = lambda: build_optimizer(name, lr, **dict(opt_kw))
    return CycleGanTrainer(
        get_model("cyclegan_generator"), get_model("cyclegan_generator"),
        get_model("cyclegan_discriminator"), get_model("cyclegan_discriminator"),
        tx_fn, tx_fn, image_shape=cfg.input_shape,
        journal=journal,
        telemetry_sample_every=telemetry_sample_every,
        health=health,
        autoprof=autoprof,
    )


def run_eval_only(cfg: ExperimentConfig, trainer, eval_fn) -> dict:
    """Quality evaluation from a checkpoint: the reference's demo-notebook
    role (YOLO demo_mscoco.ipynb, Hourglass demo_hourglass_pose.ipynb) as a
    CLI mode, with the metrics the reference never shipped (mAP 'working in
    progress' at YOLO/tensorflow/README.md:28-31; no PCK anywhere)."""
    import jax

    variables = {"params": trainer.state.params}
    if trainer.state.batch_stats:
        variables["batch_stats"] = trainer.state.batch_stats

    if cfg.task == "classification":
        summary = trainer.evaluate(eval_fn())
        print("eval: " + " ".join(f"{k}={v:.4f}" for k, v in summary.items()))
        return summary

    if cfg.task in ("detection", "centernet"):
        from deep_vision_tpu.core.detection_metrics import DetectionEvaluator
        from deep_vision_tpu.inference import (
            make_centernet_detector,
            make_yolo_detector,
        )

        if cfg.task == "detection":
            detect = make_yolo_detector(trainer.model, score_threshold=0.1)
        else:
            detect = make_centernet_detector(trainer.model)
        ev = DetectionEvaluator(cfg.num_classes)
        for batch in eval_fn():
            out = jax.device_get(detect(variables, batch["image"]))
            for i in range(len(batch["image"])):
                ev.add(out["boxes"][i], out["scores"][i], out["classes"][i],
                       batch["boxes"][i], batch["classes"][i])
        res = ev.compute(iou_threshold=0.5)
        coco = ev.compute_coco()
        print(f"eval: mAP@.5={res['mAP']:.4f} "
              f"mAP@[.5:.95]={coco['mAP@[.5:.95]']:.4f} "
              f"images={res['num_images']}")
        return {"mAP@.5": res["mAP"], **coco}

    if cfg.task == "pose":
        from deep_vision_tpu.core.detection_metrics import pck
        from deep_vision_tpu.inference import make_pose_estimator

        estimate = make_pose_estimator(trainer.model)
        preds, gts, viss, norms = [], [], [], []
        head_flags = set()
        for batch in eval_fn():
            kpts = np.asarray(jax.device_get(estimate(variables, batch["image"])))
            preds.append(kpts[..., :2])
            gts.append(np.asarray(batch["keypoints"]))
            viss.append(np.asarray(
                batch.get("visibility", np.ones(kpts.shape[:2]))) > 0)
            # PCKh when the records carry a head size; else image-normalized
            # PCK@0.05 (coordinates are in [0,1], so norm=1 is the image side)
            head_flags.add("head_size" in batch)
            norms.append(np.asarray(
                batch.get("head_size", np.ones(len(kpts)))))
        if len(head_flags) > 1:
            raise ValueError(
                "eval batches are inconsistent: some carry 'head_size', some "
                "don't — PCKh and image-normalized PCK cannot be mixed"
            )
        alpha = 0.5 if head_flags == {True} else 0.05
        out = pck(np.concatenate(preds), np.concatenate(gts),
                  np.concatenate(viss), np.concatenate(norms), alpha=alpha)
        key = [k for k in out if k.startswith("PCK")][0]
        print(f"eval: {key}={out[key]:.4f} visible={out['num_visible']}")
        return out

    raise ValueError(f"--eval-only unsupported for task {cfg.task!r}")


def _maybe_upload(args, ckpt_dir: str) -> None:
    if not args.upload_to:
        return
    from deep_vision_tpu.tools.cloud import upload_artifact

    uri = upload_artifact(ckpt_dir, args.upload_to)
    print(f"uploaded checkpoints to {uri}")


def _make_journal(args, cfg: ExperimentConfig, budget=None):
    from deep_vision_tpu.obs import locksmith

    if not args.journal:
        # DVT_LOCKSMITH arms the runtime lock sanitizer even journal-less
        # (violations still count in the registry and report())
        locksmith.arm_from_env()
        return None
    import dataclasses

    from deep_vision_tpu.obs import RunJournal

    journal = RunJournal(args.journal, kind="train")
    # chaos-smoke children run with DVT_LOCKSMITH=1: lock-order
    # violations and hold-time outliers land as typed journal events
    locksmith.arm_from_env(journal=journal)
    journal.manifest(config=dataclasses.asdict(cfg))
    # late-attach the resilience emitters (both are built before the
    # journal exists): injected faults and skipped records then show up
    # as typed `fault`/`data_skip` events next to the steps they hit
    from deep_vision_tpu.resilience import installed

    inj = installed()
    if inj is not None:
        inj.set_journal(journal)
    if budget is not None:
        budget.journal = journal
    return journal


def _make_tracer(args, journal):
    """--trace: install the process-wide span tracer; the journal notes
    the trace path so obs_report readers find the matching timeline."""
    if not args.trace:
        return None
    from deep_vision_tpu.obs import Tracer, set_tracer

    tracer = Tracer(args.trace,
                    run_id=journal.run_id if journal is not None else None)
    set_tracer(tracer)
    if journal is not None:
        journal.write("note", trace_path=args.trace)
    return tracer


def _make_health(args, journal):
    """--health-policy / --watchdog-timeout: the run's health monitor.
    Either flag alone activates it (a watchdog with the default `warn`
    NaN policy, or a NaN policy with no hang deadline)."""
    if not args.health_policy and not args.watchdog_timeout:
        return None
    from deep_vision_tpu.obs import HealthMonitor

    health = HealthMonitor(
        policy=args.health_policy or "warn",
        journal=journal,
        watchdog_timeout=args.watchdog_timeout,
        # --watchdog-timeout alone: the 'warn' NaN policy is a default the
        # user never chose, so it must not soften the trainer's
        # pre-existing fatal divergence check
        policy_explicit=args.health_policy is not None,
    )
    if journal is not None:
        # stop() is idempotent: the closer covers abnormal unwinds, the
        # explicit stop in _finish_obs covers clean exits
        journal.add_closer(health.stop)
    return health


def _make_flight(args, journal):
    """--flight-dir: install the flight recorder (obs/flight.py). It taps
    the journal for its postmortem ring buffers and registers as the
    process-wide recorder so the preemption guard, fault injector, and
    data pipeline can reach it without a handle."""
    if not args.flight_dir:
        return None
    from deep_vision_tpu.obs import FlightRecorder, set_flight

    flight = FlightRecorder(
        args.flight_dir,
        run_id=journal.run_id if journal is not None else None)
    set_flight(flight)
    if journal is not None:
        flight.attach(journal)
    return flight


def _make_telemetry(args, journal, flight, discovery_dir,
                    role: str = "train"):
    """--telemetry-port / DVT_TELEMETRY: the live observability plane
    (obs/telemetry.py). Failure to bind degrades to a warning — the
    telemetry plane must never kill the run it observes."""
    port = args.telemetry_port
    if port is None:
        from deep_vision_tpu.core import knobs

        try:
            port = knobs.get_int("DVT_TELEMETRY")
        except knobs.KnobError as e:
            # degrade, don't raise: the telemetry plane must never kill
            # the run it observes — not even at parse time
            print(f"warning: {e}; telemetry disabled", file=_sys.stderr)
            return None
    if port is None:
        return None
    from deep_vision_tpu.obs.registry import get_registry
    from deep_vision_tpu.obs.telemetry import TelemetryServer

    tele = TelemetryServer(port=port, role=role, registry=get_registry(),
                           journal=journal, flight=flight,
                           discovery_dir=discovery_dir)
    try:
        tele.start()
    except OSError as e:
        print(f"warning: telemetry server failed to bind port {port} "
              f"({e}); continuing without live endpoints",
              file=_sys.stderr)
        return None
    if journal is not None:
        # abnormal unwinds: close is idempotent, the clean path in
        # _finish_obs re-running it is a no-op
        journal.add_closer(tele.close)
    print(f"telemetry: http://{tele.address}/statusz")
    return tele


def _parse_profile_window(parser, spec: str):
    try:
        start_s, stop_s = spec.split(":")
        start, stop = int(start_s), int(stop_s)
    except ValueError:
        parser.error(f"--profile-window {spec!r} is not 'START:STOP'")
    if not 0 <= start < stop:
        parser.error(f"--profile-window needs 0 <= START < STOP, got {spec}")
    return start, stop


def _make_autoprof(args, journal, default_dir: str, window=None):
    """--profile-dir (static window) / --autoprof (anomaly triggers):
    one AutoProfiler owns both capture modes (obs/autoprof.py)."""
    if not args.profile_dir and not args.autoprof:
        return None
    from deep_vision_tpu.obs import AutoProfiler

    # --autoprof without --profile-dir still needs somewhere to put the
    # captures; the checkpoint dir is the run's natural artifact home
    pdir = args.profile_dir or os.path.join(default_dir, "autoprof")
    return AutoProfiler(
        pdir, journal=journal,
        # the static window applies only when the user asked for a static
        # capture dir; pure --autoprof runs capture on anomalies alone
        window=window if args.profile_dir else None,
        auto=args.autoprof,
        window_steps=args.autoprof_window,
        cooldown_steps=args.autoprof_cooldown,
        max_captures=args.autoprof_budget,
        z_threshold=args.autoprof_z,
    )


def _finish_obs(args, journal, status: str = "clean_exit",
                tracer=None, health=None, autoprof=None,
                flight=None, telemetry=None) -> None:
    """Clean-run epilogue: Prometheus export + trace flush + journal exit
    marker + multi-host journal aggregation + flight disarm. (Abnormal
    exits are covered by the journal's atexit crash marker, the tracer's
    atexit flush, the health closer, and the flight recorder's atexit
    crash dump.)"""
    if telemetry is not None:
        # first: stop answering scrapes before the sources below tear down
        # (a probe against a half-closed run would read freed state)
        telemetry.close()
    if autoprof is not None:
        autoprof.close()  # stop an in-flight capture instead of leaking it
    if health is not None:
        health.stop()
    if tracer is not None:
        from deep_vision_tpu.obs import set_tracer

        tracer.close()
        set_tracer(None)
        print(f"trace written to {tracer.path} "
              "(load in Perfetto / chrome://tracing)")
    if journal is not None:
        journal.close(status)
        # multi-host: every host closed its .pN file at the barrier inside
        # aggregate_obs; the primary stitches them into one timeline with
        # cross-host straggler detection (no-op single-process)
        try:
            from deep_vision_tpu.parallel.multihost import aggregate_obs

            merged = aggregate_obs(args.journal)
            if merged:
                print(f"merged multi-host journal -> {merged} "
                      "(render with tools/obs_report.py --merged)")
        except Exception as e:
            print(f"warning: multi-host journal merge failed: {e}")
    # metrics export AFTER the merge: counters the aggregation itself
    # bumps (obs_straggler_total) must land in the exported snapshot
    if args.metrics_export:
        from deep_vision_tpu.obs.registry import get_registry

        if get_registry().write_prometheus(args.metrics_export):
            print(f"metrics exported to {args.metrics_export}")
    if flight is not None:
        flight.close()  # clean exit: disarm, no crash bundle


# -- main --------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="deep_vision_tpu trainer (train.py -m <config> [-c ckpt])"
    )
    parser.add_argument("-m", "--model", required=True,
                        choices=sorted(CONFIG_REGISTRY))
    parser.add_argument("-c", "--checkpoint", default=None,
                        help="resume: checkpoint dir (or 'auto' for default dir)")
    parser.add_argument("--data-dir", default="./dataset")
    parser.add_argument("--ckpt-dir", default=None)
    parser.add_argument("--epochs", type=int, default=None)
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument("--num-workers", type=int, default=8,
                        help="decode thread pool size")
    parser.add_argument("--num-procs", type=int, default=0,
                        help="decode worker PROCESSES (0 = threads only); "
                             "use ~cores/2 on big hosts to scale JPEG decode "
                             "past the GIL")
    parser.add_argument("--fake-data", action="store_true")
    parser.add_argument("--fake-batches", type=int, default=4)
    parser.add_argument("--tensorboard-dir", default=None)
    parser.add_argument("--profile-dir", default=None,
                        help="capture a jax.profiler trace of the "
                             "--profile-window steps into this dir")
    parser.add_argument("--profile-window", default="10:20",
                        metavar="START:STOP",
                        help="static capture window [START, STOP) for "
                             "--profile-dir (default 10:20)")
    parser.add_argument("--autoprof", action="store_true",
                        help="anomaly-triggered profiling: step-time/"
                             "data-wait z-score regressions, recompile "
                             "bursts, and HBM high-water jumps each arm a "
                             "one-shot N-step jax.profiler capture with "
                             "cooldown and budget, journaled as typed "
                             "profile_capture events (obs/autoprof.py)")
    parser.add_argument("--autoprof-window", type=int, default=8,
                        metavar="STEPS",
                        help="steps per triggered capture (default 8)")
    parser.add_argument("--autoprof-cooldown", type=int, default=200,
                        metavar="STEPS",
                        help="steps after a capture before another trigger "
                             "may arm (default 200)")
    parser.add_argument("--autoprof-budget", type=int, default=2,
                        metavar="N",
                        help="max triggered captures per run (default 2; "
                             "the static --profile-window is exempt)")
    parser.add_argument("--autoprof-z", type=float, default=5.0,
                        metavar="Z",
                        help="rolling z-score threshold for the step-time/"
                             "data-wait regression triggers (default 5.0)")
    parser.add_argument("--flight-dir", default=None, metavar="DIR",
                        help="always-on flight recorder: ring-buffer the "
                             "recent steps/health/journal/span tail and "
                             "dump an atomic crc-checked postmortem bundle "
                             "under DIR on crash, hang, health abort, or "
                             "preemption (obs/flight.py; validate with "
                             "obs.flight.validate_bundle)")
    parser.add_argument("--journal", default=None, metavar="PATH",
                        help="append typed run events (manifest, per-step "
                             "timing, eval/checkpoint, exit marker) to this "
                             "JSONL; render with tools/obs_report.py")
    parser.add_argument("--metrics-export", default=None, metavar="PATH",
                        help="write the metrics registry as Prometheus text "
                             "exposition format at the end of the run")
    parser.add_argument("--telemetry-sample-every", type=int, default=16,
                        help="block_until_ready fence cadence for the "
                             "step-time breakdown (obs/stepclock.py)")
    parser.add_argument("--telemetry-port", type=int, default=None,
                        metavar="PORT",
                        help="serve live /metrics /varz /healthz /statusz "
                             "over HTTP on PORT (0 = auto-assign; the bound "
                             "port is journaled as a telemetry_server event "
                             "and written to a discovery file under the "
                             "checkpoint dir for tools/obs_poll.py). "
                             "DVT_TELEMETRY=PORT is the env equivalent "
                             "(obs/telemetry.py)")
    parser.add_argument("--trace", default=None, metavar="PATH",
                        help="write Chrome trace-event JSON spans (data "
                             "fetch/augment, train/eval steps, checkpoint "
                             "I/O) to this path; load in Perfetto or "
                             "chrome://tracing (obs/trace.py)")
    parser.add_argument("--health-policy", default=None,
                        choices=["warn", "skip_step", "abort"],
                        help="NaN/Inf + divergence guard on per-step loss "
                             "and grad norm: 'warn' logs and continues, "
                             "'skip_step' discards the poisoned update "
                             "inside the jitted step, 'abort' writes a "
                             "typed health journal event and raises "
                             "(obs/health.py)")
    parser.add_argument("--watchdog-timeout", type=float, default=None,
                        metavar="SECONDS",
                        help="hang detector: if no step completes within "
                             "this deadline, dump every thread's stack to "
                             "stderr and a 'health' journal event (a hung "
                             "multi-host collective stays diagnosable "
                             "post-mortem)")
    parser.add_argument("--skip-preflight", action="store_true",
                        help="skip the environment preflight (backend "
                             "liveness + version handshake, mesh-shape "
                             "sanity, checkpoint-dir writability) that "
                             "otherwise runs first so a doomed run fails "
                             "in seconds instead of minutes "
                             "(tools/preflight.py, `make preflight`)")
    parser.add_argument("--backend-retries", type=int, default=0,
                        metavar="N",
                        help="treat a lost backend (dropped connection, "
                             "hung-backend timeout) as an expected input: "
                             "rebuild the jitted step, restore the last "
                             "checkpoint, and replay, up to N times — "
                             "journaled as typed backend_lost/"
                             "backend_recovered events "
                             "(resilience/elastic.py BackendSupervisor; "
                             "0 = fail on the first backend error)")
    parser.add_argument("--fault-spec", default=None, metavar="SPEC",
                        help="inject deterministic faults at named I/O "
                             "points (resilience/faults.py), e.g. "
                             "'data.read:io_error@0.01;ckpt.sidecar:"
                             "crash_after_write'; exported to data-worker "
                             "processes via the environment")
    parser.add_argument("--fault-seed", type=int, default=0,
                        help="seed for probabilistic fault rules (same seed "
                             "= same fault sequence)")
    parser.add_argument("--data-snapshot", action="store_true",
                        help="checkpoint the input pipeline with the model "
                             "(data/snapshot.py): every save's host sidecar "
                             "carries the train DataLoader's position "
                             "(epoch, batches, shard cursor, bad-record-"
                             "budget spend) and resume replays a byte-"
                             "identical batch stream instead of restarting "
                             "from shard zero (typed data_resume event; "
                             "requires a real dataset, --num-procs 0)")
    parser.add_argument("--data-service", default=None, metavar="HOST:PORT",
                        help="consume training batches from a shared "
                             "dataset service (data/service.py; run one "
                             "with tools/data_service.py) instead of a "
                             "local pipeline — decode/augment leave this "
                             "process, several trainers/evals share one "
                             "stream, reconnects ride the retry policy")
    parser.add_argument("--data-service-steps", type=int, default=64,
                        metavar="N",
                        help="batches per epoch window when consuming "
                             "--data-service (the service stream is "
                             "continuous; epochs are client-side)")
    parser.add_argument("--bad-record-budget", default=None, metavar="N|FRAC",
                        help="skip corrupt/undecodable records instead of "
                             "crashing, up to this many (>=1) or this "
                             "fraction (<1) of records seen; each skip is "
                             "dead-lettered with file+offset, and the run "
                             "aborts once the budget is spent (per worker "
                             "process with --num-procs)")
    parser.add_argument("--dead-letter", default=None, metavar="PATH",
                        help="dead-letter JSONL for skipped records "
                             "(default: <ckpt-dir>/dead_letter.jsonl)")
    parser.add_argument("--eval-first", action="store_true",
                        help="epoch-0 sanity validate (ResNet/pytorch/train.py:390)")
    parser.add_argument("--eval-only", action="store_true",
                        help="no training: evaluate the checkpoint on the val "
                             "split (classification loss/top-k, detection mAP, "
                             "pose PCK)")
    parser.add_argument("--preprocessing", default="torch",
                        choices=["torch", "tf"],
                        help="ImageNet chain: torchvision stats or the TF "
                             "0-255 mean-subtraction variant")
    parser.add_argument("--summary", action="store_true",
                        help="print the per-parameter model summary table "
                             "(torchsummary analog) before training")
    parser.add_argument("--multistep", type=int, default=1, metavar="K",
                        help="optimizer steps per device dispatch via a "
                             "lax.scan superstep: one dispatch carries K "
                             "stacked batches, amortizing host dispatch "
                             "overhead K-fold; per-step metrics/NaN-guard "
                             "are preserved and step counters advance by K "
                             "per dispatch (incompatible with --checkify "
                             "and --ema-decay)")
    parser.add_argument("--device-prefetch", type=int, default=0,
                        metavar="DEPTH",
                        help="pad/shard/device_put the next DEPTH batches "
                             "on a producer thread so H2D transfer overlaps "
                             "compute (2 = double buffering; 0 = place on "
                             "the critical path as before); depth/starvation "
                             "ride the device_prefetch_* metrics")
    parser.add_argument("--sharding-rules", default=None, metavar="TABLE",
                        help="declarative pattern->PartitionSpec sharding "
                             "table (parallel/shardmap.py): a family name "
                             "(vit/moe/resnet), 'auto' (derive from the "
                             "model, refusing families without a table), or "
                             "'heuristic' (the explicit infer_tp_sharding "
                             "size-heuristic fallback). The full train state "
                             "places per the table, coverage is hard-checked "
                             "at startup against the family's floor, and the "
                             "rule->leaf resolution is journaled as a typed "
                             "sharding_resolved event")
    parser.add_argument("--executable-cache", default=None, metavar="DIR",
                        help="persistent compiled-executable cache dir "
                             "(core/excache.py; env DVT_EXCACHE): step "
                             "executables AOT-round-trip through the "
                             "content-addressed store so a restarted "
                             "process, a backend-loss rebuild, or a "
                             "re-exec'd host loads instead of recompiling "
                             "(JAX's own compilation cache is placed "
                             "separately: JAX_COMPILATION_CACHE_DIR, else "
                             "<checkout>/.jax_cache)")
    parser.add_argument("--opt-state-dtype", default=None,
                        choices=["bfloat16", "float32"],
                        help="storage dtype for optimizer state (momentum/"
                             "Adam moments): bfloat16 halves the update's "
                             "HBM traffic; the update still computes in f32 "
                             "and the injected LR stays f32")
    parser.add_argument("--ema-decay", type=float, default=None,
                        help="maintain an EMA of the weights at this decay "
                             "and evaluate with it (train/ema.py)")
    parser.add_argument("--checkify", action="store_true",
                        help="run the train step under jax.experimental."
                             "checkify (NaN/out-of-bounds/div0 checks on "
                             "every op, ~2x step cost) and raise a located "
                             "error — the compiled-mode sanitizer")
    parser.add_argument("--debug-nans", action="store_true",
                        help="jax_debug_nans: re-run the op that produced "
                             "the first NaN un-jitted and raise there (the "
                             "sanitizer analog; SURVEY §5 'race detection/"
                             "sanitizers: NONE' upstream)")
    parser.add_argument("--upload-to", default=None,
                        help="after training, upload the checkpoint dir to "
                             "this destination (gs://, s3://, or a local/"
                             "file:// path) — the cloud-run hook from "
                             "Hourglass/tensorflow/main.py:50-65")
    args = parser.parse_args(argv)

    # JAX's compilation cache is placed BEFORE anything compiles
    # (preflight's probe op would otherwise be the first, uncached one)
    from deep_vision_tpu.core.excache import EXCACHE_ENV, place_compile_cache

    place_compile_cache()
    # the requeue latch is process-wide and main() may be called more than
    # once per process (tests, notebooks): this run's verdict starts clean
    from deep_vision_tpu.obs import flight as _flight_mod

    _flight_mod.clear_requeue()
    if not args.executable_cache:
        from deep_vision_tpu.core import knobs

        args.executable_cache = knobs.get_str(EXCACHE_ENV)
    if args.debug_nans:
        import jax as _jax_cfg

        _jax_cfg.config.update("jax_debug_nans", True)
    cfg = get_config(args.model)

    # environment preflight FIRST (tools/preflight.py): a backend that does
    # not answer, a libtpu version skew, or an unwritable checkpoint volume
    # fails here in seconds — before any dataloader, compile, or epoch
    # burns minutes proving the same thing
    if not args.skip_preflight:
        from deep_vision_tpu.tools.preflight import render, run_preflight

        pf_ckpt = args.ckpt_dir or os.path.join("checkpoints", cfg.name)
        if args.checkpoint and args.checkpoint != "auto":
            pf_ckpt = args.checkpoint  # saves follow the resume dir
        pf_ok, pf_results = run_preflight(
            ckpt_dir=pf_ckpt, excache_dir=args.executable_cache)
        if not pf_ok:
            render(pf_results)
            print("preflight FAILED: fix the environment (or pass "
                  "--skip-preflight to proceed anyway)", flush=True)
            return 1
    if args.epochs is not None:
        cfg.epochs = args.epochs
    if args.batch_size is not None:
        cfg.batch_size = args.batch_size
    # declarative sharding table (parallel/shardmap.py): resolved here so
    # an unknown family/typo is a usage error before any loader is built
    sharding_rules = None
    if args.sharding_rules:
        from deep_vision_tpu.parallel.shardmap import (
            ShardingRuleError,
            get_rules,
        )

        try:
            sharding_rules = get_rules(args.sharding_rules, cfg.model)
        except ShardingRuleError as e:
            parser.error(str(e))
    # per-host sharded loading (multihost.host_shard): in a multi-host
    # world each host reads only its disjoint record-shard slice; the
    # value routes through the CURRENT rendezvous generation, so the
    # elastic layer's per-generation re-derive is inherited for free.
    # Single-host runs pass None — loader fingerprints stay unchanged.
    host_shard = None
    from deep_vision_tpu.parallel import multihost as _mh

    if _mh.process_count() > 1:
        host_shard = _mh.host_shard()
    if args.preprocessing == "tf" and (
        args.fake_data or cfg.dataset.get("kind") != "imagenet"
    ):
        print("warning: --preprocessing tf only applies to the ImageNet "
              "records/folder pipeline; this run uses its default chain")

    # faults install BEFORE any data/checkpoint object is built so loader
    # construction is already covered; the journal attaches once it exists
    if args.fault_spec:
        from deep_vision_tpu.resilience import install_spec

        install_spec(args.fault_spec, seed=args.fault_seed)
        print(f"faults: installed spec {args.fault_spec!r} "
              f"(seed {args.fault_seed})")
    budget = None
    if args.bad_record_budget:
        from deep_vision_tpu.data.records import BadRecordBudget

        default_ckpt = args.ckpt_dir or os.path.join("checkpoints", cfg.name)
        budget = BadRecordBudget.parse(
            args.bad_record_budget,
            dead_letter_path=args.dead_letter or os.path.join(
                default_ckpt, "dead_letter.jsonl"),
        )

    if args.data_service:
        # the trainer consumes the shared service — local data is only
        # needed for the eval split, so its absence must not kill the
        # run (the documented service-consumer invocation passes no
        # --data-dir at all); train_fn is replaced by the service
        # client below either way
        try:
            train_fn, eval_fn = build_dataloaders(
                cfg, args.data_dir, args.fake_data, args.fake_batches,
                args.num_workers, preprocessing=args.preprocessing,
                num_procs=args.num_procs, bad_record_budget=budget,
                host_shard=host_shard,
            )
        except (FileNotFoundError, OSError) as e:
            print(f"--data-service: no local eval dataset ({e}); "
                  "training without an eval split")
            train_fn, eval_fn = (lambda: []), None
        if args.eval_only and eval_fn is None:
            parser.error("--eval-only needs a local eval dataset, which "
                         "--data-service could not find")
    else:
        train_fn, eval_fn = build_dataloaders(
            cfg, args.data_dir, args.fake_data, args.fake_batches,
            args.num_workers, preprocessing=args.preprocessing,
            num_procs=args.num_procs, bad_record_budget=budget,
            host_shard=host_shard,
        )

    if cfg.task in ("dcgan", "cyclegan"):
        if sharding_rules is not None:
            parser.error(
                "--sharding-rules rides the standard Trainer state "
                f"placement; GAN task {cfg.task!r} has its own G/D "
                "trainers without it")
        if args.eval_only:
            parser.error(
                f"--eval-only is not supported for GAN task {cfg.task!r} "
                "(no scalar quality metric; use the sample grids instead)"
            )
        if args.data_service or args.data_snapshot:
            parser.error(
                "--data-service/--data-snapshot ride the standard Trainer "
                f"checkpoint/resume path; GAN task {cfg.task!r} has its own "
                "loop without them"
            )
        import jax as _jax

        from deep_vision_tpu.core.summary import count_params

        journal = _make_journal(args, cfg, budget=budget)
        tracer = _make_tracer(args, journal)
        health = _make_health(args, journal)
        flight = _make_flight(args, journal)
        autoprof = _make_autoprof(
            args, journal, args.ckpt_dir or os.path.join("checkpoints",
                                                         cfg.name),
            window=_parse_profile_window(parser, args.profile_window))
        telemetry = _make_telemetry(
            args, journal, flight,
            args.ckpt_dir or os.path.join("checkpoints", cfg.name))
        if telemetry is not None and health is not None:
            telemetry.add_health("train", health.healthz)
        trainer = build_gan_trainer(
            cfg, journal=journal,
            telemetry_sample_every=args.telemetry_sample_every,
            health=health, autoprof=autoprof)
        if journal is not None:
            journal.write("note", mesh_shape=dict(trainer.mesh.shape))
        states = (
            {"G": trainer.g_state, "D": trainer.d_state}
            if cfg.task == "dcgan"
            else {"G_ab": trainer.gab, "G_ba": trainer.gba,
                  "D_a": trainer.da, "D_b": trainer.db}
        )
        print("model " + cfg.model + ": " + " ".join(
            f"{k}={count_params(s.params):,}" for k, s in states.items()
        ) + " trainable params")
        if args.summary:
            from deep_vision_tpu.core.summary import model_summary
            from deep_vision_tpu.models import get_model as _gm
            import jax.numpy as _jnp

            img = _jnp.ones((2, *cfg.input_shape), _jnp.float32)
            if cfg.task == "dcgan":
                parts = {"G": (_gm("dcgan_generator"), _jnp.ones((2, 100))),
                         "D": (_gm("dcgan_discriminator"), img)}
            else:
                parts = {"G": (_gm("cyclegan_generator"), img),
                         "D": (_gm("cyclegan_discriminator"), img)}
            for k, (mod, sample) in parts.items():
                print(f"-- {k} --")
                print(model_summary(mod, sample))
        # checkpoint/resume: the reference GAN trainers capture G/D/optimizers
        # + epoch and restore-or-initialize (CycleGAN/tensorflow/train.py:
        # 133-148; DCGAN/tensorflow/main.py:34-40); CycleGAN saves every 2
        # epochs (:329-333), DCGAN every epoch with max_to_keep=3 (:40,80-83)
        from deep_vision_tpu.core import CheckpointManager

        start_epoch = 0
        gan_save_every = 2 if cfg.task == "cyclegan" else 1
        ckpt_dir = args.ckpt_dir or os.path.join("checkpoints", cfg.name)
        if args.checkpoint and args.checkpoint != "auto":
            ckpt_dir = args.checkpoint
        gan_ckpt = CheckpointManager(
            ckpt_dir,
            max_to_keep=3 if cfg.task == "dcgan" else None,
            journal=journal,
        )
        if args.checkpoint:
            start_epoch = trainer.restore(gan_ckpt)
            if start_epoch:
                print(f"resumed GAN training at epoch {start_epoch}")
        # preemption-safe like Trainer.fit, via the SAME mechanism
        # (multihost.PreemptionGuard: SIGTERM handler + cross-host
        # consensus at a deterministic cadence)
        from deep_vision_tpu.parallel.multihost import PreemptionGuard

        if health is not None:
            health.start_watchdog()  # no-op without --watchdog-timeout
        with PreemptionGuard() as guard:
            for epoch in range(start_epoch, cfg.epochs):
                # keep per-step metrics as device arrays; float() only at epoch
                # end so the host never blocks async dispatch mid-epoch
                collected: list = []
                interrupted = False
                # poll keyed to the batch index — host-identical (sharded
                # drop_remainder loaders yield equal counts), so every host
                # rendezvouses at the same boundary
                for batch_i, batch in enumerate(
                        trainer.clock.iter_data(train_fn())):
                    if guard.agreed(step=batch_i):
                        interrupted = True
                        break
                    if cfg.task == "dcgan":
                        metrics = trainer.train_step(batch["image"])
                    else:
                        half = len(batch["image"]) // 2 or 1
                        metrics = trainer.train_step(
                            batch["image"][:half], batch["image"][half:half * 2]
                        )
                    collected.append(metrics)
                if collected and not interrupted:
                    # (suppressed on preemption: a partial-epoch summary would
                    # duplicate the re-run epoch's row, as in Trainer.fit)
                    collected = _jax.device_get(collected)  # one host round-trip
                    keys = sorted(collected[0])
                    summary = {
                        k: sum(float(m[k]) for m in collected) / len(collected)
                        for k in keys
                    }
                    print(f"epoch {epoch}: " + " ".join(
                        f"{k}={v:.4f}" for k, v in summary.items()
                    ))
                    if journal is not None:
                        journal.write("epoch", name="gan", epoch=epoch,
                                      summary=summary)
                    # epoch-granularity NaN guard: the GAN loop keeps
                    # per-step metrics on device, so the summary is the
                    # first host-visible place divergence can be caught
                    if health is not None:
                        health.check_summary(epoch, summary)
                if guard.agreed(force=True):
                    # interrupted: mid-epoch states saved under the global
                    # optimizer step, marked so resume re-runs this epoch; a
                    # loop that ran to completion saves the epoch as complete
                    done = epoch if not interrupted else epoch - 1
                    saved = trainer.save(gan_ckpt, epoch, completed_epoch=done)
                    gan_ckpt.wait()
                    print(f"preempted in epoch {epoch}: "
                          + ("checkpoint written" if saved
                             else "checkpoint DECLINED (nothing new to save)"))
                    # same SIGTERM escalation as Trainer._preempt_save:
                    # typed event + the scheduler's requeue exit code
                    if journal is not None:
                        journal.write(
                            "preempt_checkpoint",
                            step=int(gan_ckpt.latest_step() or 0),
                            epoch=epoch, saved=bool(saved),
                            dir=ckpt_dir)
                    _flight_mod.request_requeue()
                    break
                if (epoch + 1) % gan_save_every == 0:
                    trainer.save(gan_ckpt, epoch)
        gan_ckpt.wait()
        _maybe_upload(args, ckpt_dir)
        _finish_obs(args, journal, tracer=tracer, health=health,
                    autoprof=autoprof, flight=flight, telemetry=telemetry)
        # a graceful preemption exits with the requeue code (EX_TEMPFAIL):
        # the scheduler resubmits and the run resumes from the preempt
        # checkpoint — on whatever mesh the new allocation provides
        return (_flight_mod.REQUEUE_EXIT_CODE
                if _flight_mod.requeue_requested() else 0)

    ckpt_dir = args.ckpt_dir or os.path.join("checkpoints", cfg.name)
    journal = _make_journal(args, cfg, budget=budget)
    tracer = _make_tracer(args, journal)
    health = _make_health(args, journal)
    flight = _make_flight(args, journal)
    autoprof = _make_autoprof(
        args, journal, ckpt_dir,
        window=_parse_profile_window(parser, args.profile_window))
    telemetry = _make_telemetry(args, journal, flight, ckpt_dir)
    # -- the data plane's two new modes (data/service.py, data/snapshot.py)
    if args.data_snapshot and args.data_service:
        # refuse BEFORE any client/loader is built: a constructed client
        # would register a journal closer and stamp a phantom
        # data_service summary into a run that never happened
        parser.error(
            "--data-snapshot checkpoints the LOCAL pipeline; a "
            "--data-service stream is shared across consumers and "
            "snapshots nothing (its resume story is the trainer's "
            "step checkpoint + the service's own restart)")
    data_client = None
    if args.data_service:
        from deep_vision_tpu.data.service import DataServiceClient

        data_client = DataServiceClient(args.data_service, name=cfg.name,
                                        journal=journal)
        svc_steps = args.data_service_steps
        train_fn = lambda: data_client.batches(svc_steps)  # noqa: E731
        if journal is not None:
            # closer covers abnormal unwinds; the clean path closes below
            journal.add_closer(data_client.close)
    data_loader = None
    if args.data_snapshot:
        cand = train_fn()
        if (hasattr(cand, "snapshot_supported")
                and cand.snapshot_supported()):
            data_loader = cand
        else:
            parser.error(
                "--data-snapshot needs a snapshot-capable DataLoader: a "
                "real dataset (not --fake-data) with --num-procs 0")
    supervisor = None
    if args.backend_retries > 0:
        from deep_vision_tpu.resilience.elastic import BackendSupervisor

        supervisor = BackendSupervisor(max_retries=args.backend_retries,
                                       journal=journal, name="train.backend")
    excache = None
    if args.executable_cache:
        from deep_vision_tpu.core.excache import ExecutableCache

        excache = ExecutableCache(args.executable_cache, journal=journal)
    trainer = build_trainer(cfg, train_fn, ckpt_dir,
                            tb_dir=args.tensorboard_dir,
                            checkify_errors=args.checkify,
                            ema_decay=args.ema_decay,
                            journal=journal,
                            telemetry_sample_every=args.telemetry_sample_every,
                            health=health, autoprof=autoprof,
                            multistep=args.multistep,
                            device_prefetch=args.device_prefetch,
                            opt_state_dtype=(
                                None if args.opt_state_dtype == "float32"
                                else args.opt_state_dtype),
                            backend_supervisor=supervisor,
                            data_loader=data_loader,
                            steps_per_epoch=(args.data_service_steps
                                             if args.data_service else None),
                            executable_cache=excache,
                            sharding_rules=sharding_rules,
                            telemetry=telemetry)
    if journal is not None:
        # an unwinding run (exception/SIGTERM) still stops an in-flight
        # profiler trace and flushes writers via the atexit crash path
        journal.add_closer(trainer.close)
        journal.write("note", mesh_shape=dict(trainer.mesh.shape))
    # param accounting before training, like summary(net, (3,224,224)) at
    # ResNet/pytorch/train.py:350 / model.summary() at YOLO/tensorflow/train.py:297
    from deep_vision_tpu.core.summary import count_params

    if args.summary:
        from deep_vision_tpu.core.summary import model_summary

        # summarize the exact module build_trainer constructed, not a rebuild
        print(model_summary(trainer.model, sample_input(cfg)))
    print(f"model {cfg.model}: {count_params(trainer.state.params):,} trainable params")
    start_epoch = 0
    if args.checkpoint:
        if args.checkpoint != "auto":
            # saves (and the end-of-run upload) follow the resume dir
            ckpt_dir = args.checkpoint
            trainer.ckpt = type(trainer.ckpt)(ckpt_dir, journal=journal)
        start_epoch = trainer.resume()
        print(f"resumed from step {int(trainer.state.step)} -> epoch {start_epoch}")
    if args.eval_only:
        run_eval_only(cfg, trainer, eval_fn)
        trainer.close()
        _finish_obs(args, journal, tracer=tracer, health=health,
                    autoprof=autoprof, flight=flight, telemetry=telemetry)
        return 0
    trainer.fit(
        train_fn, eval_fn, epochs=cfg.epochs, start_epoch=start_epoch,
        eval_first=args.eval_first,
    )
    trainer.close()
    if data_client is not None:
        data_client.close()  # idempotent: the journal closer may re-run it
    _maybe_upload(args, ckpt_dir)
    _finish_obs(args, journal, tracer=tracer, health=health,
                autoprof=autoprof, flight=flight, telemetry=telemetry)
    # SIGTERM escalation epilogue: the preempt checkpoint is on disk and
    # journaled — exit with the requeue code so the scheduler resubmits
    # (resume rides the cross-mesh restore if the new slice is smaller)
    return (_flight_mod.REQUEUE_EXIT_CODE
            if _flight_mod.requeue_requested() else 0)


if __name__ == "__main__":
    raise SystemExit(main())
