"""Perf layer: the Pallas NMS kernel, scan-multistep Trainer, device
prefetch, bf16 optimizer state. (BatchNorm's fused tail is plain
jax.numpy: tests/test_bn_tail.py.)

Kernel tests run the REAL Pallas kernel under interpret=True (the same
code path the TPU compiles), against pure-lax references. Multistep tests
prove the one-dispatch-per-K-steps contract.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# -- pallas NMS -------------------------------------------------------------

def _detections(seed, b=2, n=256):
    rng = np.random.RandomState(seed)
    xy = rng.rand(b, n, 2).astype(np.float32) * 0.8
    wh = rng.rand(b, n, 2).astype(np.float32) * 0.25 + 0.02
    boxes = jnp.asarray(np.concatenate([xy, xy + wh], -1))
    scores = jnp.asarray(rng.rand(b, n).astype(np.float32))
    classes = jnp.asarray(rng.randint(0, 6, size=(b, n)).astype(np.int32))
    return boxes, scores, classes


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_nms_exact_parity(seed):
    from deep_vision_tpu.ops.nms import non_maximum_suppression

    boxes, scores, classes = _detections(seed)
    kw = dict(max_detections=25, iou_threshold=0.5, score_threshold=0.3)
    lax_out = non_maximum_suppression(boxes, scores, classes, impl="lax", **kw)
    pal_out = non_maximum_suppression(boxes, scores, classes, impl="pallas",
                                      **kw)
    for u, v, name in zip(lax_out, pal_out,
                          ("boxes", "scores", "classes", "valid")):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                      err_msg=f"seed {seed}: {name}")


def test_pallas_nms_under_jit_and_env_flag(monkeypatch):
    from deep_vision_tpu.ops.nms import non_maximum_suppression

    boxes, scores, classes = _detections(3)
    want = non_maximum_suppression(boxes, scores, classes, impl="lax",
                                   max_detections=10)
    # env flag forces the kernel for impl=None callers (inference paths)
    monkeypatch.setenv("DVT_NMS_IMPL", "pallas")
    f = jax.jit(lambda b, s, c: non_maximum_suppression(
        b, s, c, max_detections=10))
    got = f(boxes, scores, classes)
    for u, v in zip(want, got):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def test_nms_impl_rejects_unknown():
    from deep_vision_tpu.ops.nms import non_maximum_suppression

    boxes, scores, _ = _detections(0)
    with pytest.raises(ValueError, match="unknown NMS impl"):
        non_maximum_suppression(boxes, scores, impl="cuda")


def test_nms_env_flag_typo_is_loud(monkeypatch):
    """A mistyped DVT_NMS_IMPL must raise, not silently run 'auto' —
    the disable flag exists for triage."""
    from deep_vision_tpu.ops.nms import non_maximum_suppression

    boxes, scores, _ = _detections(0)
    monkeypatch.setenv("DVT_NMS_IMPL", "LAX")
    with pytest.raises(ValueError, match="DVT_NMS_IMPL"):
        non_maximum_suppression(boxes, scores, max_detections=5)


# -- device prefetch --------------------------------------------------------

def test_device_prefetch_depth2_never_starves():
    from deep_vision_tpu.data.device_prefetch import (
        DevicePrefetcher, PlacedBatch)
    from deep_vision_tpu.obs.registry import Registry

    reg = Registry()
    pf = DevicePrefetcher(place_one=lambda b: PlacedBatch(b, 1, 1),
                          depth=2, name="t", registry=reg)
    seen = 0
    for item in pf(iter(range(16))):
        # consumer slower than producer, by more than a producer thread
        # waits for a core while six test workers share the machine
        time.sleep(0.02)
        assert isinstance(item, PlacedBatch)
        seen += 1
    assert seen == 16
    assert reg.counter("device_prefetch_starved_total",
                       labels={"loader": "t"}).value == 0
    assert reg.counter("device_prefetch_batches_total",
                       labels={"loader": "t"}).value == 16


def test_device_prefetch_starvation_detected():
    from deep_vision_tpu.data.device_prefetch import (
        DevicePrefetcher, PlacedBatch)
    from deep_vision_tpu.obs.registry import Registry

    reg = Registry()

    def slow():
        for i in range(8):
            time.sleep(0.01)
            yield i

    pf = DevicePrefetcher(place_one=lambda b: PlacedBatch(b, 1, 1),
                          depth=1, name="s", registry=reg)
    list(pf(slow()))
    assert reg.counter("device_prefetch_starved_total",
                       labels={"loader": "s"}).value > 0


def test_device_prefetch_groups_and_tail():
    from deep_vision_tpu.data.device_prefetch import (
        DevicePrefetcher, PlacedBatch)
    from deep_vision_tpu.obs.registry import Registry

    pf = DevicePrefetcher(
        place_one=lambda b: PlacedBatch(("one", b), 1, 1),
        place_group=lambda bs: PlacedBatch(("grp", tuple(bs)), len(bs),
                                           len(bs)),
        depth=2, group=3, name="g", registry=Registry())
    items = list(pf(iter(range(7))))  # 2 full groups + 1-batch tail
    assert [it.group for it in items] == [3, 3, 1]
    assert items[0].data == ("grp", (0, 1, 2))
    assert items[2].data == ("one", 6)


def test_device_prefetch_propagates_source_error():
    from deep_vision_tpu.data.device_prefetch import (
        DevicePrefetcher, PlacedBatch)
    from deep_vision_tpu.obs.registry import Registry

    def bad():
        yield 1
        raise RuntimeError("decode exploded")

    pf = DevicePrefetcher(place_one=lambda b: PlacedBatch(b, 1, 1),
                          depth=2, name="e", registry=Registry())
    with pytest.raises(RuntimeError, match="decode exploded"):
        list(pf(bad()))


# -- scan-multistep Trainer -------------------------------------------------

def _lenet_trainer(mesh8, multistep=1, device_prefetch=0, journal=None,
                   registry=None, tx=None):
    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    model = get_model("lenet5", num_classes=4)
    tx = tx or build_optimizer("sgd", 0.05, momentum=0.9)
    return Trainer(model, tx, classification_loss_fn,
                   sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8,
                   multistep=multistep, device_prefetch=device_prefetch,
                   journal=journal, registry=registry)


def _mk_batches(n, bs=32, seed=0):
    rng = np.random.RandomState(seed)
    return [{"image": rng.rand(bs, 32, 32, 1).astype(np.float32),
             "label": rng.randint(0, 4, size=bs)} for _ in range(n)]


def test_multistep_superstep_equivalent_to_single_steps(mesh8):
    batches = _mk_batches(4)
    t1 = _lenet_trainer(mesh8, multistep=1)
    t4 = _lenet_trainer(mesh8, multistep=4)
    singles = [t1.train_step(b) for b in batches]
    stacked = t4.train_superstep(batches)
    # same RNG derivation, same update order: float-ulp agreement
    p1, p4 = jax.device_get((t1.state.params, t4.state.params))
    for u, v in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p4)):
        np.testing.assert_allclose(u, v, rtol=1e-6, atol=1e-6)
    for i in range(4):
        assert abs(float(singles[i]["loss"])
                   - float(stacked[i]["loss"])) <= 1e-5
    assert int(t1.state.step) == int(t4.state.step) == 4


def test_multistep_fit_tail_and_journal(mesh8, tmp_path):
    from deep_vision_tpu.obs.journal import RunJournal
    from deep_vision_tpu.obs.registry import Registry

    jpath = tmp_path / "ms.jsonl"
    batches = _mk_batches(7)  # 2 groups of 3 + 1 tail single
    with RunJournal(str(jpath), kind="train") as j:
        j.manifest(config={})
        t = _lenet_trainer(mesh8, multistep=3, journal=j,
                           registry=Registry())
        t.fit(lambda: iter(batches), epochs=1, handle_preemption=False)
        assert int(t.state.step) == 7
    rows = [json.loads(line) for line in open(jpath)]
    steps = [r for r in rows if r["event"] == "step"]
    assert [r.get("multistep") for r in steps] == [3, 3, None]
    assert [r["step"] for r in steps] == [3, 6, 7]
    # per-microstep series reach the logger: 7 rows, not 3
    assert len(t.logger.history["loss"]) == 1  # one epoch summary


def test_multistep_second_epoch_compiles_nothing(mesh8):
    """The superstep executable is compiled once: a second epoch over the
    same shapes adds no backend compile."""
    from deep_vision_tpu.obs.stepclock import recompile_count

    batches = _mk_batches(8, seed=1)
    t = _lenet_trainer(mesh8, multistep=4)
    t.fit(lambda: iter(batches), epochs=1, handle_preemption=False)
    before = recompile_count()
    t.fit(lambda: iter(batches), epochs=2, start_epoch=1,
          handle_preemption=False)
    assert recompile_count() == before
    assert int(t.state.step) == 16  # 4 dispatches of 4 steps


def test_multistep_partial_batch_inside_full_group(mesh8):
    """A short final batch landing INSIDE a full K-group must be padded to
    the group's common size and masked, not crash np.stack."""
    batches = _mk_batches(2, bs=32) + _mk_batches(1, bs=8, seed=9)
    t = _lenet_trainer(mesh8, multistep=3)
    metrics = t.train_superstep(batches)  # group of [32, 32, 8]
    assert int(t.state.step) == 3
    assert all(np.isfinite(float(m["loss"])) for m in metrics)
    # and through fit with the device prefetcher grouping in its thread
    t2 = _lenet_trainer(mesh8, multistep=3, device_prefetch=2)
    t2.fit(lambda: iter(list(batches)), epochs=1, handle_preemption=False)
    assert int(t2.state.step) == 3


def test_multistep_logs_per_microstep_lr_under_schedule(mesh8):
    """With an LR schedule, each microstep's logged lr must be the
    schedule's value at that step, not the last microstep's."""
    import optax

    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer

    sched = optax.linear_schedule(0.1, 0.0, 100)
    tx = optax.inject_hyperparams(optax.sgd)(learning_rate=sched)
    t = Trainer(get_model("lenet5", num_classes=4), tx,
                classification_loss_fn,
                sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8,
                multistep=4, lr_schedule=sched)
    seen = []
    orig = t.logger.log_step
    t.logger.log_step = lambda step, m, **kw: (
        seen.append((step, kw.get("lr"))), orig(step, m, **kw))
    t.fit(lambda: iter(_mk_batches(4)), epochs=1, handle_preemption=False)
    lrs = dict(seen)
    for step in (1, 2, 3, 4):
        assert lrs[step] == pytest.approx(float(sched(step - 1)), rel=1e-6)
    assert lrs[1] != lrs[4]  # the series actually moves within a dispatch


def test_multistep_with_device_prefetch_fit(mesh8):
    from deep_vision_tpu.obs.registry import Registry

    reg = Registry()
    batches = _mk_batches(8, seed=2)
    t = _lenet_trainer(mesh8, multistep=2, device_prefetch=2, registry=reg)
    t.fit(lambda: iter(batches), epochs=1, handle_preemption=False)
    assert int(t.state.step) == 8
    assert reg.counter("device_prefetch_batches_total",
                       labels={"loader": "train"}).value == 4  # 4 groups


def test_multistep_refuses_checkify_and_ema(mesh8):
    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer, build_optimizer

    model = get_model("lenet5", num_classes=4)
    kw = dict(loss_fn=classification_loss_fn,
              sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8)
    with pytest.raises(ValueError, match="checkify"):
        Trainer(model, build_optimizer("sgd", 0.05), multistep=2,
                checkify_errors=True, **kw)
    with pytest.raises(ValueError, match="ema"):
        Trainer(model, build_optimizer("sgd", 0.05), multistep=2,
                ema_decay=0.99, **kw)


def test_superstep_rejects_wrong_group_size(mesh8):
    t = _lenet_trainer(mesh8, multistep=3)
    with pytest.raises(ValueError, match="superstep got 2"):
        t.train_superstep(_mk_batches(2))
    t1 = _lenet_trainer(mesh8, multistep=1)
    with pytest.raises(ValueError, match="multistep"):
        t1.train_superstep(_mk_batches(2))


def test_trainer_accepts_placed_batch(mesh8):
    t = _lenet_trainer(mesh8)
    b = _mk_batches(1)[0]
    placed = t._place_one(b)
    metrics = t.train_step(placed)
    assert np.isfinite(float(metrics["loss"]))
    assert placed.n == 32


# -- bf16 optimizer state ---------------------------------------------------

def test_bf16_opt_state_dtypes_and_training(mesh8):
    from deep_vision_tpu.train import build_optimizer

    tx = build_optimizer("sgd", 0.05, momentum=0.9, state_dtype="bfloat16")
    t = _lenet_trainer(mesh8, tx=tx)
    b = _mk_batches(1)[0]
    losses = [float(t.train_step(b)["loss"]) for _ in range(6)]
    assert losses[-1] < losses[0]  # still optimizes on the same batch
    dtypes = set()
    jax.tree_util.tree_map(
        lambda x: dtypes.add(str(x.dtype))
        if jnp.issubdtype(x.dtype, jnp.floating) else None,
        t.state.opt_state.inner_state)
    assert dtypes == {"bfloat16"}  # the big state rounds, nothing else
    # the injected LR stays f32 — plateau writes are unaffected
    assert t.state.opt_state.hyperparams["learning_rate"].dtype == jnp.float32


def test_bf16_opt_state_adam_moments():
    from deep_vision_tpu.train import build_optimizer

    tx = build_optimizer("adam", 1e-3, state_dtype="bfloat16")
    params = {"w": jnp.ones((4, 4))}
    state = tx.init(params)
    grads = {"w": jnp.full((4, 4), 0.1)}
    updates, state = tx.update(grads, state, params)
    dtypes = set()
    jax.tree_util.tree_map(
        lambda x: dtypes.add(str(x.dtype))
        if jnp.issubdtype(x.dtype, jnp.floating) else None,
        state.inner_state)
    assert dtypes == {"bfloat16"}
    assert updates["w"].dtype == jnp.float32  # updates stay full precision
