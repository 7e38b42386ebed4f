"""End-to-end trainer tests on the 8-device virtual CPU mesh.

The integration-smoke analog of the reference's LeNet/MNIST run
(LeNet/pytorch/train.py): a tiny synthetic problem must converge.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.core.metrics import topk_accuracy
from deep_vision_tpu.losses import classification_loss_fn
from deep_vision_tpu.models import get_model
from deep_vision_tpu.train import Trainer, build_optimizer, ReduceLROnPlateau


def synthetic_mnist(n=256, seed=0):
    """Linearly-separable-ish 32x32 images: class = brightest quadrant."""
    rng = np.random.RandomState(seed)
    images = rng.rand(n, 32, 32, 1).astype(np.float32) * 0.1
    labels = rng.randint(0, 4, size=n)
    for i, l in enumerate(labels):
        r, c = divmod(l, 2)
        images[i, r * 16:(r + 1) * 16, c * 16:(c + 1) * 16, 0] += 0.9
    return images, labels


def batches(images, labels, bs):
    for i in range(0, len(images) - bs + 1, bs):
        yield {"image": images[i:i + bs], "label": labels[i:i + bs]}


@pytest.fixture(scope="module")
def lenet_trainer(mesh8):
    model = get_model("lenet5", num_classes=4)
    tx = build_optimizer("adam", 1e-3)
    return Trainer(
        model, tx, classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)),
        mesh=mesh8,
    )


def test_train_step_decreases_loss(lenet_trainer):
    images, labels = synthetic_mnist()
    first_loss, last_loss = None, None
    for epoch in range(3):
        for batch in batches(images, labels, 32):
            metrics = lenet_trainer.train_step(batch)
            if first_loss is None:
                first_loss = float(metrics["loss"])
            last_loss = float(metrics["loss"])
    assert last_loss < first_loss * 0.5, (first_loss, last_loss)


def test_eval_accuracy_high_after_training(lenet_trainer):
    # runs after the training test (module-scoped fixture keeps state)
    images, labels = synthetic_mnist(seed=1)
    metrics = lenet_trainer.eval_step({"image": images[:64], "label": labels[:64]})
    assert float(metrics["top1"]) > 0.9


def test_state_is_replicated_on_mesh(lenet_trainer, mesh8):
    leaf = jax.tree_util.tree_leaves(lenet_trainer.state.params)[0]
    assert len(leaf.sharding.device_set) == 8


def test_topk_accuracy_exact():
    logits = jnp.array([[0.1, 0.5, 0.2, 0.0], [0.9, 0.0, 0.05, 0.05]])
    labels = jnp.array([1, 2])
    acc = topk_accuracy(logits, labels, ks=(1, 2, 3))
    assert float(acc["top1"]) == pytest.approx(0.5)
    assert float(acc["top3"]) == pytest.approx(1.0)


def test_plateau_schedule():
    from deep_vision_tpu.train.optimizers import ReduceLROnPlateau

    p = ReduceLROnPlateau(factor=0.1, patience=1, mode="max")
    assert p.step(0.5) == 1.0
    assert p.step(0.4) == 1.0   # 1 bad epoch <= patience
    assert p.step(0.4) == 0.1   # 2nd bad epoch triggers decay
    assert p.step(0.6) == 0.1   # improvement holds the new scale
    sd = p.state_dict()
    q = ReduceLROnPlateau(factor=0.1, patience=1, mode="max")
    q.load_state_dict(sd)
    assert q.scale == 0.1


def test_partial_batch_padded_and_masked(lenet_trainer):
    # 20 rows on an 8-device mesh: not divisible -> padded to 24 + masked
    images, labels = synthetic_mnist(seed=2)
    full = lenet_trainer.eval_step({"image": images[:64], "label": labels[:64]})
    part = lenet_trainer.eval_step({"image": images[:20], "label": labels[:20]})
    assert 0.0 <= float(part["top1"]) <= 1.0
    # padded rows must not dilute accuracy: a perfectly-trained model stays 1.0
    assert float(full["top1"]) == pytest.approx(1.0)
    assert float(part["top1"]) == pytest.approx(1.0)


@pytest.mark.slow
def test_fit_with_plateau_and_eval(mesh8, tmp_path):
    model = get_model("lenet5", num_classes=4)
    tx = build_optimizer("sgd", 0.05, momentum=0.9)
    trainer = Trainer(
        model, tx, classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)),
        mesh=mesh8,
        plateau=ReduceLROnPlateau(patience=0, mode="max"),
    )
    images, labels = synthetic_mnist(n=128)

    trainer.fit(
        lambda: batches(images, labels, 32),
        lambda: batches(images, labels, 32),
        epochs=2,
        eval_first=True,
    )
    assert int(trainer.state.step) == 8
    assert len(trainer.eval_logger.history["top1"]) == 3  # eval_first + 2 epochs


def test_plateau_write_does_not_recompile_the_step(mesh8):
    """The plateau writes the LR into opt_state after every epoch's eval; a
    leaf that lands anywhere but where the old one lived changes the step's
    input layout, and the whole step compiles a second time at the first
    step of epoch 2 (seen on the flagship: one extra full compile)."""
    trainer = Trainer(
        get_model("lenet5", num_classes=4),
        build_optimizer("sgd", 0.05, momentum=0.9), classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8,
        plateau=ReduceLROnPlateau(patience=0, mode="max"),
    )
    images, labels = synthetic_mnist(n=64)
    data = lambda: batches(images, labels, 32)
    lr_leaf = trainer.state.opt_state.hyperparams["learning_rate"]
    trainer.fit(data, data, epochs=3)
    new_leaf = trainer.state.opt_state.hyperparams["learning_rate"]
    assert new_leaf.sharding == lr_leaf.sharding
    assert float(new_leaf) < 0.05  # the plateau did write
    assert trainer._train_step._cache_size() == 1


@pytest.mark.slow
def test_fit_raises_on_diverged_loss(mesh8):
    """Failure detection: a NaN epoch must stop the run loudly (SURVEY §5)."""
    import jax.numpy as jnp

    model = get_model("lenet5", num_classes=4)
    tx = build_optimizer("sgd", 1e-3)
    trainer = Trainer(
        model, tx, classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8,
    )
    images, labels = synthetic_mnist(64)
    images[0] = np.nan  # a poisoned batch: the loss goes non-finite
    with pytest.raises(FloatingPointError, match="diverged"):
        trainer.fit(lambda: batches(images, labels, 32), epochs=3)


@pytest.mark.slow
def test_checkify_mode_locates_nan_in_step(mesh8):
    """Sanitizer mode (SURVEY §2.7): checkify raises a located error on the
    first poisoned op inside the jitted step, instead of finishing the epoch
    with garbage."""
    from jax.experimental import checkify as _checkify

    model = get_model("lenet5", num_classes=4)
    tx = build_optimizer("sgd", 1e-3)
    trainer = Trainer(
        model, tx, classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8,
        checkify_errors=True,
    )
    images, labels = synthetic_mnist(64)
    # clean step passes and trains
    m = trainer.train_step({"image": images[:32], "label": labels[:32]})
    assert np.isfinite(float(m["loss"]))
    # poisoned batch raises from inside the step with a location
    bad = images[:32].copy()
    bad[0] = np.nan
    with pytest.raises(_checkify.JaxRuntimeError, match="nan"):
        trainer.train_step({"image": bad, "label": labels[:32]})


@pytest.mark.slow
def test_preemption_checkpoints_and_resumes(mesh8, tmp_path):
    """Elastic recovery (SURVEY §2.7 upstream: 'recovery = manual resume'):
    SIGTERM mid-epoch finishes the in-flight step, writes a checkpoint, and
    fit returns; a fresh Trainer resumes the incomplete epoch."""
    import os
    import signal

    from deep_vision_tpu.core import CheckpointManager

    images, labels = synthetic_mnist()

    def make():
        return Trainer(
            get_model("lenet5", num_classes=4),
            build_optimizer("adam", 1e-3),
            classification_loss_fn,
            sample_input=jnp.zeros((8, 32, 32, 1)),
            mesh=mesh8,
            checkpoint_manager=CheckpointManager(str(tmp_path)),
        )

    def preempting_batches():
        for i, b in enumerate(batches(images, labels, 32)):
            if i == 2:  # "maintenance event" after 2 steps of epoch 0
                os.kill(os.getpid(), signal.SIGTERM)
            yield b

    trainer = make()
    trainer.fit(preempting_batches, epochs=5)  # returns instead of dying
    saved_step = int(trainer.state.step)
    assert saved_step == 3  # the in-flight 3rd step completed, then stopped

    trainer2 = make()
    next_epoch = trainer2.resume()
    assert next_epoch == 0  # incomplete epoch is re-run
    assert int(trainer2.state.step) == saved_step
    trainer2.fit(lambda: batches(images, labels, 32), epochs=2,
                 start_epoch=next_epoch)
    assert int(trainer2.state.step) == saved_step + 2 * 8


@pytest.mark.slow
def test_preemption_during_eval_saves_completed_epoch(mesh8, tmp_path):
    """SIGTERM mid-eval: eval bails early, the finished training epoch is
    checkpointed as complete, and resume continues at the NEXT epoch."""
    import os
    import signal

    from deep_vision_tpu.core import CheckpointManager

    images, labels = synthetic_mnist()

    def make():
        return Trainer(
            get_model("lenet5", num_classes=4),
            build_optimizer("adam", 1e-3),
            classification_loss_fn,
            sample_input=jnp.zeros((8, 32, 32, 1)),
            mesh=mesh8,
            checkpoint_manager=CheckpointManager(str(tmp_path)),
        )

    def preempting_eval():
        os.kill(os.getpid(), signal.SIGTERM)
        yield from batches(images[:64], labels[:64], 32)

    trainer = make()
    trainer.fit(lambda: batches(images, labels, 32), preempting_eval,
                epochs=5)
    assert int(trainer.state.step) == 8  # epoch 0 trained fully

    trainer2 = make()
    assert trainer2.resume() == 1  # epoch 0 is complete; eval is re-runnable
    assert int(trainer2.state.step) == 8


def test_schedule_plus_plateau_rejected(mesh8):
    """One LR policy per recipe (VERDICT r2 weak #6): a scheduled LR is
    re-evaluated inside the jitted step and silently overrides plateau
    writes, so the combination is refused at construction."""
    from deep_vision_tpu.configs import ExperimentConfig
    from deep_vision_tpu.train.optimizers import make_schedule

    with pytest.raises(ValueError, match="schedule.*plateau|plateau"):
        ExperimentConfig(
            name="bad", task="classification", model="lenet5",
            schedule={"kind": "step", "step_size_epochs": 10},
            plateau={"factor": 0.1},
        )

    model = get_model("lenet5", num_classes=4)
    tx = build_optimizer(
        "sgd", make_schedule("step", 0.1, step_size=10), momentum=0.9
    )
    with pytest.raises(ValueError, match="schedule"):
        Trainer(
            model, tx, classification_loss_fn,
            sample_input=jnp.zeros((8, 32, 32, 1)),
            mesh=mesh8, plateau=ReduceLROnPlateau(),
        )


@pytest.mark.slow
def test_current_lr_tracks_schedule(mesh8):
    """The logged LR must be the schedule's current value, not NaN
    (VERDICT r2 weak #6): inject_hyperparams re-evaluates scheduled
    hyperparams each step and current_lr reads the live value."""
    from deep_vision_tpu.train.optimizers import make_schedule

    model = get_model("lenet5", num_classes=4)
    sched = make_schedule("step", 0.1, step_size=2, gamma=0.5)
    tx = build_optimizer("sgd", sched, momentum=0.9)
    tr = Trainer(
        model, tx, classification_loss_fn,
        sample_input=jnp.zeros((8, 32, 32, 1)), mesh=mesh8,
    )
    assert np.isclose(tr.current_lr, 0.1)
    images, labels = synthetic_mnist(n=64)
    for batch in batches(images, labels, 16):
        tr.train_step(batch)
    # 4 steps at gamma=0.5, step_size=2: steps 0-1 ran at 0.1, steps 2-3 at
    # 0.05; current_lr reads the LR the LAST applied update used
    assert np.isclose(tr.current_lr, 0.05), tr.current_lr
