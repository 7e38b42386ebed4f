"""`Trainer.fit` keeps one step in flight (ISSUE 27): it dispatches step N,
then reads, logs and health-checks step N-1's report, and is whole again
wherever it must be: the end of an epoch, a preemption save, an exception
on its way out, `fit`'s return."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deep_vision_tpu.obs.registry import Registry
from deep_vision_tpu.obs.stepclock import StepClock, _StepRecord
from deep_vision_tpu.train import trainer as trainer_mod

STEP, LR = trainer_mod._STEP, trainer_mod._LR


class Journal:
    """What `Trainer` needs of a run journal, kept in memory."""

    def __init__(self):
        self.steps, self.events = [], []

    def step(self, step, **fields):
        self.steps.append({"step": step, **fields})

    def write(self, event, **fields):
        self.events.append({"event": event, **fields})

    def add_tap(self, fn):
        pass

    def add_closer(self, fn):
        pass


def _trainer(mesh8, **kw):
    from deep_vision_tpu.losses import classification_loss_fn
    from deep_vision_tpu.models import get_model
    from deep_vision_tpu.train import Trainer

    # a rate that moves every step, so that a row's `lr` says which step's
    tx = optax.inject_hyperparams(optax.sgd)(
        learning_rate=optax.linear_schedule(0.05, 0.0, 50))
    return Trainer(get_model("lenet5", num_classes=4), tx,
                   classification_loss_fn, jnp.ones((2, 32, 32, 1)),
                   mesh=mesh8, **kw)


def _batches(n, bs=8, nan_at=None):
    rng = np.random.RandomState(0)
    out = [{"image": rng.rand(bs, 32, 32, 1).astype(np.float32),
            "label": rng.randint(0, 4, (bs,)).astype(np.int32)}
           for _ in range(n)]
    if nan_at is not None:
        out[nan_at]["image"][:] = np.nan
    return out


def _fit(trainer, feed, **kw):
    kw.setdefault("handle_preemption", False)
    return trainer.fit(feed if callable(feed) else (lambda: feed), epochs=1,
                       **kw)


def _rows(journal):
    return [(r["step"], r["metrics"]["loss"], r["metrics"]["lr"])
            for r in journal.steps]


@pytest.mark.parametrize("multistep", [1, 2])
def test_fit_journals_what_reading_each_step_at_once_gives(mesh8, multistep):
    k, data = multistep, _batches(5)  # K = 2: two supersteps and a tail
    by_hand = _trainer(mesh8, multistep=k)
    want = []
    while data:
        if k > 1 and len(data) >= k:
            group, data = data[:k], data[k:]
            loss = by_hand.train_superstep(group)[-1]["loss"]
        else:
            loss = by_hand.train_step(data.pop(0))["loss"]
        step = int(by_hand.state.step)  # read at once: the loop as it was
        want.append((step, float(loss), by_hand.lr_at(step)))
    by_hand.close()

    journal = Journal()
    trainer = _trainer(mesh8, multistep=k, journal=journal)
    _fit(trainer, _batches(5))
    trainer.close()
    assert _rows(journal) == want  # count, order and every bit
    assert [r[0] for r in want] == ([1, 2, 3, 4, 5] if k == 1 else [2, 4, 5])
    assert len({r[2] for r in want}) == len(want)  # the rate did move
    for row in journal.steps:
        assert not {STEP, LR} & set(row["metrics"])


def test_fit_over_one_batch_returns_closed(mesh8):
    journal = Journal()
    trainer = _trainer(mesh8, journal=journal)
    state = _fit(trainer, _batches(1))
    assert trainer._in_flight is None
    assert [r["step"] for r in journal.steps] == [1] == [int(state.step)]
    assert np.isfinite(journal.steps[0]["metrics"]["loss"])
    trainer.close()


@pytest.mark.parametrize("multistep", [1, 2])
def test_a_report_is_read_after_the_next_dispatch(mesh8, monkeypatch,
                                                  multistep):
    k = multistep
    journal = Journal()
    trainer = _trainer(mesh8, multistep=k, journal=journal,
                       telemetry_sample_every=1)  # a fence on every step
    order = []
    name = "_dispatch_superstep" if k > 1 else "_dispatch_step"
    dispatch, get = getattr(trainer, name), jax.device_get

    def dispatching(item):
        order.append(("dispatch", trainer.clock.steps_seen))
        return dispatch(item)

    def getting(tree):
        if isinstance(tree, dict) and STEP in tree:
            order.append(("get", int(np.atleast_1d(get(tree[STEP]))[-1]) // k))
        return get(tree)

    fence = _StepRecord._fence

    def fencing(rec, out):
        order.append(("fence", rec.index))
        return fence(rec, out)

    monkeypatch.setattr(trainer, name, dispatching)
    monkeypatch.setattr(jax, "device_get", getting)
    monkeypatch.setattr(_StepRecord, "_fence", fencing)
    _fit(trainer, _batches(4 * k))
    trainer.close()
    assert order == [
        ("dispatch", 1), ("dispatch", 2), ("fence", 1), ("get", 1),
        ("dispatch", 3), ("fence", 2), ("get", 2),
        ("dispatch", 4), ("fence", 3), ("get", 3),
        ("fence", 4), ("get", 4)]  # the flush, once the feed has ended
    # but at the flush, the fence never names the newest dispatch
    newest = 0
    for what, i in order[:-2]:
        newest = i if what == "dispatch" else newest
        assert what == "dispatch" or i < newest
    assert all("sync_ms" in r for r in journal.steps)


def test_a_held_report_survives_the_next_donated_dispatch(mesh8):
    a, b = _batches(2)
    at_once = _trainer(mesh8)
    want = [jax.device_get(at_once._dispatch_step(x)) for x in (a, b)]
    at_once.close()

    trainer = _trainer(mesh8)
    before = trainer.state
    first = trainer._dispatch_step(a)
    assert before.step.is_deleted()  # the state was donated, for real
    between = trainer.state
    second = trainer._dispatch_step(b)
    assert between.step.is_deleted()  # and step 1's state into step 2
    got = [jax.device_get(first), jax.device_get(second)]
    trainer.close()
    assert [int(g[STEP]) for g in got] == [1, 2]
    for g, w in zip(got, want):
        assert g.keys() == w.keys() and {STEP, LR, "loss"} <= g.keys()
        for key in g:
            assert g[key] == w[key], key


def test_abort_names_the_step_that_produced_the_nan(mesh8):
    from deep_vision_tpu.obs.health import HealthMonitor, TrainingHealthError

    journal = Journal()
    health = HealthMonitor(policy="abort", journal=journal,
                           registry=Registry())
    trainer = _trainer(mesh8, journal=journal, health=health,
                       registry=health.registry)
    with pytest.raises(TrainingHealthError, match="at step 3 "):
        _fit(trainer, _batches(6, nan_at=2))
    # step 4 was dispatched before step 3 was read, and goes with the state
    assert int(trainer.state.step) == 4
    assert [r["step"] for r in journal.steps] == [1, 2, 3]
    assert trainer._in_flight is None
    aborts = [e for e in journal.events if e["event"] == "health"]
    assert [e["step"] for e in aborts] == [3]
    trainer.close()


@pytest.mark.parametrize("multistep", [1, 2])
def test_preemption_flushes_the_step_in_flight_first(mesh8, tmp_path,
                                                     multistep):
    from deep_vision_tpu.core import CheckpointManager
    from deep_vision_tpu.obs import flight

    k = multistep
    journal = Journal()
    trainer = _trainer(mesh8, multistep=k, journal=journal,
                       checkpoint_manager=CheckpointManager(str(tmp_path)))
    data = _batches(6 * k)

    def feed():
        for i, batch in enumerate(data):
            if i == 2 * k:  # agreed at the next poll: while this one flies
                trainer._pguard.requested = True
            yield batch

    flight.clear_requeue()
    try:
        _fit(trainer, feed, handle_preemption=True, preemption_poll_every=1)
        assert trainer.preempted and trainer._in_flight is None
        n = 3 * k  # dispatch 3 was in flight when dispatch 2's poll agreed
        assert int(trainer.state.step) == n
        assert [r["step"] for r in journal.steps] == [k, 2 * k, n]
        assert trainer.ckpt.latest_step() == n
        saved, = [e for e in journal.events
                  if e["event"] == "preempt_checkpoint"]
        assert saved["step"] == n and saved["saved"] is True
    finally:
        flight.clear_requeue()
        trainer.close()


def test_an_exception_out_of_the_feed_flushes_the_pending_row(mesh8):
    journal = Journal()
    trainer = _trainer(mesh8, journal=journal)
    data = _batches(2)

    def feed():
        yield from data
        raise OSError("the feed broke")

    with pytest.raises(OSError, match="the feed broke"):
        _fit(trainer, feed)
    assert [r["step"] for r in journal.steps] == [1, 2]
    assert trainer._in_flight is None
    trainer.close()


@pytest.mark.parametrize("multistep", [1, 2])
def test_step_times_add_up_to_the_epochs_wall(mesh8, monkeypatch, multistep):
    journal = Journal()
    trainer = _trainer(mesh8, multistep=multistep, journal=journal)
    data = _batches(6)
    stamps = {}

    def feed():
        stamps["start"] = time.perf_counter()
        for batch in data:
            time.sleep(0.02)
            yield batch

    end_epoch = trainer.logger.end_epoch

    def ending(epoch):
        stamps["end"] = time.perf_counter()
        return end_epoch(epoch)

    monkeypatch.setattr(trainer.logger, "end_epoch", ending)
    _fit(trainer, feed)
    trainer.close()
    wall_ms = (stamps["end"] - stamps["start"]) * 1e3
    total = sum(r["step_time_ms"] for r in journal.steps)
    assert total == pytest.approx(wall_ms, rel=0.02)


def test_covered_counts_the_reads_that_found_the_report_not_ready(mesh8):
    reg = Registry()
    clock = StepClock(registry=reg, name="t", sample_every=1000,
                      track_memory=False)
    covered, steps = (reg.counter("t_steps_covered_total"),
                      reg.counter("t_steps_total"))

    class Busy:  # a leaf the device still has in work
        def is_ready(self):
            return False

    ready = jax.block_until_ready(jnp.ones(()))
    for report, now in (({"loss": ready}, 0), ({"loss": Busy()}, 1),
                        ({"loss": ready}, 1), ({"loss": Busy()}, 2)):
        with clock.step(batch_size=1, auto_commit=False) as rec:
            pass
        rec.await_report(report)
        rec.commit()
        assert covered.value == now
    assert steps.value == 4

    reg = Registry()
    trainer = _trainer(mesh8, registry=reg)
    _fit(trainer, _batches(5))
    trainer.close()
    assert reg.counter("train_steps_total").value == 5
    assert 0 <= reg.counter("train_steps_covered_total").value <= 5
