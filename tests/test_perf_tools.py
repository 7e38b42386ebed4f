"""Host-side units of the evidence tooling: the aux-metric naming
convention and the procedural dataset generators of
`tools/convergence_run.py`. (HLO byte counting: tests/test_step_bytes.py.)
"""
import numpy as np
import pytest


def test_aux_metric_prefix_convention():
    """'_'-prefixed aux names surface as metrics WITHOUT touching the loss;
    reserved surfaced names still raise (models/vit.py router telemetry)."""
    import jax.numpy as jnp

    from deep_vision_tpu.losses.classification import classification_loss_fn

    logits = jnp.asarray([[4.0, 0.0], [0.0, 4.0]])
    batch = {"label": jnp.asarray([0, 1])}
    base, _ = classification_loss_fn(logits, batch)
    loss, metrics = classification_loss_fn(
        (logits, {"penalty": jnp.asarray(2.0),
                  "_router_entropy": jnp.asarray(1.5)}),
        batch, penalty_weight=0.01,
    )
    assert metrics["router_entropy"] == 1.5
    # only the un-prefixed penalty moved the loss
    assert float(loss) == pytest.approx(float(base) + 0.02, abs=1e-6)
    with pytest.raises(ValueError):
        classification_loss_fn(
            (logits, {"_loss": jnp.asarray(1.0)}), batch
        )


def test_procedural_shapes_layout():
    from deep_vision_tpu.tools.convergence_run import procedural_shapes

    imgs, boxes, classes = procedural_shapes(8, size=96, seed=3)
    assert imgs.shape == (8, 96, 96, 3) and imgs.dtype == np.float32
    assert boxes.shape == (8, 3, 4) and classes.shape == (8, 3)
    valid = classes >= 0
    assert valid.any(axis=1).all()  # every image has >= 1 object
    # valid boxes are normalized, non-degenerate, in-bounds
    vb = boxes[valid]
    assert (vb[:, 2] > vb[:, 0]).all() and (vb[:, 3] > vb[:, 1]).all()
    assert (vb >= 0).all() and (vb <= 1).all()
    # padded rows are zero boxes (the DetectionEvaluator drop convention)
    assert not boxes[~valid].any()
    # deterministic per seed
    i2, b2, c2 = procedural_shapes(8, size=96, seed=3)
    np.testing.assert_array_equal(boxes, b2)
    np.testing.assert_array_equal(imgs, i2)


def test_procedural_figures_layout():
    from deep_vision_tpu.tools.convergence_run import procedural_figures

    imgs, kpts, heads = procedural_figures(6, size=64, seed=1)
    assert imgs.shape == (6, 64, 64, 3)
    assert kpts.shape == (6, 5, 2) and heads.shape == (6,)
    assert (kpts >= 0).all() and (kpts <= 1).all()
    assert (heads > 0).all() and (heads < 0.5).all()
    # the head keypoint sits inside the drawn head disc: the brightest
    # region around kpt 0 must be far above the noise floor
    for i in range(6):
        x, y = (kpts[i, 0] * 64).astype(int)
        patch = imgs[i, max(y - 2, 0):y + 3, max(x - 2, 0):x + 3]
        assert patch.max() > 0.5


def test_gratings_difficulty_knob():
    from deep_vision_tpu.tools.convergence_run import procedural_gratings

    easy, labels = procedural_gratings(4, classes=16, size=32, noise=0.05)
    hard, _ = procedural_gratings(4, classes=16, size=32, noise=0.6)
    # same class structure, different SNR: hard images have more extreme
    # clipping mass at 0/1
    clip_easy = ((easy <= 0.001) | (easy >= 0.999)).mean()
    clip_hard = ((hard <= 0.001) | (hard >= 0.999)).mean()
    assert clip_hard > clip_easy
    # 32-class variant factors 8 orientations x 4 freqs and stays in range
    imgs32, labels32 = procedural_gratings(8, classes=32, size=32)
    assert labels32.max() < 32


def test_gratings_nonfactoring_class_count_stays_in_freq_range():
    """ADVICE r4: class counts that don't factor as n_orient x n_freq must
    still map every label to a frequency inside the documented 4-13 cycles
    grid (n_freq rounds UP, never leaving labels off-grid)."""
    import math

    import numpy as np

    from deep_vision_tpu.tools.convergence_run import procedural_gratings

    for classes in (20, 30, 5):
        imgs, labels = procedural_gratings(2 * classes, classes=classes,
                                           size=32, seed=1)
        assert labels.max() < classes and np.isfinite(imgs).all()
        # the implementation's own grid: ceil'd n_freq keeps every label's
        # frequency inside [4, 13] cycles
        n_orient = 4 if classes <= 16 else 8
        n_freq = max(1, math.ceil(classes / n_orient))
        for c in range(classes):
            freq = 4.0 + (9.0 / max(1, n_freq - 1)) * (c // n_orient)
            assert 4.0 <= freq <= 13.0 + 1e-9, (classes, c, freq)
