"""Checkpoint/resume: snapshot-index protocol + chunked-boosting resume
(reference: utils/snapshot.h, gradient_boosted_trees.cc:345-427
TryLoadSnapshotFromDisk/CreateSnapshot, fault injection worker.cc:415)."""

import os

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.learners.gbt import _TrainingAborted
from ydf_tpu.utils.snapshot import Snapshots


def _data(n=1500, seed=2):
    rng = np.random.RandomState(seed)
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    y = (x1 + 0.5 * x2 + rng.normal(scale=0.5, size=n) > 0).astype(np.int64)
    return {"x1": x1, "x2": x2, "y": y}


def test_snapshot_protocol(tmp_path):
    s = Snapshots(str(tmp_path), max_kept=2)
    assert s.latest() is None
    s.save(5, {"a": np.arange(3)}, meta={"k": 1})
    s.save(10, {"a": np.arange(4)}, meta={"k": 2})
    s.save(15, {"a": np.arange(5)}, meta={"k": 3})
    idx, arrays, meta = s.latest()
    assert idx == 15 and meta["k"] == 3 and len(arrays["a"]) == 5
    # max_kept=2: payload 5 pruned, index keeps the survivors.
    assert not os.path.isfile(str(tmp_path / "snapshot_5.npz"))
    assert s.indices() == [5, 10, 15]


def test_snapshot_corrupt_payload_falls_back(tmp_path):
    s = Snapshots(str(tmp_path))
    s.save(1, {"a": np.arange(2)}, meta={})
    s.save(2, {"a": np.arange(3)}, meta={})
    # Corrupt the newest payload: latest() must fall back to snapshot 1
    # (crash-safe order: payload write precedes index update).
    with open(str(tmp_path / "snapshot_2.npz"), "wb") as f:
        f.write(b"garbage")
    idx, arrays, _ = s.latest()
    assert idx == 1 and len(arrays["a"]) == 2


def test_chunked_training_equals_single_shot(tmp_path):
    data = _data()
    kw = dict(label="y", num_trees=12, max_depth=3, random_seed=7)
    base = ydf.GradientBoostedTreesLearner(**kw).train(data)
    chunked = ydf.GradientBoostedTreesLearner(
        working_dir=str(tmp_path), resume_training_snapshot_interval_trees=5,
        **kw,
    ).train(data)
    np.testing.assert_array_equal(base.predict(data), chunked.predict(data))


def test_kill_and_resume(tmp_path):
    data = _data()
    kw = dict(label="y", num_trees=12, max_depth=3, random_seed=7)
    base = ydf.GradientBoostedTreesLearner(**kw).train(data)

    learner = ydf.GradientBoostedTreesLearner(
        working_dir=str(tmp_path), resume_training_snapshot_interval_trees=5,
        **kw,
    )
    learner._abort_after_chunks = 1  # fault injection after 5 trees
    with pytest.raises(_TrainingAborted):
        learner.train(data)

    resumed = ydf.GradientBoostedTreesLearner(
        working_dir=str(tmp_path), resume_training=True,
        resume_training_snapshot_interval_trees=5, **kw,
    ).train(data)
    np.testing.assert_array_equal(base.predict(data), resumed.predict(data))


def test_resume_refuses_mismatched_config(tmp_path):
    data = _data()
    learner = ydf.GradientBoostedTreesLearner(
        label="y", num_trees=10, max_depth=3,
        working_dir=str(tmp_path), resume_training_snapshot_interval_trees=5,
    )
    learner._abort_after_chunks = 1
    with pytest.raises(_TrainingAborted):
        learner.train(data)
    with pytest.raises(ValueError, match="different"):
        ydf.GradientBoostedTreesLearner(
            label="y", num_trees=10, max_depth=6,  # changed hyperparameter
            working_dir=str(tmp_path), resume_training=True,
            resume_training_snapshot_interval_trees=5,
        ).train(data)


def test_chunked_early_stopping_saves_compute():
    """With a working_dir, training stops between chunks once the
    validation loss stalls (reference early_stopping.h look-ahead),
    instead of training all requested trees."""
    import tempfile

    rng = np.random.RandomState(3)
    n = 800
    x = rng.normal(size=n)
    y = (x + rng.normal(scale=2.0, size=n) > 0).astype(np.int64)  # noisy
    data = {"x": x, "y": y}
    with tempfile.TemporaryDirectory() as d:
        m = ydf.GradientBoostedTreesLearner(
            label="y", num_trees=200, max_depth=3,
            early_stopping="LOSS_INCREASE",
            early_stopping_num_trees_look_ahead=10,
            working_dir=d, resume_training_snapshot_interval_trees=10,
        ).train(data)
    assert m.num_trees() < 200  # stopped early


def test_inloop_early_stopping_without_working_dir():
    """WITHOUT a working_dir the boosting loop must also stop in-loop
    (reference early_stopping.h:29-66) — round 1 trained all num_trees
    and truncated post-hoc, wasting the wall-clock the reference saves."""
    rng = np.random.RandomState(3)
    n = 800
    x = rng.normal(size=n)
    y = (x + rng.normal(scale=2.0, size=n) > 0).astype(np.int64)  # noisy
    data = {"x": x, "y": y}
    m = ydf.GradientBoostedTreesLearner(
        label="y", num_trees=200, max_depth=3,
        early_stopping="LOSS_INCREASE",
        early_stopping_num_trees_look_ahead=10,
    ).train(data)
    trained = m.training_logs["num_trees_trained"]
    assert trained < 200  # the loop actually stopped, not just truncation
    assert m.num_trees() <= trained


def test_inloop_early_stop_matches_full_run():
    """A train that stops between chunks is bit-identical to one chunk of
    all the trees truncated at the same validation-loss argmin (chunk
    boundaries must be invisible: RNG keys derive from absolute iteration
    indices)."""
    rng = np.random.RandomState(5)
    n = 600
    x = rng.normal(size=n)
    y = (x + rng.normal(scale=1.5, size=n) > 0).astype(np.int64)
    data = {"x": x, "y": y}
    kw = dict(label="y", num_trees=60, max_depth=3, random_seed=11)
    stopped = ydf.GradientBoostedTreesLearner(
        early_stopping="LOSS_INCREASE",
        early_stopping_num_trees_look_ahead=8,
        **kw,
    ).train(data)
    # MIN_LOSS_FINAL trains everything, then truncates at the argmin.
    full = ydf.GradientBoostedTreesLearner(
        early_stopping="MIN_LOSS_FINAL", **kw,
    ).train(data)
    assert stopped.training_logs["num_trees_trained"] < 60
    assert full.training_logs["num_trees_trained"] == 60
    # The fixture is chosen so both truncate to the same argmin — the
    # bit-identity check must actually run.
    assert stopped.num_trees() == full.num_trees()
    np.testing.assert_array_equal(stopped.predict(data), full.predict(data))
