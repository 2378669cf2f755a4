"""Device-resident boosting loop (ops/device_loop.py): multi-tree
donated-carry dispatch must be INVISIBLE in the results — any chunk
length (forced here through the snapshot interval under a working_dir)
produces the same model arrays and per-iteration losses as one dispatch
of all the trees, early stopping fires at the same iteration,
snapshot/resume at a chunk boundary is bit-identical — while the
host-sync accounting counts what the loop actually dispatched, and the
chunk length follows from what can end the loop
(docs/device_loop.md)."""

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.learners.gbt import _TrainingAborted
from ydf_tpu.ops import device_loop


def _data(n=900, seed=3, nan_cat=False):
    rng = np.random.RandomState(seed)
    d = {"x1": rng.normal(size=n), "x2": rng.normal(size=n)}
    y = (
        d["x1"] + 0.5 * d["x2"] + rng.normal(scale=0.5, size=n) > 0
    ).astype(np.int64)
    if nan_cat:
        x3 = rng.normal(size=n)
        x3[rng.rand(n) < 0.15] = np.nan  # missing-value routing
        d["x3"] = x3
        d["c1"] = rng.choice(["a", "b", "c", "d"], size=n)
    d["y"] = y
    return d


def _train(data, chunk, tmp_path, **kw):
    """Trains in chunks of `chunk` trees (None: as the learner decides,
    which for _KW is one dispatch of all the trees)."""
    if chunk is not None:
        kw = dict(
            kw, working_dir=str(tmp_path / f"chunks_of_{chunk}"),
            resume_training_snapshot_interval_trees=chunk,
        )
    return ydf.GradientBoostedTreesLearner(label="y", **kw).train(data)


def _assert_identical(a, b, data):
    import jax

    for la, lb in zip(jax.tree.leaves(a.forest), jax.tree.leaves(b.forest)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert a.training_logs["train_loss"] == b.training_logs["train_loss"]
    assert a.training_logs["valid_loss"] == b.training_logs["valid_loss"]
    np.testing.assert_array_equal(a.predict(data), b.predict(data))


_KW = dict(num_trees=11, max_depth=3, random_seed=7,
           validation_ratio=0.0, early_stopping="NONE")


@pytest.mark.parametrize("quant", ["f32", "bf16x2", "int8"])
def test_chunked_equals_single_scan_per_quant(quant, monkeypatch, tmp_path):
    """One dispatch of all the trees vs a dispatch per tree vs a chunk
    length that does not divide num_trees (4 on 11 trees): model arrays
    AND per-iteration losses bit-identical in every
    gradient-quantization mode."""
    monkeypatch.setenv("YDF_TPU_HIST_QUANT", quant)
    data = _data()
    base = _train(data, None, tmp_path, **_KW)
    per_tree = _train(data, 1, tmp_path, **_KW)
    chunked = _train(data, 4, tmp_path, **_KW)
    _assert_identical(base, per_tree, data)
    _assert_identical(base, chunked, data)


def test_chunked_equals_single_scan_sampling(tmp_path):
    """Row subsampling + feature sampling draw from the carried PRNG
    key; per-iteration randomness folds the ABSOLUTE iteration index,
    so chunk boundaries must not move any draw."""
    data = _data(seed=5)
    kw = dict(_KW, subsample=0.7, num_candidate_attributes=1)
    base = _train(data, None, tmp_path, **kw)
    chunked = _train(data, 3, tmp_path, **kw)
    _assert_identical(base, chunked, data)


def test_chunked_equals_single_scan_nan_categorical(tmp_path):
    data = _data(seed=6, nan_cat=True)
    base = _train(data, None, tmp_path, **_KW)
    chunked = _train(data, 5, tmp_path, **_KW)
    _assert_identical(base, chunked, data)


def test_early_stop_same_iteration(tmp_path):
    """In-loop early stopping is decided from the per-iteration
    validation losses — identical across chunkings — so every chunk
    length keeps the SAME trees, whatever boundary the driver noticed
    the stall at."""
    rng = np.random.RandomState(3)
    n = 800
    x = rng.normal(size=n)
    y = (x + rng.normal(scale=2.0, size=n) > 0).astype(np.int64)
    data = {"x": x, "y": y}
    kw = dict(num_trees=80, max_depth=3, random_seed=7,
              early_stopping="LOSS_INCREASE",
              early_stopping_num_trees_look_ahead=10)
    a = _train(data, 1, tmp_path, **kw)
    b = _train(data, 7, tmp_path, **kw)
    assert a.training_logs["num_trees"] < 80  # it actually stopped
    assert a.training_logs["num_trees"] == b.training_logs["num_trees"]
    assert a.num_trees() == b.num_trees()
    kept = a.training_logs["num_trees"]
    assert (
        a.training_logs["train_loss"][:kept]
        == b.training_logs["train_loss"][:kept]
    )
    np.testing.assert_array_equal(a.predict(data), b.predict(data))


def test_snapshot_resume_at_chunk_boundary(tmp_path):
    """Preemption at a chunk boundary: kill after one 5-tree dispatch,
    resume, and the final model is bit-identical to the uninterrupted
    one-dispatch train (donated carries never leak into the snapshot —
    it serializes the NEW carry)."""
    data = _data()
    kw = dict(label="y", num_trees=12, max_depth=3, random_seed=7)
    base = ydf.GradientBoostedTreesLearner(**kw).train(data)

    learner = ydf.GradientBoostedTreesLearner(
        working_dir=str(tmp_path),
        resume_training_snapshot_interval_trees=5, **kw,
    )
    learner._abort_after_chunks = 1
    with pytest.raises(_TrainingAborted):
        learner.train(data)
    resumed = ydf.GradientBoostedTreesLearner(
        working_dir=str(tmp_path), resume_training=True,
        resume_training_snapshot_interval_trees=5, **kw,
    ).train(data)
    np.testing.assert_array_equal(base.predict(data), resumed.predict(data))


def test_chunk_fn_cached_across_chunk_lengths():
    """The donated-carry jit wrapper is built ONCE per run object;
    changing chunk_len mid-run (5,2,5-style tails) must reuse the same
    callable and compile one executable per distinct length — the
    retrace regression this round fixes."""
    import functools

    import jax
    import jax.numpy as jnp

    class _Run:
        pass

    @functools.partial(jax.jit, static_argnames=("chunk_len",))
    def run_chunk(carry, start, chunk_len, xs):
        def step(c, i):
            return c + xs * (start + i), c

        return jax.lax.scan(step, carry, jnp.arange(chunk_len))

    run = _Run()
    run.run_chunk = run_chunk
    fn = device_loop.chunk_fn(run)
    assert device_loop.chunk_fn(run) is fn  # cached per run
    carry = jnp.zeros(4)
    xs = jnp.ones(4)
    for clen in (3, 2, 3, 2, 3):
        carry, _ = device_loop.run_chunk(run, carry, 0, clen, xs)
    # Two distinct static chunk lengths -> exactly two executables;
    # start is a device scalar, so offsets never fork compilations.
    assert fn._cache_size() == 2


def test_stats_accounting(tmp_path):
    """12 trees at 5 trees/dispatch = dispatches at starts 0/5/10 (the
    tail overshoots by design — one executable serves every chunk);
    host-sync bytes count the per-chunk output fetches."""
    data = _data()
    device_loop.reset_stats()
    _train(data, 5, tmp_path, num_trees=12, max_depth=3,
           random_seed=7, validation_ratio=0.0, early_stopping="NONE")
    snap = device_loop.stats_snapshot()
    assert snap["dispatches"] == 3
    assert snap["device_loop"] == 5  # the chunk length dispatched
    assert snap["host_sync_bytes"] > 0
    assert snap["host_sync_bytes_per_tree"] > 0
    assert 0 < snap["dispatches_per_tree"] < 1
    device_loop.reset_stats()
    assert device_loop.stats_snapshot()["dispatches"] == 0


def test_one_dispatch_when_nothing_can_stop():
    """The benchmark cells' shape of call: 4 trees, 10 % validation
    rows, look-ahead 30. Nothing can end the loop before its last tree,
    so it is one dispatch of 4 trees, and the job's profile has every
    key the benchmark's metrics and its `[job]` line read."""
    device_loop.reset_stats()
    model = ydf.GradientBoostedTreesLearner(
        label="y", num_trees=4, max_depth=3, random_seed=7,
        validation_ratio=0.1, early_stopping="LOSS_INCREASE",
        early_stopping_num_trees_look_ahead=30,
    ).train(_data())
    snap = device_loop.stats_snapshot()
    assert snap["dispatches"] == 1
    assert snap["device_loop"] == 4
    assert snap["dispatches_per_tree"] == 0.25
    for key in ("device_loop.init", "device_loop.dispatch",
                "device_loop.wait", "device_loop.fetch",
                "device_loop.merge", "device_loop.program_build_s",
                "device_loop.route_select", "device_loop.route_gather"):
        assert key in model.training_profile, key


@pytest.mark.parametrize(
    "case, kw, dispatches, chunk",
    [
        # Under a working_dir: the snapshot interval.
        ("snapshot_interval",
         dict(num_trees=12, resume_training_snapshot_interval_trees=5,
              validation_ratio=0.0, early_stopping="NONE"), 3, 5),
        # Early stopping that can fire (num_trees > look-ahead): the
        # look-ahead window ...
        ("lookahead",
         dict(num_trees=20, validation_ratio=0.2,
              early_stopping="LOSS_INCREASE",
              early_stopping_num_trees_look_ahead=8), 3, 8),
        # ... at most 25 trees,
        ("lookahead_capped",
         dict(num_trees=60, validation_ratio=0.2,
              early_stopping="LOSS_INCREASE",
              early_stopping_num_trees_look_ahead=40), 3, 25),
        # and 25 trees under a deadline.
        ("deadline",
         dict(num_trees=30, validation_ratio=0.0, early_stopping="NONE",
              maximum_training_duration=3600.0), 2, 25),
        # Nothing can stop the loop: all the trees.
        ("all_trees",
         dict(num_trees=30, validation_ratio=0.0, early_stopping="NONE"),
         1, 30),
        # A look-ahead the loop cannot outlive stops nothing.
        ("lookahead_not_outlived",
         dict(num_trees=12, validation_ratio=0.2,
              early_stopping="LOSS_INCREASE",
              early_stopping_num_trees_look_ahead=12), 1, 12),
    ],
)
def test_chunk_length_rule(case, kw, dispatches, chunk, tmp_path):
    """The chunk length follows from what can end the loop
    (learners/gbt.py:_trees_per_chunk); no train here stops early, so
    the dispatch count is ceil(num_trees / chunk)."""
    if case == "snapshot_interval":
        kw = dict(kw, working_dir=str(tmp_path))
    # x2 decides the label: the validation loss falls with every tree.
    rng = np.random.RandomState(11)
    x1, x2 = rng.normal(size=600), rng.normal(size=600)
    data = {"x1": x1, "x2": x2, "y": (x2 > 0).astype(np.int64)}
    device_loop.reset_stats()
    model = ydf.GradientBoostedTreesLearner(
        label="y", max_depth=2, shrinkage=0.02, random_seed=7, **kw
    ).train(data)
    snap = device_loop.stats_snapshot()
    assert model.training_logs["num_trees_trained"] == kw["num_trees"]
    assert snap["device_loop"] == chunk
    assert snap["dispatches"] == dispatches
