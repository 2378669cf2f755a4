"""What the GBT learner tells the `matmul` histogram of its stats rows
`[g w, h w, w]` (learners/gbt.py:_hist_stat_columns, ops/histogram.py
StatColumn; PERF.md section 6, PR 37): a weight that is 0 or 1 rides as
one bf16 piece and a hessian that is the weight is not contracted, so a
slot of the narrow operand is 7 or 4 columns where it is 9. The learner
says so only where it can see that it holds, and the forest is the same
bit for bit either way."""

import jax
import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.learners import gbt
from ydf_tpu.ops import histogram as histogram_ops


def _table(n, seed, label):
    """Four numerical features, a weights column that is not 0/1 and a
    query id (20 documents a query), with the label `label` asks for."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    score = x[:, 0] - 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.normal(size=n)
    data = {f"f{i}": x[:, i] for i in range(4)}
    data["w"] = rng.uniform(0.5, 2.0, n).astype(np.float32)
    data["query"] = (np.arange(n) // 20).astype(np.int64)
    data["label"] = {
        "real": score.astype(np.float32),
        "binary": (score > 0).astype(np.int64),
        "classes": np.digitize(score, [-0.7, 0.7]).astype(np.int64),
        "grades": np.digitize(score, [-1.0, 0.0, 1.0, 2.0]).astype(np.int64),
    }[label]
    return data


@pytest.fixture
def fresh_programs(monkeypatch):
    """The `matmul` histogram on this CPU, and every program traced
    anew: JAX keeps the trace of `_grow_tree_jit` and `_histogram_jit`,
    the learner its boosting function, none keyed on the environment."""
    monkeypatch.setenv("YDF_TPU_HIST_IMPL", "matmul")
    jax.clear_caches()
    gbt._make_boost_fn.cache_clear()
    yield monkeypatch
    jax.clear_caches()
    gbt._make_boost_fn.cache_clear()


_RANKING = dict(task=Task.RANKING, ranking_group="query")
_CASES = {
    # name: (label, learner arguments, environment, columns a slot)
    "squared_error": ("real", dict(task=Task.REGRESSION), {}, 4),
    "squared_error_subsample": (
        "real", dict(task=Task.REGRESSION, subsample=0.5), {}, 4),
    "binomial": ("binary", dict(task=Task.CLASSIFICATION), {}, 7),
    "multinomial": ("classes", dict(task=Task.CLASSIFICATION), {}, 7),
    "ranking": ("grades", _RANKING, {}, 7),
    "ranking_selgb": (
        "grades", dict(sampling_method="SELGB", **_RANKING), {}, 7),
    "weights_column": (
        "real", dict(task=Task.REGRESSION, weights="w"), {}, 9),
    "weights_column_binomial": (
        "binary", dict(task=Task.CLASSIFICATION, weights="w"), {}, 9),
    "goss": ("real", dict(task=Task.REGRESSION, sampling_method="GOSS"),
             {}, 9),
    "goss_binomial": (
        "binary", dict(task=Task.CLASSIFICATION, sampling_method="GOSS"),
        {}, 9),
    # The lower-precision modes hand the dots their own operand.
    "bf16x2": ("real", dict(task=Task.REGRESSION),
               {"YDF_TPU_HIST_QUANT": "bf16x2"}, 6),
    "int8": ("binary", dict(task=Task.CLASSIFICATION),
             {"YDF_TPU_HIST_QUANT": "int8"}, 3),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_learner_describes_its_stats_only_where_it_can_see(
    case, fresh_programs
):
    """`device_loop.hist_columns_per_slot` and what `_histogram_matmul`
    is really handed while the job's program is traced: 4 (squared
    error) or 7 (every other loss) with no weights column under `RANDOM`
    at any `subsample` and `SELGB`; the plain 9 with a weights column
    and under `GOSS`, whose kept rows are re-weighted; the modes of
    `YDF_TPU_HIST_QUANT` get their own operand and no f32 description
    can touch it."""
    label, kwargs, env, want = _CASES[case]
    for key, value in env.items():
        fresh_programs.setenv(key, value)
    seen = []
    plain = histogram_ops._histogram_matmul

    def spy(bins, slot, stats, num_slots, num_bins, chunk, stat_columns):
        if stats.dtype == np.float32:
            seen.append(histogram_ops.narrow_columns_per_slot(stat_columns))
        else:
            assert stats.dtype in (np.int8, jax.numpy.bfloat16)
            seen.append(stats.shape[1])
        return plain(bins, slot, stats, num_slots, num_bins, chunk,
                     stat_columns)

    fresh_programs.setattr(histogram_ops, "_histogram_matmul", spy)
    model = ydf.GradientBoostedTreesLearner(
        label="label", num_trees=2, max_depth=3, validation_ratio=0.0,
        features=[f"f{i}" for i in range(4)], **kwargs
    ).train(_table(1200, 7, label))
    assert model.training_logs["implementations"]["hist_impl"] == "matmul"
    assert model.training_profile["device_loop.hist_columns_per_slot"] == want
    assert seen and set(seen) == {want}, seen


def test_description_comes_from_weights_sampling_and_loss_alone():
    from ydf_tpu.learners.losses import (
        BinomialLogLikelihood,
        MeanSquaredError,
        MultinomialLogLikelihood,
        PoissonLoss,
    )
    from ydf_tpu.ops.histogram import StatColumn, narrow_columns_per_slot

    describe = gbt._hist_stat_columns
    any_f32, unit = StatColumn(), StatColumn(pieces=1)
    assert describe(None, "RANDOM", BinomialLogLikelihood()) == (
        any_f32, any_f32, unit)
    assert describe(None, "SELGB", MultinomialLogLikelihood(3)) == (
        any_f32, any_f32, unit)
    assert describe(None, "RANDOM", MeanSquaredError()) == (
        any_f32, StatColumn(same_as=2), unit)
    # A loss that declares nothing keeps its hessian column.
    assert not hasattr(PoissonLoss, "unit_hessian")
    assert describe(None, "RANDOM", PoissonLoss())[1] == any_f32
    for loss in (MeanSquaredError(), BinomialLogLikelihood()):
        assert describe("w", "RANDOM", loss) is None
        assert describe(None, "GOSS", loss) is None
        assert describe(None, "a method of later", loss) is None
    assert [
        narrow_columns_per_slot(d, q) for d, q in (
            (None, "f32"), (describe(None, "RANDOM", PoissonLoss()), "f32"),
            (describe(None, "RANDOM", MeanSquaredError()), "f32"),
            (None, "bf16x2"), (None, "int8"),
        )
    ] == [9, 7, 4, 6, 3]


@pytest.mark.parametrize("task,label", [
    (Task.REGRESSION, "real"), (Task.CLASSIFICATION, "binary"),
])
def test_forest_is_the_same_with_and_without_the_description(
    task, label, fresh_programs
):
    """20,000 rows through the `matmul` histogram, as the learner comes
    (4 or 7 columns a slot) and with the one function that describes
    the stats returning nothing (9): the same forest, array for array,
    bit for bit. The dropped columns summed exact zeros."""
    data = _table(20_000, 11, label)

    def forest():
        model = ydf.GradientBoostedTreesLearner(
            label="label", task=task, num_trees=4, max_depth=6,
            features=[f"f{i}" for i in range(4)],
        ).train(data)
        assert model.training_logs["implementations"]["hist_impl"] == "matmul"
        return model.training_profile, {
            k: np.asarray(v) for k, v in model.forest.to_numpy().items()
        }

    profile, described = forest()
    assert profile["device_loop.hist_columns_per_slot"] in (4, 7)
    fresh_programs.setattr(gbt, "_hist_stat_columns", lambda *a: None)
    profile, plain = forest()
    assert profile["device_loop.hist_columns_per_slot"] == 9
    assert set(described) == set(plain)
    for name, want in plain.items():
        got = described[name]
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert got.tobytes() == want.tobytes(), name
