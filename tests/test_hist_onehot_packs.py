"""The `matmul` histogram's one-hot by each feature's bin count
(ops/histogram.py onehot_plan; PERF.md section 6): features of at
most 128 bins share one-hot tiles of 128 rows, first-fit by decreasing
count, and a feature of more keeps its own `num_bins` rows. The sums are
the ones the plain one-hot gives, bit for bit, for every operand the
quant modes hand it; and the plan rests on the binner's invariant that
every bin of feature f is below `feature_num_bins[f]`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.dataset.binning import Binner
from ydf_tpu.dataset.dataset import Dataset
from ydf_tpu.learners import gbt
from ydf_tpu.ops.histogram import (
    StatColumn,
    _histogram_matmul,
    _histogram_segment,
    onehot_plan,
    onehot_rows,
)

_AIRLINE = (256, 256, 13, 32, 8, 23, 256, 256)


def test_plan_packs_first_fit_by_decreasing_count():
    """The airline table's four calendar and carrier columns share one
    tile, the largest first; 70 columns of 2 bins fill one tile and
    start a second; a column of 128 bins is a tile of its own beside
    one of 129, which keeps 256 rows; columns past the description and
    a call with none keep theirs."""
    plan = onehot_plan(_AIRLINE, 9, 256)  # the 9th column: a mesh's pad
    assert plan.wide == (0, 1, 6, 7, 8)
    assert plan.packs == (((3, 0, 32), (5, 32, 23), (2, 55, 13), (4, 68, 8)),)
    plan = onehot_plan((2,) * 70, 70, 256)
    assert [len(pack) for pack in plan.packs] == [64, 6] and not plan.wide
    assert plan.packs[1][-1] == (69, 10, 2)
    assert onehot_plan((128, 129), 2, 256) == ((1,), (((0, 0, 128),),))
    assert onehot_plan(None, 3, 64) == ((0, 1, 2), ())
    assert onehot_plan((256,) * 4, 4, 256) == ((0, 1, 2, 3), ())
    # A count of 0 (an empty dictionary) still holds bin 0.
    assert onehot_plan((0, 300), 2, 256) == ((1,), (((0, 0, 1),),))


@pytest.mark.parametrize("counts,features,want", [
    # The four cells: rows a chunk 256 a wide feature and 128 a pack.
    (_AIRLINE, 8, (1152, 4)),
    ((256,) * 100, 100, (25_600, 0)),
    ((256,) * 28, 28, (7_168, 0)),
    ((256,) * 136, 136, (34_816, 0)),
    (None, 8, (2_048, 0)),
    ((2,) * 70, 70, (256, 70)),
])
def test_onehot_rows_of_the_cells(counts, features, want):
    assert onehot_rows(counts, features, 256) == want


_COUNTS = {
    # name: (counts of the described columns, columns, num_bins)
    "all_256": ((256,) * 5, 5, 256),
    # and a pad column past the description, 256 bins of its own
    "airline": (_AIRLINE, 9, 256),
    "seventy_of_2": ((2,) * 70, 70, 256),
    "128_beside_129": ((128, 129), 2, 256),
    "num_bins_64": ((64, 40, 64, 7, 64), 5, 64),
}
_ANY, _UNIT = StatColumn(), StatColumn(pieces=1)
_MODES = {
    # name: (stats dtype, description of f32 stats)
    "f32_9": (np.float32, None),
    "f32_7": (np.float32, (_ANY, _ANY, _UNIT)),
    "f32_4": (np.float32, (_ANY, StatColumn(same_as=2), _UNIT)),
    "bf16x2": (jnp.bfloat16, None),
    "int8": (np.int8, None),
}
_SLOTS = (1, 2, 16)


def _stats(rng, n, mode):
    """Whole-number stats whose sums are exact in any order: the f32
    ones on a grid of 2^-7 with up to 17 significant bits (all three
    bf16 pieces), a weight of zeros and ones where the description says
    so and a hessian that is the weight under `f32_4`."""
    dtype, columns = _MODES[mode]
    if dtype == np.int8:
        return jnp.asarray(rng.integers(-127, 128, (n, 3)), jnp.int8)
    if dtype == jnp.bfloat16:  # high halves of 8 bits beside residuals
        hi = rng.integers(-127, 128, (n, 3)) * 256
        lo = rng.integers(-127, 128, (n, 3))
        return jnp.asarray(np.concatenate([hi, lo], axis=1), jnp.bfloat16)
    fine = lambda: (rng.integers(-(2 ** 17) + 1, 2 ** 17, n) | 1) / 128.0
    w = (rng.random(n) < 0.8).astype(np.float64) if columns else fine()
    h = w if mode == "f32_4" else fine() * (w != 0)
    return jnp.asarray(np.stack([fine() * (w != 0), h, w], 1), jnp.float32)


@pytest.mark.parametrize("mode", sorted(_MODES))
@pytest.mark.parametrize("case", sorted(_COUNTS))
def test_packed_sums_are_the_plain_ones(case, mode):
    """`_histogram_matmul` handed the bin counts returns what it returns
    with none, byte for byte, and what the `segment` impl returns, over
    two whole chunks and a ragged tail with rows on the trash slot, at
    one, two and sixteen slots (one of them a case, in turn)."""
    counts, F, B = _COUNTS[case]
    L = _SLOTS[(sorted(_COUNTS).index(case) + sorted(_MODES).index(mode)) % 3]
    rng = np.random.default_rng(len(case) * 31 + len(mode) * 7 + L)
    chunk = 256
    n = 2 * chunk + 77
    bins = np.stack(
        [rng.integers(0, c, n) for c in counts]
        + [rng.integers(0, B, n) for _ in range(F - len(counts))], axis=1,
    )
    bins = jnp.asarray(bins, jnp.uint8)
    slot = jnp.asarray(rng.integers(0, L + 1, (n,)), jnp.int32)  # L: trash
    stats = _stats(rng, n, mode)
    columns = _MODES[mode][1]
    matmul = jax.jit(_histogram_matmul, static_argnums=range(3, 8))
    plain = np.asarray(matmul(bins, slot, stats, L, B, chunk, columns, None))
    got = np.asarray(matmul(bins, slot, stats, L, B, chunk, columns, counts))
    assert got.shape == plain.shape == (L, F, B, stats.shape[1])
    assert np.any(plain != 0)
    assert got.tobytes() == plain.tobytes()
    want = np.asarray(
        jax.jit(_histogram_segment, static_argnums=(3, 4))(
            bins, slot, stats, L, B)
    )
    np.testing.assert_array_equal(got, want)


def _flights(n, seed):
    """Two numerical columns beside four categorical ones of 3, 9, 17
    and 40 items (one coded as floats), and a binary label that reads
    all of them."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 2)).astype(np.float32)
    items = {"c3": 3, "c9": 9, "c17": 17, "c40": 40}
    codes = {k: rng.integers(0, v, n) for k, v in items.items()}
    effect = sum(
        np.sin(np.arange(v) * (i + 1.3))[codes[k]]
        for i, (k, v) in enumerate(items.items())
    )
    score = x[:, 0] - 0.5 * x[:, 1] + effect + 0.5 * rng.normal(size=n)
    data = {"x0": x[:, 0], "x1": x[:, 1]}
    for k, v in codes.items():
        data[k] = np.array([f"{k}_{c}" for c in v])
    data["c17"] = codes["c17"].astype(np.float64)  # codes as floats
    data["label"] = (score > np.median(score)).astype(np.int64)
    return data


def test_forest_is_the_same_with_and_without_the_bin_counts(monkeypatch):
    """4,000 rows through the `matmul` histogram, as the learner comes
    (its four categorical columns of 4 to 41 bins in one tile) and with
    the one function that hands the bin counts returning nothing: the
    same forest, array for array, bit for bit."""
    monkeypatch.setenv("YDF_TPU_HIST_IMPL", "matmul")
    data = _flights(4_000, 3)

    def forest():
        jax.clear_caches()
        gbt._make_boost_fn.cache_clear()
        model = ydf.GradientBoostedTreesLearner(
            label="label", task=Task.CLASSIFICATION, num_trees=2,
            max_depth=4, num_bins=256, column_types={
                k: "CATEGORICAL" for k in ("c3", "c9", "c17", "c40")},
        ).train(data)
        assert model.training_logs["implementations"]["hist_impl"] == "matmul"
        return model.training_profile, {
            k: np.asarray(v) for k, v in model.forest.to_numpy().items()
        }

    try:
        profile, packed = forest()
        assert profile["device_loop.hist_packed_features"] == 4
        assert profile["device_loop.hist_onehot_rows"] == 2 * 256 + 128
        monkeypatch.setattr(gbt, "_hist_feature_num_bins", lambda b: None)
        profile, plain = forest()
        assert profile["device_loop.hist_packed_features"] == 0
        assert profile["device_loop.hist_onehot_rows"] == 6 * 256
    finally:
        jax.clear_caches()
        gbt._make_boost_fn.cache_clear()
    assert set(packed) == set(plain)
    for name, want in plain.items():
        got = packed[name]
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert got.tobytes() == want.tobytes(), name


def _assert_bins_below_counts(binner, bins):
    assert bins.shape[1] == binner.num_scalar
    for f in range(binner.num_scalar):
        assert int(bins[:, f].max()) < int(binner.feature_num_bins[f]), (
            binner.feature_names[f], int(bins[:, f].max()),
            int(binner.feature_num_bins[f]))


def test_every_bin_is_below_its_features_count():
    """The invariant the plan rests on (a bin at or past its count would
    be left out of a packed feature's sums): numerical columns with NaN, few
    values or none at all; categorical columns with missing values,
    items pruned by `min_vocab_frequency`, more than 255 items, codes
    as floats and as strings; and rows binned later that carry items
    the dictionary never saw."""
    rng = np.random.default_rng(42)
    n = 3_000

    def table(rows, shift):
        many = rng.integers(0, 400, rows)  # about 350 kept: past 255
        rare = rng.integers(0, 1000, rows)  # most under 5 rows: pruned
        strings = np.array(
            [f"s{v + shift}" for v in rng.integers(0, 30, rows)], dtype=object
        )
        strings[rng.random(rows) < 0.05] = None
        floats = rng.integers(0, 12, rows).astype(np.float64) + shift
        floats[rng.random(rows) < 0.05] = np.nan
        num = rng.normal(size=rows)
        num[rng.random(rows) < 0.1] = np.nan
        return {
            "num": num,
            "few": rng.integers(0, 3, rows).astype(np.float64),
            "empty": np.full(rows, np.nan),
            "many": np.array([f"m{v + shift}" for v in many]),
            "rare": np.array([f"r{v}" for v in rare]),
            "strings": strings,
            "floats": floats,
        }

    names = ["num", "few", "empty", "many", "rare", "strings", "floats"]
    ds = Dataset.from_data(table(n, 0), column_types={
        k: ydf.ColumnType.CATEGORICAL for k in names[3:]})
    binner = Binner.fit(ds, names, num_bins=256)
    assert binner.num_numerical == 3 and binner.num_categorical == 4
    assert int(binner.feature_num_bins[
        binner.feature_names.index("many")]) == 256
    _assert_bins_below_counts(binner, binner.transform(ds))
    later = Dataset.from_data(table(n, 7), dataspec=ds.dataspec)
    _assert_bins_below_counts(binner, binner.transform(later))
    for num_bins in (32, 64):
        small = Binner.fit(ds, names, num_bins=num_bins)
        _assert_bins_below_counts(small, small.transform(later))
