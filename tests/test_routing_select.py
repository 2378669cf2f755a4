"""ops/lookup.py: the compare-and-select look-ups equal the gathers they
replace, on both sides of each size limit, and a forest grown through
either form is the same forest, array for array."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ydf_tpu.ops import grower, lookup
from ydf_tpu.ops.lookup import lookup_small, pick_column
from ydf_tpu.ops.routing import route_tree_bins
from ydf_tpu.ops.split_rules import HessianGainRule


@pytest.mark.parametrize("dense", [True, False, None])
@pytest.mark.parametrize("F", [1, 28, 100, 129])
def test_pick_column_equals_gather(F, dense):
    """Every column, bin 255 and trailing pad columns (feature-parallel
    padding appends columns of zeros that no split names)."""
    rng = np.random.default_rng(F)
    n, pad = 1000, 3
    bins = rng.integers(0, 256, (n, F + pad), dtype=np.int64).astype(np.uint8)
    bins[:, F:] = 0
    bins[::7, F - 1] = 255
    f = rng.integers(0, F, n).astype(np.int32)
    f[:F] = np.arange(F)
    f[-1] = F + pad - 1  # a pad column is still a column
    want = np.take_along_axis(bins, f[:, None], axis=1)[:, 0]
    got = pick_column(jnp.asarray(bins), jnp.asarray(f), dense)
    assert got.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pick_column_of_signed_ranks():
    """The set path picks int32 ranks out of [n, Fs]."""
    rng = np.random.default_rng(0)
    cols = rng.integers(-5, 3000, (500, 5)).astype(np.int32)
    f = rng.integers(0, 5, 500).astype(np.int32)
    want = np.take_along_axis(cols, f[:, None], axis=1)[:, 0]
    for dense in (True, False):
        got = pick_column(jnp.asarray(cols), jnp.asarray(f), dense)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dense", [True, False, None])
@pytest.mark.parametrize("Ld", [1, 2, 32, 33])
def test_lookup_small_equals_padded_gather(Ld, dense):
    """Slots in [0, Ld) and the retired slot L read what the grower's
    `pad(table, fill)[slot]` read, whatever lies past `Ld` in the table."""
    rng = np.random.default_rng(Ld)
    L = 64
    slot = rng.integers(0, Ld, 2000).astype(np.int32)
    slot[:Ld] = np.arange(Ld)
    slot[::5] = L
    for table, fill in (
        (rng.integers(-3, 1 << 20, L + 1).astype(np.int32), 127),
        (rng.random(L + 1) < 0.5, False),
        (rng.standard_normal(L + 1).astype(np.float32), 0.0),
        (rng.integers(0, 1 << 32, L + 1, dtype=np.uint64).astype(np.uint32), 0),
    ):
        padded = np.concatenate(
            [table[:Ld], np.full(L + 1 - Ld, fill, table.dtype)]
        )
        got = lookup_small(
            jnp.asarray(table), jnp.asarray(slot), Ld, fill, dense
        )
        assert got.dtype == table.dtype
        np.testing.assert_array_equal(np.asarray(got), padded[slot])


def test_size_limits_choose_the_form(monkeypatch):
    """By size (dense=None): the dense form at the limit, the gather one
    past it, equal answers on both sides, and the counters say which."""
    monkeypatch.setattr(lookup, "DENSE_COLUMNS_MAX", 5)
    monkeypatch.setattr(lookup, "DENSE_TABLE_MAX", 6)
    rng = np.random.default_rng(1)
    n = 300
    for F, went_dense in ((5, True), (6, False)):
        bins = rng.integers(0, 256, (n, F), dtype=np.int64).astype(np.uint8)
        f = rng.integers(0, F, n).astype(np.int32)
        before = lookup.counts()
        got = pick_column(jnp.asarray(bins), jnp.asarray(f))
        assert lookup.counts(since=before) == (
            (1, 0) if went_dense else (0, 1)
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.take_along_axis(bins, f[:, None], 1)[:, 0]
        )
    for size, went_dense in ((6, True), (7, False)):
        table = rng.integers(0, 100, size).astype(np.int32)
        idx = rng.integers(0, size + 1, n).astype(np.int32)
        before = lookup.counts()
        got = lookup_small(jnp.asarray(table), jnp.asarray(idx), size, -1)
        assert lookup.counts(since=before) == (
            (1, 0) if went_dense else (0, 1)
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.append(table, -1)[idx]
        )


def test_resolve_dense_keeps_a_cpu_on_the_gather(monkeypatch):
    assert lookup.resolve_dense() is False  # the tests' backend is a CPU
    monkeypatch.setattr(lookup, "is_tpu_backend", lambda: True)
    assert lookup.resolve_dense() is None
    for forced in (True, False, None):
        assert lookup.resolve_dense(forced) is forced


def _mixed_table(n=4000, seed=0):
    """Three numerical columns, two categorical, one set feature of 40
    items; a target that each kind explains part of."""
    rng = np.random.default_rng(seed)
    num = rng.integers(0, 64, (n, 3))
    cat = rng.integers(0, 12, (n, 2))
    bins = np.concatenate([num, cat], axis=1).astype(np.uint8)
    items = rng.random((n, 1, 40)) < 0.15
    words = np.zeros((n, 1, 2), np.uint32)
    for v in range(40):
        words[:, :, v // 32] |= (
            items[:, :, v].astype(np.uint32) << np.uint32(v % 32)
        )
    g = (
        (num[:, 0] > 30) * 1.0 - np.isin(cat[:, 0], (2, 5, 7)) * 1.5
        + items[:, 0, 3] * 2.0 + 0.1 * rng.standard_normal(n)
    ).astype(np.float32)
    stats = np.stack([g, np.ones(n, np.float32), np.ones(n, np.float32)], 1)
    return jnp.asarray(bins), jnp.asarray(stats), jnp.asarray(words)


def test_forest_equal_through_either_form():
    """grow_tree on the XLA chain with a numerical, a categorical and a
    set feature: the dense form, the gather and the rule by size give
    the same tree and the same leaf for every row; so does the
    validation route over it."""
    bins, stats, set_bits = _mixed_table()
    grown = {}
    for dense in (True, False, None):
        trees = []
        for t in range(3):
            res = grower.grow_tree(
                bins, stats, jax.random.PRNGKey(t),
                rule=HessianGainRule(l2=1.0), max_depth=5, frontier=16,
                max_nodes=63, num_bins=64, num_numerical=3, min_examples=5,
                set_bits=set_bits, hist_impl="segment", route_impl="xla",
                dense_lookups=dense,
            )
            leaves = route_tree_bins(
                res.tree, bins, 5, x_set=set_bits, num_numerical=3,
                dense_lookups=dense,
            )
            trees.append((res, leaves))
        grown[dense] = trees
    kinds = np.concatenate(
        [np.asarray(res.tree.is_cat) + 2 * np.asarray(res.tree.is_set)
         + 4 * ~np.asarray(res.tree.is_leaf) for res, _ in grown[True]]
    )
    # The three trees split on all three kinds of feature.
    assert {4, 5, 6} <= set(kinds.tolist()), sorted(set(kinds.tolist()))
    for dense in (False, None):
        for (a, la), (b, lb) in zip(grown[True], grown[dense]):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # The validation route reaches the leaf the grower put the row in.
    for res, leaves in grown[True]:
        np.testing.assert_array_equal(
            np.asarray(leaves), np.asarray(res.leaf_id)
        )


def test_route_skips_the_mask_without_categoricals():
    """With every column numerical the nodes' masks are never fetched:
    the caller's static fact, not a look at the tree."""
    bins, stats, _ = _mixed_table(n=1500)
    res = grower.grow_tree(
        bins[:, :3], stats, jax.random.PRNGKey(0),
        rule=HessianGainRule(l2=1.0), max_depth=4, frontier=8, max_nodes=31,
        num_bins=64, num_numerical=3, hist_impl="segment", route_impl="xla",
        dense_lookups=True,
    )
    counted = {}
    for num_numerical in (3, None):
        before = lookup.counts()
        leaves = route_tree_bins(
            res.tree, bins[:, :3], 4, num_numerical=num_numerical,
            dense_lookups=True,
        )
        counted[num_numerical] = lookup.counts(since=before)[0]
        np.testing.assert_array_equal(
            np.asarray(leaves), np.asarray(res.leaf_id)
        )
    # feature, bin, threshold, left, right, is_leaf; + is_cat, mask word
    assert counted == {3: 4 * 6, None: 4 * 8}
