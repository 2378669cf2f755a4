"""LambdaMART on the bucketed query layout (PR 36) against its plain
reference, `benchmark/references/lambdamart.py`: per-document gradients
and hessians before the first tree (every score tied, so they ARE the
tie rule) and after two trees, leaf values and -NDCG@5 on both splits;
the bucketed layout against the plain [G, G] formula; a table shuffled
across queries against the table ordered by query; the second `train()`
on one `Dataset` (no program built, nothing sent, the same forest); the
cap on a query's length. Small and seeded: a few thousand documents,
queries of 1 to 300 documents, one longer than every bucket boundary
but the last, one of a single grade, one of one document."""

import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.learners import ranking_loss as rl
from ydf_tpu.ops import device_loop

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import manifest  # noqa: E402
from harness.compiles import CompileCounter  # noqa: E402
from harness.datagen import as_columns  # noqa: E402

lambdamart = manifest.named_module("references", "lambdamart",
                                   ("forest_arrays", "readings"))

FEATURES = 16
SIZES = [300, 1, 7, 64, 65, 128, 33, 2, 256, 129, 17, 90, 5, 250, 40] * 2
HP = dict(group_row=FEATURES, ndcg_truncation=5, num_bins=256,
          validation_ratio=0.1, random_seed=123456, shrinkage=0.1,
          min_examples=5, l2_regularization=0.0, max_depth=3)


def table(seed=0, sizes=SIZES):
    """(x [FEATURES + 1, n] with the query id last, y grades): queries
    consecutive, ids ascending; query 3 has a single grade."""
    rng = np.random.default_rng(seed)
    sizes = np.asarray(sizes)
    n = int(sizes.sum())
    ids = np.repeat(np.arange(len(sizes)), sizes)
    x = rng.standard_normal((FEATURES, n)).astype(np.float32)
    latent = x[0] + 0.7 * x[1] - 0.5 * x[2] + 0.6 * rng.standard_normal(n)
    y = np.clip(np.floor(latent + 0.8), 0, 4).astype(np.float32)
    y[ids == 3] = 2.0
    return np.vstack([x, ids[None].astype(np.float32)]), y


def learner(**kw):
    kw = {"label": "label", "task": Task.RANKING,
          "ranking_group": f"f{FEATURES}", "num_trees": 3, "max_depth": 3,
          "num_bins": 256,
          **kw}
    return ydf.GradientBoostedTreesLearner(**kw)


def program_lambdas(ids, y, s, loss=None, cap=None):
    """(g, h, -NDCG) of the program's loss for rows ordered by query."""
    loss = loss or rl.LambdaMartNdcg()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        groups, facts = rl.build_rank_groups(ids, max_group_size=cap)
    groups = jax.tree.map(jnp.asarray, groups)
    ctx = loss.group_context(jnp.asarray(y), groups)
    g, h = loss.grad_hess(jnp.asarray(y), jnp.asarray(s)[:, None], ctx)
    value = loss.loss(jnp.asarray(y), jnp.asarray(s)[:, None], None,
                      groups=ctx)
    return np.asarray(g[:, 0]), np.asarray(h[:, 0]), float(value), facts


def plain_lambdas(ids, y, s, truncation=5):
    """The [G, G] formula, one query at a time, in float64."""
    g, h = np.zeros(len(y)), np.zeros(len(y))
    ndcg = []
    for q in np.unique(ids):
        rows = np.flatnonzero(ids == q)
        sq, yq = s[rows].astype(np.float64), y[rows].astype(np.float64)
        gains = 2.0 ** yq - 1.0
        order = np.argsort(-sq, kind="stable")
        position = np.argsort(order)
        disc_at = np.where(np.arange(len(rows)) < truncation,
                           1.0 / np.log2(np.arange(len(rows)) + 2.0), 0.0)
        disc = disc_at[position]
        maxdcg = np.sum(np.sort(gains)[::-1] * disc_at)
        if maxdcg <= 0:
            continue
        better = yq[:, None] > yq[None, :]
        rho = 1.0 / (1.0 + np.exp(-(sq[None, :] - sq[:, None])))
        delta = (np.abs(gains[:, None] - gains[None, :])
                 * np.abs(disc[:, None] - disc[None, :]) / maxdcg)
        lam = np.where(better, rho * delta, 0.0)
        hl = np.where(better, rho * (1 - rho) * delta, 0.0)
        g[rows] = -lam.sum(1) + lam.sum(0)
        h[rows] = hl.sum(1) + hl.sum(0)
        ndcg.append(np.sum(gains[order] * disc_at) / maxdcg)
    return g, h, -float(np.mean(ndcg))


def scores(kind, n, seed=1):
    rng = np.random.default_rng(seed)
    return {"tied": np.zeros(n), "random": rng.standard_normal(n),
            "many_ties": np.round(rng.standard_normal(n))}[kind].astype(
                np.float32)


# ------------------------------------------- the layout against [G, G]


@pytest.mark.parametrize("kind", ["tied", "random", "many_ties"])
def test_bucketed_layout_is_the_plain_formula(kind):
    x, y = table()
    ids = x[-1].astype(np.int64)
    s = scores(kind, len(y))
    g, h, value, facts = program_lambdas(ids, y, s)
    g0, h0, value0 = plain_lambdas(ids, y, s)
    scale = np.abs(g0).max()
    assert np.abs(g - g0).max() < 2e-6 * scale
    assert np.abs(h - h0).max() < 2e-6 * scale
    assert value == pytest.approx(value0, rel=2e-6)
    assert facts["rank_buckets"] == 8  # 1, 2, 8, 32 .. 512: none to the longest
    assert facts["rank_pairs"] <= facts["rank_pair_slots"]
    assert facts["rank_pair_slots"] < 2 * 5 * len(y)
    assert g[ids == 3].any() == False and g[ids == 1].any() == False  # noqa: E712


def test_first_tree_gradients_are_the_tie_rule():
    """All scores 0: the top five are a query's first five documents, so
    only they and the documents of another grade than theirs move."""
    ids = np.zeros(8, np.int64)
    y = np.array([0, 0, 0, 0, 0, 3, 0, 0], np.float32)
    g, h, _, _ = program_lambdas(ids, y, np.zeros(8, np.float32))
    assert g[5] < 0 and np.all(g[:5] > 0)  # the relevant one, sixth, rises
    assert g[6] == 0 and g[7] == 0  # same grade as nothing in the top five
    # third of the query: in the top five, so a pair with every other one
    g2, _, _, _ = program_lambdas(ids, y[::-1].copy(), np.zeros(8, np.float32))
    assert g2[2] < 0 and np.all(np.delete(g2, 2) > 0)
    np.testing.assert_allclose(
        g2, plain_lambdas(ids, y[::-1], np.zeros(8, np.float32))[0], rtol=1e-6)


def test_views_and_rows_round_trip():
    x, y = table()
    ids = x[-1].astype(np.int64)
    groups, _ = rl.build_rank_groups(ids, num_rows=len(y) + 3)
    groups = jax.tree.map(jnp.asarray, groups)
    v = jnp.arange(len(y) + 3, dtype=jnp.float32) + 1.0
    views = rl.to_groups(groups, v, fill=-1.0)
    assert [a.shape[1] for a in views] == [1, 2, 8, 32, 64, 128, 256, 512]
    back, twice = (np.asarray(a) for a in rl.from_groups(
        groups, views, [2 * a for a in views]))
    np.testing.assert_array_equal(twice, 2 * back)
    np.testing.assert_array_equal(back[:len(y)], np.asarray(v)[:len(y)])
    np.testing.assert_array_equal(back[len(y):], 0.0)  # rows of no query
    for a, sizes in zip(views, groups.sizes):
        np.testing.assert_array_equal(np.sum(np.asarray(a) > 0, axis=1),
                                      np.asarray(sizes))


def test_xe_ndcg_on_the_bucketed_layout():
    x, y = table()
    ids = x[-1].astype(np.int64)
    s = scores("random", len(y))
    g, h, value, _ = program_lambdas(ids, y, s, loss=rl.XeNdcg())
    total, count = 0.0, 0
    for q in np.unique(ids):
        rows = np.flatnonzero(ids == q)
        gains = 2.0 ** y[rows].astype(np.float64) - 1.0
        if gains.sum() <= 0:
            assert not g[rows].any()
            continue
        e = np.exp(s[rows] - s[rows].max(), dtype=np.float64)
        p, t = e / e.sum(), gains / gains.sum()
        np.testing.assert_allclose(g[rows], p - t, atol=2e-6)
        np.testing.assert_allclose(h[rows], np.maximum(p * (1 - p), 1e-6),
                                   atol=2e-6)
        total += -np.sum(t * np.log(p + 1e-12))
        count += 1
    assert value == pytest.approx(total / count, rel=1e-5)


@pytest.mark.parametrize("loss", [rl.LambdaMartNdcg(), rl.XeNdcg()],
                         ids=["lambda_mart", "xe_ndcg"])
@pytest.mark.parametrize("kind", ["tied", "random", "many_ties"])
def test_one_view_gives_the_lambdas_and_the_loss(kind, loss):
    """`grad_hess_loss` is `grad_hess` and `loss` called apart, bit for
    bit, compiled and op by op, with queries of one document and of
    fewer documents than the truncation."""
    x, y = table()
    ids = x[-1].astype(np.int64)
    assert {1, 2} <= set(SIZES) and min(SIZES) < loss.ndcg_truncation
    groups, _ = rl.build_rank_groups(ids)
    groups = jax.tree.map(jnp.asarray, groups)
    y_d = jnp.asarray(y)
    s_d = jnp.asarray(scores(kind, len(y)))[:, None]
    ctx = loss.group_context(y_d, groups)
    for run in (jax.jit, lambda f: f):
        g, h, value = run(loss.grad_hess_loss)(y_d, s_d, ctx)
        g0, h0 = run(loss.grad_hess)(y_d, s_d, ctx)
        value0 = run(lambda y_, s_, c: loss.loss(y_, s_, None, groups=c))(
            y_d, s_d, ctx)
        for a, b in ((g, g0), (h, h0), (value, value0)):
            np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                          np.asarray(b).view(np.int32))
    assert np.asarray(g).any() and float(value) != 0.0


# ------------------------------------------- against the plain reference


@pytest.fixture(scope="module")
def trained():
    for seed in range(20):  # the first table whose three trees all stay
        x, y = table(seed)
        model = learner().train(as_columns(x, y))
        if model.num_trees() == 3:
            return x, y, model
    raise AssertionError("no table keeps three trees")


def reference_stats(ref):
    """(g, h) of every document from the reference's per-row stats."""
    st = np.concatenate([np.asarray(p).reshape(-1, 3) for p in ref.stats])
    return st[:ref.n, 0], st[:ref.n, 1]


@pytest.mark.parametrize("trees", [0, 2])
def test_gradients_and_hessians_against_the_reference(trained, trees):
    x, y, model = trained
    hp = dict(HP, validation_ratio=0.0)  # every document's lambdas are read
    with jax.default_matmul_precision("highest"):
        ref = lambdamart.RankReference(x, y, hp, block_rows=1 << 12)
        arrays = lambdamart.forest_arrays(model)
        for t in range(trees):
            ref.follow_tree({k: arrays[k][t] for k in lambdamart.FOREST_KEYS},
                            with_regret=False)
    s = np.concatenate([np.asarray(p).reshape(-1) for p in ref.pred])[:ref.n]
    assert (np.unique(s).size > 1) == (trees > 0)
    g0, h0 = reference_stats(ref)
    g, h, value, _ = program_lambdas(x[-1].astype(np.int64), y, s)
    scale = np.abs(g0).max()
    assert np.abs(g - g0).max() < 2e-6 * scale
    assert np.abs(h - h0).max() < 2e-6 * scale
    assert value == pytest.approx(ref.train_loss, rel=2e-6)


def test_leaves_and_ndcg_of_both_splits_against_the_reference(trained):
    x, y, model = trained
    assert model.num_trees() == 3
    got = lambdamart.readings(x, y, HP, [lambdamart.forest_arrays(model)],
                              block_rows=1 << 12)
    for name in ("jobs_differ", "bin_edges_differ", "thresholds_off_grid",
                 "leaf_rows_gap", "init_gap"):
        assert got[name] == 0, (name, got)
    assert got["split_regret"] < 5e-5, got
    assert got["leaf_gap"] < 1e-4 and got["leaf_gap_median"] < 2e-6, got
    assert 0 < got["train_loss_gap"] < 2.5e-6, got
    assert 0 < got["valid_loss_gap"] < 2.5e-6, got
    assert "trees_missing" not in got


def test_the_split_takes_whole_queries_by_the_stated_rule(trained):
    x, y, model = trained
    nq = len(SIZES)
    valid = lambdamart.validation_queries(nq, 0.1, 123456)
    assert valid.sum() == 3
    want = np.zeros(nq, bool)
    want[np.random.RandomState(123456).permutation(nq)[:3]] = True
    np.testing.assert_array_equal(valid, want)
    # the program trained on the other queries' documents: tree 1's root
    assert model.forest.to_numpy()["cover"][0, 0] == np.asarray(SIZES)[
        ~valid].sum()


# ------------------------------------------------- through train()


def forest_bits(model):
    out = {}
    for k, v in model.forest.to_numpy().items():
        v = np.asarray(v)
        out[k] = v.view(np.int32) if v.dtype == np.float32 else v
    return out


def assert_same_forest(a, b):
    a, b = forest_bits(a), forest_bits(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_a_table_shuffled_across_queries_gives_the_same_forest(trained):
    """Rows of different queries interleaved, each query's own documents
    in their order: the device arrays, and so the forest, are the ordered
    table's."""
    x, y, model = trained
    rng = np.random.default_rng(3)
    ids = x[-1].astype(np.int64)
    # a random merge of the queries that keeps each query's own order
    lanes = rng.random(len(y))
    within = np.concatenate(
        [np.sort(lanes[ids == q]) for q in range(len(SIZES))])
    order = np.argsort(within, kind="stable")
    assert not np.array_equal(x[-1][order], x[-1])
    for q in (0, 8):  # a query's documents keep their order
        np.testing.assert_array_equal(order[ids[order] == q],
                                      np.flatnonzero(ids == q))
    shuffled = learner().train(as_columns(x[:, order], y[order]))
    assert_same_forest(model, shuffled)


def test_tables_of_one_size_share_their_shapes():
    """Which queries validate moves how many rows train; the device
    arrays of two tables of one size have the same shapes all the same
    (zero-weight rows of no query at the parts' ends), and the forest
    counts the real rows."""
    shapes, trained_on = [], set()
    even = [44, 45, 46, 47, 48] * 12  # 60 queries, 2,760 documents
    for seed, sizes in ((0, even), (1, even[::-1])):
        x, y = table(seed, sizes)
        ds = ydf.Dataset.from_data(as_columns(x, y), label="label")
        model = learner(num_trees=1).train(ds)
        held = [d for d in (ds, *ds._retyped.values()) if d._device_inputs][0]
        shapes.append([a.shape for a in held._device_inputs[2][:6]])
        valid = lambdamart.validation_queries(len(sizes), 0.1, 123456)
        real = int(np.asarray(sizes)[~valid].sum())
        assert model.forest.to_numpy()["cover"][0, 0] == real
        assert real <= shapes[-1][0][0] <= len(y)
        trained_on.add(real)
    assert shapes[0] == shapes[1] and len(trained_on) == 2
    from ydf_tpu.learners.gbt import _split_capacities

    assert _split_capacities(14_000_000, 12_594_156, 1_405_844, 0.1) == (
        _split_capacities(14_000_000, 12_614_780, 1_385_220, 0.1)
    ) == (12_713_984, 1_572_864)
    assert _split_capacities(4000, 3990, 10, 0.1)[0] >= 3990  # always holds


def test_second_train_builds_no_program_and_sends_nothing():
    x, y = table(seed=2)
    ds = ydf.Dataset.from_data(as_columns(x, y), label="label")
    device_loop.reset_stats()
    first = learner().train(ds)
    sent_first = device_loop.stats_snapshot()["h2d_bytes"]
    counter = CompileCounter()
    device_loop.reset_stats()
    second = learner().train(ds)
    assert counter.builds == 0
    assert device_loop.stats_snapshot()["h2d_bytes"] == 0 < sent_first
    p1, p2 = first.training_profile, second.training_profile
    assert p1["device_loop.inputs_cached"] == 0
    assert p2["device_loop.inputs_cached"] == 1
    assert p2["device_loop.compile"] == 0.0
    assert p2["device_loop.program_build_s"] == p1[
        "device_loop.program_build_s"]  # the program the first job ran
    assert p2["rank_groups"] < 1e-3 and p2["split"] < 1e-3
    for key in ("rank_pair_slots", "rank_pairs", "rank_buckets"):
        assert p2["device_loop." + key] == p1["device_loop." + key] > 0
    assert p2["device_loop.rank_pairs"] < p2["device_loop.rank_pair_slots"]
    assert_same_forest(first, second)
    # the query structure is kept beside the six arrays and counted
    held = [d for d in (ds, *ds._retyped.values()) if d._device_inputs][0]
    assert len(held._device_inputs[2]) == 7
    assert held.device_inputs_bytes() == sent_first


def test_the_ranking_program_names_its_device_scope(monkeypatch):
    """The compiled boosting chunk of a ranking job carries `ydf.rank`
    in its operations' metadata, inside `ydf.grad` (the lambdas and the
    NDCG of the forest before the tree) and inside `ydf.loss` (the
    validation NDCG, and the chunk's last forest's), and takes the query
    structure as arguments."""
    texts = []
    run_chunk = device_loop.run_chunk

    def spy(run, carry, start, chunk_len, *args, timer=None, **kwargs):
        assert set(kwargs) == {"groups_tr", "groups_va"}
        texts.append(device_loop.chunk_fn(run).lower(
            carry, jnp.asarray(start), chunk_len, *args, **kwargs
        ).as_text(debug_info=True))
        return run_chunk(run, carry, start, chunk_len, *args, timer=timer,
                         **kwargs)

    monkeypatch.setattr(device_loop, "run_chunk", spy)
    x, y = table(seed=4)
    learner(num_trees=1).train(as_columns(x, y))
    (text,) = texts
    assert "ydf.grad/ydf.rank/" in text and "ydf.loss/ydf.rank/" in text


@pytest.mark.parametrize("kind", ["one_chunk", "chunks", "dart"])
def test_each_tree_logs_the_loss_of_the_forest_it_ends(kind, tmp_path):
    """The training loss logged for tree t is -NDCG@5 of the scores of
    trees 1 to t, though outside DART the program reads it off the
    lambdas' view of tree t + 1 (and a chunk's last tree's off one more
    view): in one chunk of four trees, across chunks of two that
    overshoot five trees, and under DART, whose losses are read apart
    (its final forest rescales the trees, so only the last loss is the
    final forest's)."""
    x, y = table(seed=5)
    ids = x[-1].astype(np.int64)
    kw = dict(validation_ratio=0.0, num_trees=4)
    if kind == "chunks":
        kw.update(num_trees=5, working_dir=str(tmp_path),
                  resume_training_snapshot_interval_trees=2)
    if kind == "dart":
        kw.update(dart_dropout=0.5)
    model = learner(**kw).train(as_columns(x, y))
    logged = model.training_logs["train_loss"]
    assert len(logged) == model.num_trees() == kw["num_trees"]
    views = {"one_chunk": 5 / 4, "chunks": 9 / 5, "dart": 2.0}[kind]
    assert model.training_profile["device_loop.rank_score_views"] == views
    groups, _ = rl.build_rank_groups(ids)
    ctx = rl.LambdaMartNdcg().group_context(
        jnp.asarray(y), jax.tree.map(jnp.asarray, groups))
    forest = model.forest
    recomputed = []
    for t in range(1, model.num_trees() + 1):
        model.forest = forest.truncated(t)
        s = model.predict(as_columns(x, y)).astype(np.float32)
        recomputed.append(float(rl.LambdaMartNdcg().loss(
            jnp.asarray(y), jnp.asarray(s)[:, None], None, groups=ctx)))
    model.forest = forest
    if kind == "dart":
        logged, recomputed = logged[-1:], recomputed[-1:]
    assert logged == pytest.approx(recomputed, rel=1e-6, abs=0)
    assert len(set(recomputed)) == len(recomputed)  # a shift would show


def test_a_pointwise_loss_counts_no_score_view():
    x, y = table(seed=5)
    model = ydf.GradientBoostedTreesLearner(
        label="label", task=Task.REGRESSION, num_trees=2, max_depth=3,
    ).train(as_columns(x[:4], y))
    assert "device_loop.rank_score_views" not in model.training_profile


def test_another_cap_or_truncation_misses_the_kept_inputs():
    x, y = table(seed=2)
    ds = ydf.Dataset.from_data(as_columns(x, y), label="label")
    learner().train(ds)
    other = learner(ndcg_truncation=3).train(ds)
    assert other.training_profile["device_loop.inputs_cached"] == 0
    again = learner(ndcg_truncation=3).train(ds)
    assert again.training_profile["device_loop.inputs_cached"] == 1
    assert_same_forest(other, again)


@pytest.mark.parametrize("cap", [None, 128])
def test_the_cap_on_a_querys_length(cap):
    """None drops nothing, silently; an int cap still warns, and the
    documents past it get no gradient and no place in the NDCG."""
    x, y = table()
    ids = x[-1].astype(np.int64)
    s = scores("random", len(y))
    if cap is None:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            groups, facts = rl.build_rank_groups(ids, max_group_size=None)
            learner(num_trees=1, ranking_max_group_size=None).train(
                as_columns(x, y))
        assert sum(int(a.sum()) for a in groups.sizes) == len(y)
        assert len(groups.lanes[-1]) == 512  # the query of 300, whole
        assert ydf.GradientBoostedTreesLearner(
            label="label").ranking_max_group_size is None
        return
    with pytest.warns(UserWarning, match="max_group_size=128"):
        rl.build_rank_groups(ids, max_group_size=cap)
    with pytest.warns(UserWarning, match="max_group_size=128"):
        learner(num_trees=1, ranking_max_group_size=cap).train(
            as_columns(x, y))
    g, h, _, _ = program_lambdas(ids, y, s, cap=cap)
    past = np.concatenate([np.flatnonzero(ids == q)[cap:]
                           for q in np.unique(ids)])
    assert len(past) == (300 - 128) * 2 + (256 - 128) * 2 + 2 * (250 - 128) + 2
    assert not g[past].any() and not h[past].any()
    kept = np.setdiff1d(np.arange(len(y)), past)
    g0, h0, _ = plain_lambdas(ids[kept], y[kept], s[kept])
    assert np.abs(g[kept] - g0).max() < 2e-6 * np.abs(g0).max()
