"""Cox proportional-hazard survival loss + survival/ranking/regression
metric additions (reference loss_imp_cox.cc, metric.h:128 MSLE/RMSLE,
ranking_ap.cc MAP, Harrell's C for evaluation)."""

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.learners.survival_loss import CoxProportionalHazardLoss
from ydf_tpu.metrics.metrics import (
    concordance_index,
    mean_average_precision,
)


def _naive_cox(preds, departure, event, entry):
    """O(n²) oracle straight from the partial-likelihood formulas.

    Risk set of event i: examples j with entry_j < t_i <= departure_j —
    plus tie handling matching the reference's sequential sweep: among
    same-time events, an earlier-index event still sees the later ones in
    its risk set, but not vice versa."""
    n = len(preds)
    e = np.exp(preds)
    loss = 0.0
    grad = np.zeros(n)
    hess = np.zeros(n)
    # For each event i: risk set under the reference's update ordering.
    key_removal = [
        (departure[j], 1 if event[j] else 2, j) for j in range(n)
    ]
    for i in range(n):
        if not event[i]:
            continue
        # j is still present at i's event if j's removal update sorts at or
        # after i's (j's arrival must sort before, i.e. entry_j <= t_i with
        # arrivals-first tie order).
        at_risk = [
            j
            for j in range(n)
            if entry[j] <= departure[i] and key_removal[j] >= key_removal[i]
        ]
        hz = sum(e[j] for j in at_risk)
        loss += np.log(hz) - preds[i]
        for j in at_risk:
            grad[j] += e[j] / hz
            hess[j] += e[j] / hz - (e[j] / hz) ** 2
    grad -= event.astype(float)
    return loss / n, grad, hess


def _synthetic(n, seed, with_entry=False):
    rng = np.random.RandomState(seed)
    preds = rng.normal(scale=0.7, size=n)
    departure = rng.exponential(scale=2.0, size=n) + 0.1
    event = rng.uniform(size=n) < 0.7
    entry = (
        rng.uniform(0, 0.08, size=n) if with_entry else np.zeros(n)
    )
    return preds.astype(np.float32), departure, event, entry


@pytest.mark.parametrize("with_entry", [False, True])
def test_cox_matches_naive_oracle(with_entry):
    import jax.numpy as jnp

    n = 300
    preds, departure, event, entry = _synthetic(n, 0, with_entry)
    loss_obj = CoxProportionalHazardLoss()
    loss_obj.register_survival(
        "train", departure, event, entry if with_entry else None
    )
    got_loss = float(
        loss_obj.loss(None, jnp.asarray(preds)[:, None], None, tag="train")
    )
    g, h = loss_obj.grad_hess(None, jnp.asarray(preds)[:, None])
    want_loss, want_g, want_h = _naive_cox(
        preds.astype(np.float64), departure, event, entry
    )
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g)[:, 0], want_g, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h)[:, 0], want_h, atol=2e-4)


def test_cox_gbt_end_to_end():
    rng = np.random.RandomState(1)
    n = 2000
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    true_hazard = np.exp(1.2 * x1 - 0.8 * x2)
    t_event = rng.exponential(1.0 / true_hazard)
    t_censor = rng.exponential(scale=np.median(1.0 / true_hazard) * 2, size=n)
    departure = np.minimum(t_event, t_censor) + 1e-3
    event = t_event <= t_censor
    data = {
        "x1": x1,
        "x2": x2,
        "age": departure,
        "event": event.astype(np.int64),
    }
    m = ydf.GradientBoostedTreesLearner(
        label="age",
        task=Task.SURVIVAL_ANALYSIS,
        label_event_observed="event",
        num_trees=60,
        max_depth=4,
    ).train(data)
    ev = m.evaluate(data)
    # log-hazard predictions must rank risk: strong signal → C well over 0.5.
    assert ev.concordance > 0.7, ev.concordance
    # Higher x1 → higher predicted log-hazard.
    lo = m.predict({"x1": np.full(100, -2.0), "x2": np.zeros(100),
                    "age": np.ones(100), "event": np.ones(100, np.int64)})
    hi = m.predict({"x1": np.full(100, 2.0), "x2": np.zeros(100),
                    "age": np.ones(100), "event": np.ones(100, np.int64)})
    assert hi.mean() > lo.mean() + 0.5


def test_concordance_index_formula():
    times = np.array([1.0, 2.0, 3.0, 4.0])
    events = np.array([True, True, False, False])
    perfect = np.array([4.0, 3.0, 2.0, 1.0])  # higher risk → earlier event
    assert concordance_index(times, perfect, events) == 1.0
    assert concordance_index(times, -perfect, events) == 0.0
    assert concordance_index(times, np.zeros(4), events) == 0.5


def test_msle_rmsle():
    y = np.array([1.0, 3.0, 7.0])
    p = np.array([2.0, 3.0, -1.0])  # negative prediction clamps to 0
    from ydf_tpu.metrics import evaluate_predictions

    ev = evaluate_predictions(Task.REGRESSION, y, p)
    want = np.mean(
        (np.log1p(np.maximum(p, 0)) - np.log1p(y)) ** 2
    )
    np.testing.assert_allclose(ev.msle, want, rtol=1e-6)
    np.testing.assert_allclose(ev.rmsle, np.sqrt(want), rtol=1e-6)
    # Negative labels: MSLE omitted, not an error.
    ev2 = evaluate_predictions(Task.REGRESSION, np.array([-1.0, 2.0]), p[:2])
    assert "msle" not in ev2.metrics


def test_mean_average_precision():
    # One group: relevance [1, 0, 1, 0] ranked by score descending.
    labels = np.array([1.0, 0.0, 1.0, 0.0])
    scores = np.array([4.0, 3.0, 2.0, 1.0])
    groups = np.zeros(4, np.int64)
    # Relevant at ranks 1 and 3: AP = (1/1 + 2/3) / 2.
    want = (1.0 + 2.0 / 3.0) / 2.0
    np.testing.assert_allclose(
        mean_average_precision(labels, scores, groups, k=5), want
    )
    # Truncation at k=2 sees only rank-1 relevant: AP = 1.
    np.testing.assert_allclose(
        mean_average_precision(labels, scores, groups, k=2), 1.0
    )


def test_cep_tracks_label_means():
    rng = np.random.RandomState(4)
    n = 2000
    x = rng.normal(size=n)
    y = (x > 0).astype(np.int64)  # label exactly determined by sign(x)
    m = ydf.GradientBoostedTreesLearner(
        label="y", num_trees=20, max_depth=3
    ).train({"x": x, "y": y})
    from ydf_tpu.analysis import conditional_expectation

    cep = conditional_expectation(m, {"x": x, "y": y}, "x", num_bins=10,
                                  max_rows=2000)
    vals = np.asarray(cep["values"])
    ml = np.asarray(cep["mean_label"], np.float64)
    mp = np.asarray(cep["mean_prediction"], np.float64)
    ok = np.isfinite(ml)
    # mean_label is the indicator of classes[1] (the class whose
    # probability predict() returns); the encoding is frequency-ordered so
    # classes[1] may be "0" or "1".
    pos = int(m.classes[1])
    left, right = (0.0, 1.0) if pos == 1 else (1.0, 0.0)
    np.testing.assert_allclose(ml[ok][vals[ok] < -0.5], left, atol=0.1)
    np.testing.assert_allclose(ml[ok][vals[ok] > 0.5], right, atol=0.1)
    # The model's conditional mean prediction tracks the label means.
    assert np.max(np.abs(mp[ok] - ml[ok])) < 0.2


def test_ranking_group_truncation_warns():
    from ydf_tpu.learners.ranking_loss import build_rank_groups

    codes = np.array([0] * 10 + [1] * 3)
    with pytest.warns(UserWarning, match="max_group_size"):
        groups, _ = build_rank_groups(codes, max_group_size=4)
    assert [len(lane) for lane in groups.lanes] == [4]
    assert groups.sizes[0].tolist() == [4, 3]


def _naive_cox_weighted(preds, departure, event, entry, w):
    """O(n²) weighted partial-likelihood oracle; returns loss and the
    PRE-weight-division grad/hess that grad_hess() emits (the grower's
    stats multiply by w, restoring dL/dpred)."""
    n = len(preds)
    e = np.exp(preds)
    loss = 0.0
    dS1 = np.zeros(n)
    dS2 = np.zeros(n)
    key_removal = [
        (departure[j], 1 if event[j] else 2, j) for j in range(n)
    ]
    for i in range(n):
        if not event[i]:
            continue
        at_risk = [
            j
            for j in range(n)
            if entry[j] <= departure[i] and key_removal[j] >= key_removal[i]
        ]
        hz = sum(w[j] * e[j] for j in at_risk)
        loss += w[i] * (np.log(hz) - preds[i])
        for j in at_risk:
            dS1[j] += w[i] / hz
            dS2[j] += w[i] / hz**2
    g = e * dS1 - event.astype(float)
    h = e * dS1 - w * e**2 * dS2
    return loss / w.sum(), g, h


def test_cox_weighted_matches_oracle():
    """Weighted Cox (beyond the reference, whose weights are an in-code
    TODO): risk sets aggregate w·exp(pred), event terms carry w."""
    import jax.numpy as jnp

    n = 250
    preds, departure, event, entry = _synthetic(n, 3, with_entry=True)
    rng = np.random.RandomState(9)
    w = rng.choice([0.5, 1.0, 2.0, 3.0], size=n)
    loss_obj = CoxProportionalHazardLoss()
    loss_obj.register_survival(
        "train", departure, event, entry, weights=w
    )
    got_loss = float(
        loss_obj.loss(None, jnp.asarray(preds)[:, None], None, tag="train")
    )
    g, h = loss_obj.grad_hess(None, jnp.asarray(preds)[:, None])
    want_loss, want_g, want_h = _naive_cox_weighted(
        preds.astype(np.float64), departure, event, entry, w
    )
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g)[:, 0], want_g, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h)[:, 0], want_h, atol=3e-4)


def test_cox_gbt_weighted_trains():
    rng = np.random.RandomState(4)
    n = 1500
    x1 = rng.normal(size=n)
    hazard = np.exp(0.9 * x1)
    age = rng.exponential(1.0 / hazard) + 0.1
    censor = rng.exponential(2.0, size=n) + 0.1
    data = {
        "x1": x1, "x2": rng.normal(size=n),
        "age": np.minimum(age, censor).astype(np.float32),
        "obs": age <= censor,
        "w": rng.uniform(0.5, 2.0, size=n).astype(np.float32),
    }
    m = ydf.GradientBoostedTreesLearner(
        label="age", task=Task.SURVIVAL_ANALYSIS,
        label_event_observed="obs", weights="w", num_trees=8, max_depth=3,
        validation_ratio=0.0, early_stopping="NONE",
    ).train(data)
    preds = m.predict({"x1": x1, "x2": np.zeros(n)})
    assert np.corrcoef(preds, x1)[0, 1] > 0.5
