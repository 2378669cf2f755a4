"""Drives the rest of a run past the harness's look for a chip, with the
timed path broken underneath, and sees `correct` come out false: once
for each fault a one-chip training cell can have. A sound run of the
same size comes out true, so the faults and not the size fail it."""

import numpy as np
import pytest

import ydf_tpu
from tests.small import run_small


class Planted:
    """A trained model whose forest arrays a fault has gone over."""

    def __init__(self, model, alter):
        self._model, self._alter = model, alter
        self.forest = self

    def __getattr__(self, name):
        return getattr(self._model, name)

    def to_numpy(self):
        arrays = {k: np.array(v) for k, v in
                  self._model.forest.to_numpy().items()}
        self._alter(arrays, self._model)
        return arrays


def state_unchanged(arrays, model):
    """A boosting step that hands back its predictions unmoved grows the
    first tree again and logs the first loss again."""
    for k, v in arrays.items():
        v[1:] = v[0]
    for key in ("train_loss", "valid_loss"):
        model.training_logs[key] = [model.training_logs[key][0]] * len(
            model.training_logs[key])


def leaf_altered(arrays, _model):
    leaf = np.flatnonzero(arrays["is_leaf"][1][:arrays["num_nodes"][1]])[3]
    arrays["leaf_value"][1, leaf] *= 1.01


def split_altered(arrays, _model):
    arrays["threshold"][0, 0] += 0.25  # the root's cut, off the bin grid


def faulty_learner(alter=None, half_batch=False):
    real = ydf_tpu.GradientBoostedTreesLearner

    class Learner(real):
        def train(self, ds, valid=None):
            if half_batch:  # half of the rows left out, the rest trained on
                half = {k: v[: len(v) // 2] for k, v in ds.data.items()}
                ds = ydf_tpu.Dataset.from_data(half, label="label")
            model = super().train(ds, valid=valid)
            return Planted(model, alter) if alter else model

    return Learner


def test_sound_run_is_correct():
    result = run_small()
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "train_rows_trees_per_s"}
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault", [
    dict(alter=state_unchanged), dict(half_batch=True),
    dict(alter=leaf_altered), dict(alter=split_altered),
], ids=["state_unchanged", "half_batch", "leaf_altered", "split_altered"])
def test_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(ydf_tpu, "GradientBoostedTreesLearner",
                        faulty_learner(**fault))
    result = run_small()
    assert not result["correct"], result["compared"]


def test_binary_table_with_frontier_cap_is_correct_at_depth_8():
    """The reference's other loss and its reading of the frontier cap
    (YDF's better_default template), which no cell uses yet."""
    result = run_small(rows=120_000, depth=8, frontier=32,
                       table="binary_logit")
    assert result["correct"], result["compared"]
