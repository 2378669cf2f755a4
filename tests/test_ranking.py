import os

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.learners.ranking_loss import build_rank_groups

D = "/root/reference/yggdrasil_decision_forests/test_data/dataset"


def test_build_rank_groups():
    # rows ordered by query: "a" -> rows 0, 1 ; "b" -> 2, 3, 4 ; "c" -> 5
    groups, facts = build_rank_groups(np.array([0, 0, 1, 1, 1, 2]))
    assert [len(lane) for lane in groups.lanes] == [1, 2, 4]
    assert [s.tolist() for s in groups.starts] == [[5], [0], [2]]
    assert [s.tolist() for s in groups.sizes] == [[1], [2], [3]]
    # slots: bucket 1 holds 1, bucket 2 holds 2, bucket 4 holds 4 (3 used)
    assert groups.slot.tolist() == [1, 2, 3, 4, 5, 0]
    assert facts["rank_pairs"] == 1 + 4 + 9 == facts["rank_pairs_all"]
    assert facts["rank_pair_slots"] == 1 + 4 + 16
    with pytest.raises(ValueError, match="ordered by query"):
        build_rank_groups(np.array([1, 0, 1]))


def test_gbt_ranking_synthetic_dataset():
    model = ydf.GradientBoostedTreesLearner(
        label="LABEL",
        task=Task.RANKING,
        ranking_group="GROUP",
        num_trees=40,
    ).train(f"csv:{D}/synthetic_ranking_train.csv")
    ev = model.evaluate(f"csv:{D}/synthetic_ranking_test.csv")
    ndcg = ev.metrics["ndcg@5"]
    # The reference GBT reaches NDCG@5 ≈ 0.72 on this dataset; random ≈ 0.60.
    assert ndcg > 0.65, str(ev)


def test_ranking_requires_group():
    with pytest.raises(ValueError, match="ranking_group"):
        ydf.GradientBoostedTreesLearner(
            label="LABEL", task=Task.RANKING, num_trees=2
        ).train(f"csv:{D}/synthetic_ranking_train.csv")


def test_xe_ndcg_loss():
    model = ydf.GradientBoostedTreesLearner(
        label="LABEL",
        task=Task.RANKING,
        ranking_group="GROUP",
        loss="XE_NDCG_MART",
        num_trees=40,
    ).train(f"csv:{D}/synthetic_ranking_train.csv")
    ev = model.evaluate(f"csv:{D}/synthetic_ranking_test.csv")
    assert ev.metrics["ndcg@5"] > 0.65, str(ev)
