"""The ranking cell `mslr30k_rank.sweep` at a size a CPU test run can
hold (40,000 documents, about 330 queries of 1 to 1,251, depth 4),
through `run_cell` with the cell's own files: a sound run reads
`correct`; the control (the program's 8-bit histogram) and the planted
faults read not `correct`, among them the mechanism's own,
`groups_truncated`: the documents past a query's 512th get no gradient,
which is what a lazy bucketing would do."""

import json
import os
import subprocess
import sys

import pytest

import ydf_tpu
from tests.small import run_small
from tests.test_faults import faulty_learner, split_altered

CELL = "mslr30k_rank.sweep"
HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 5
SCRIPT = """
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
from tests.test_rank_cell import run_rank
r = run_rank()
print(json.dumps({{"correct": r["correct"], "compared": r["compared"]}}))
"""


def run_rank(seed=SEED):
    return run_small(CELL, seed=seed, features=137)


def groups_truncated_learner(cap=512):
    """The learner with the lazy bucketing's fault: a query's documents
    past its `cap`-th train on no gradient (the program's own cap,
    which the configuration leaves off)."""
    real = ydf_tpu.GradientBoostedTreesLearner

    class Learner(real):
        def train(self, ds, valid=None):
            self.ranking_max_group_size = cap
            return super().train(ds, valid=valid)

    return Learner


def test_sound_run_is_correct():
    result = run_rank()
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "train_rows_trees_per_s"}
    assert "trees_missing" not in result["compared"]


@pytest.mark.parametrize("fault", ["split_altered", "half_batch",
                                   "groups_truncated"])
def test_fault_is_not_correct(monkeypatch, fault):
    learner = {
        "split_altered": lambda: faulty_learner(alter=split_altered),
        "half_batch": lambda: faulty_learner(half_batch=True),
        "groups_truncated": groups_truncated_learner,
    }[fault]()
    monkeypatch.setattr(ydf_tpu, "GradientBoostedTreesLearner", learner)
    with pytest.warns() if fault == "groups_truncated" else _no_check():
        result = run_rank()
    assert not result["correct"], result["compared"]
    if fault == "groups_truncated":  # by the leaves or the losses
        over = {k for k, c in result["compared"].items()
                if c["value"] is None or c["value"] > c["limit"]}
        assert over & {"leaf_gap", "leaf_gap_median", "train_loss_gap",
                       "valid_loss_gap"}, result["compared"]


class _no_check:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_int8_control_is_not_correct():
    """The program's own 8-bit histogram, switched on in a fresh process
    (the switch is read when the boosting loop is first traced)."""
    bench = os.path.dirname(HERE)
    env = dict(os.environ, YDF_TPU_HIST_QUANT="int8", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         SCRIPT.format(root=os.path.dirname(bench), bench=bench)],
        env=env, capture_output=True, text=True, timeout=600, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] is False, got["compared"]
