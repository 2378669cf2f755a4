"""The reference's row blocks divided over the devices of a cell
(`GbtReference(devices=...)`): at 40,000 rows in blocks of 4,096 (ten
blocks; three devices make the parts uneven) every reading is the
one-device reading TO THE LAST DIGIT, since each device runs the
one-device programs over its own blocks and the host adds the same
float32 partial sums in the same order in float64. Then: a cell's chips reach `train()` as a mesh over exactly those
devices, every one taking rows, and the planted
faults still come out not correct with program and reference on four
devices.
The int8 control on four devices is in test_control.py, `train_mfu_pct`
on four chips in test_work.py, the trace reduction on two device planes
in test_xplane.py, what a configuration names by name in
test_manifest.py."""

import jax
import pytest

import ydf_tpu
from harness import compare, runner
from harness.datagen import as_columns, make_table
from harness.reference import GbtReference
from tests.small import run_small, small_files
from tests.test_faults import faulty_learner, split_altered

ROWS, BLOCK = 40_000, 4096
LOSSES = {"binomial": ("binary_logit", "CLASSIFICATION"),
          "squared_error": ("linear_regression", "REGRESSION")}


@pytest.fixture(scope="module", params=sorted(LOSSES))
def one_device(request):
    """A table, a forest the program grew on it, and its readings by the
    reference on one device."""
    loss = request.param
    kind, task = LOSSES[loss]
    x, y = make_table(ROWS, 28, 7, kind)
    hp = dict(loss=loss, num_bins=256, validation_ratio=0.1,
              random_seed=123456, shrinkage=0.1, min_examples=5,
              l2_regularization=0.0, max_depth=4)
    model = ydf_tpu.GradientBoostedTreesLearner(
        label="label", task=ydf_tpu.Task[task], num_trees=4,
        max_depth=4).train(as_columns(x, y))
    jobs = [compare.forest_arrays(model)]
    return x, y, hp, jobs, compare.readings(x, y, hp, jobs, block_rows=BLOCK)


@pytest.mark.parametrize("devices", [2, 3, 4])
def test_readings_equal_one_devices_to_the_last_digit(one_device, devices):
    x, y, hp, jobs, want = one_device
    assert len(jax.devices()) >= devices
    got = compare.readings(x, y, hp, jobs, block_rows=BLOCK,
                           devices=jax.devices()[:devices])
    assert got == want
    assert got["leaf_gap"] > 0 and got["train_loss_gap"] > 0  # real sums


def test_more_devices_than_blocks(one_device):
    x, y, hp, jobs, want = one_device
    ref = GbtReference(x, y, hp, block_rows=1 << 15,
                       devices=jax.devices()[:4])
    assert ref.blocks == 2
    assert [(p.lo, p.hi) for p in ref.parts] == [(0, 1), (1, 2)]
    got = compare.readings(x, y, hp, jobs, ref=ref)
    # other blocks, other float32 partial sums: equal to rounding only
    assert got.keys() == want.keys()
    for name in ("jobs_differ", "bin_edges_differ", "thresholds_off_grid",
                 "leaf_rows_gap", "init_gap", "split_regret"):
        assert got[name] == want[name], name
    assert got["leaf_gap"] == pytest.approx(want["leaf_gap"], rel=0.5)


def test_uneven_parts_are_consecutive_and_differ_by_one():
    x, y = make_table(ROWS, 10, 3, "linear_regression")
    ref = GbtReference(x, y, dict(loss="squared_error", num_bins=256,
                                  validation_ratio=0.1, random_seed=1),
                       block_rows=BLOCK, devices=jax.devices()[:3])
    assert [(p.lo, p.hi) for p in ref.parts] == [(0, 3), (3, 6), (6, 10)]
    assert [b.shape for b in ref.bins] == [(10, 3, BLOCK), (10, 3, BLOCK),
                                           (10, 4, BLOCK)]
    assert [next(iter(b.devices())) for b in ref.bins] == jax.devices()[:3]


# ------------------ through run_cell, program and reference on 4 devices


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_a_cells_chips_reach_train_as_data(chips):
    _, (_, _, config, mix, _) = small_files(chips=chips)
    devices = jax.devices()[:chips]
    mesh = runner.Traffic(config, mix, 5, devices).new_learner().mesh
    if chips == 1:
        assert mesh is None  # the learner as a one-chip cell always built it
    else:
        assert dict(mesh.shape) == {"data": chips, "feature": 1}
        assert list(mesh.devices.flat) == devices


def test_sound_run_is_correct_on_four_devices():
    result = run_small(chips=4)
    assert result["correct"], result["compared"]


@pytest.mark.parametrize("fault", [
    dict(alter=split_altered), dict(half_batch=True),
], ids=["split_altered", "half_batch"])
def test_fault_is_not_correct_on_four_devices(monkeypatch, fault):
    monkeypatch.setattr(ydf_tpu, "GradientBoostedTreesLearner",
                        faulty_learner(**fault))
    result = run_small(chips=4)
    assert not result["correct"], result["compared"]
