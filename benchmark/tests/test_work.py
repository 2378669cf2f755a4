"""`train_mfu_pct`'s arithmetic on shapes worked by hand."""

import pytest

from harness import work
from metrics import train_mfu_pct

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_training_rows():
    assert work.training_rows(1000, 0.1) == 900
    assert work.training_rows(1000, 0.0) == 1000
    assert work.training_rows(5, 0.1) == 4  # at least one validation row


def test_bytes_and_ops_by_hand():
    # 1,000 rows x 28 features, 6 levels: (28 + 16) B and 28 * 3 adds a row
    assert work.tree_bytes_and_ops(1000, 28, 6) == (264_000, 504_000)


def test_least_seconds_is_bound_by_bytes():
    s, by = work.least_seconds_per_tree(900_000, 28, 6, V5E)
    assert by == "bytes"
    assert s == pytest.approx(6 * 900_000 * 44 / 819e9)
    assert s > 6 * 900_000 * 84 / 197e12


def test_four_chips_take_a_quarter_of_one_chips_least_time():
    one, by = work.least_seconds_per_tree(900_000, 28, 6, V5E)
    assert work.least_seconds_per_tree(900_000, 28, 6, V5E, chips=1) == (one, by)
    assert work.least_seconds_per_tree(900_000, 28, 6, V5E, chips=4) == (
        one / 4, by)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")
    assert work.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_mfu_reader_by_hand():
    run = {"config": {"rows": 1_000_000, "features": 28,
                      "reference": {"validation_ratio": 0.1, "max_depth": 6}},
           "device_kind": "TPU v5 lite", "window_s": 10.0, "chips": 1,
           "jobs": [{"trees": 3}, {"trees": 3}]}
    least = 6 * 900_000 * 44 / 819e9
    assert train_mfu_pct.read(run) == pytest.approx(100 * least * 6 / 10.0)
    # the same trees in the same window on four chips' peaks
    assert train_mfu_pct.read(dict(run, chips=4)) == train_mfu_pct.read(run) / 4
    run["jobs"] = []
    assert train_mfu_pct.read(run) is None
