"""The least work a tree of a histogram GBT asks of the chip, from the
configuration's shapes alone, and the chips' published peaks. Nothing
here reads the program: the same shapes give the same numbers whatever
kernel implements them, so `train_mfu_pct` bounds every later rewrite.

Per level of a tree every training row's bins are read once (one byte a
feature) with its gradient, hessian, weight and node id (16 bytes), and
each (row, feature) adds three numbers into a histogram cell.
"""

from __future__ import annotations

import json
import os

ROW_STATE_BYTES = 16  # gradient, hessian, weight, node id: 4 bytes each
ADDS_PER_CELL = 3  # gradient, hessian and count into one histogram cell


def load_peaks(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "peaks.json")
    with open(path) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path}")
    return peaks[device_kind]


def training_rows(rows: int, validation_ratio: float) -> int:
    if validation_ratio <= 0:
        return rows
    return rows - min(max(int(rows * validation_ratio), 1), rows - 1)


def tree_bytes_and_ops(train_rows: int, features: int, levels: int):
    """(bytes moved, operations) one tree needs at the least."""
    return (levels * train_rows * (features + ROW_STATE_BYTES),
            levels * train_rows * features * ADDS_PER_CELL)


def least_seconds_per_tree(train_rows, features, levels, peaks, chips=1):
    """(seconds, what bounds it). `peaks` are one chip's; the rows of a
    cell on `chips` chips are divided over them, and bytes and operations
    both go by the row, so the least time is a `chips`-th of one chip's."""
    nbytes, ops = tree_bytes_and_ops(train_rows, features, levels)
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    by_ops = ops / peaks["flops_per_s"]
    return (max(by_bytes, by_ops) / chips,
            "bytes" if by_bytes >= by_ops else "ops")
