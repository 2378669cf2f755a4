"""The benchmark's tables, made from a seed.

`rows` x `features` float32 columns drawn from a standard normal, a
label, then 1 % NaN in columns 1 and 9, injected after the label so that
no NaN leaks into it. Two labels, named by the configuration's `table`
(any other name is a file of its own, `benchmark/tables/<table>.py`,
whose `make_table(rows, features, seed)` keeps `make_table`'s contract
below):

  binary_logit       a copy of `chip_smoke.make_data` (PR 21), the
                     Higgs-shaped table: a binary label from a fixed
                     logit of the first five columns
  linear_regression  what scikit-learn's `make_regression` makes (the
                     "Synthetic" table of the XGBoost-GPU paper): a
                     float target, linear in the first ten columns with
                     fixed coefficients of the size `make_regression`
                     draws (100 * U(0, 1)), plus N(0, 10) noise

Differences from the original, none of which changes the distribution:
rows are drawn in blocks, each block from its own child of the seed, so
that a few threads can fill a 3 GB table in seconds (numpy's generators
release the interpreter lock), and the table is column-major, so that
each column handed to `ydf.Dataset` is contiguous.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 20
NAN_COLUMNS = (1, 9)
NAN_SHARE = 0.01
THREADS = 4
LINEAR_COEFFICIENTS = (82.0, 17.0, 65.0, 43.0, 9.0, 96.0, 31.0, 54.0, 72.0, 28.0)
LINEAR_NOISE = 10.0
KINDS = ("binary_logit", "linear_regression")


def _fill_block(seed, block, x, y, kind) -> None:
    lo = block * BLOCK_ROWS
    hi = min(lo + BLOCK_ROWS, x.shape[1])
    rng = np.random.default_rng([block, seed])
    xb = rng.standard_normal((x.shape[0], hi - lo), dtype=np.float32)
    if kind == "binary_logit":
        logit = xb[0] - 0.5 * xb[1] + np.sin(2 * xb[2]) + xb[3] * xb[4]
        y[lo:hi] = rng.random(hi - lo) < 1.0 / (1.0 + np.exp(-logit))
    else:
        target = LINEAR_NOISE * rng.standard_normal(hi - lo, dtype=np.float32)
        for i, c in enumerate(LINEAR_COEFFICIENTS):
            target += np.float32(c) * xb[i]
        y[lo:hi] = target
    for col in NAN_COLUMNS:
        xb[col, rng.random(hi - lo) < NAN_SHARE] = np.nan
    x[:, lo:hi] = xb


def table_module(kind: str):
    """The file that makes a table kind this one does not."""
    from harness import manifest

    try:
        return manifest.named_module("tables", kind, ("make_table",))
    except manifest.ManifestError as err:
        raise manifest.ManifestError(
            f"table kind {kind!r} is not one of {KINDS}, and {err}")


def make_table(rows: int, features: int, seed: int, kind: str):
    """Returns (x, y): x float32 [features, rows] (column-major table),
    y [rows], int64 labels or float32 targets. The same arguments give
    the same bytes. `seed` is any whole number; its absolute value is
    used."""
    if kind not in KINDS:
        return table_module(kind).make_table(rows, features, seed)
    if features < 10:
        raise ValueError("the label and the NaN columns need 10 features")
    x = np.empty((features, rows), np.float32)
    y = np.empty((rows,), np.int64 if kind == "binary_logit" else np.float32)
    blocks = range((rows + BLOCK_ROWS - 1) // BLOCK_ROWS)
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(_fill_block, abs(int(seed)), b, x, y, kind)
                  for b in blocks]:
            f.result()
    return x, y


def as_columns(x: np.ndarray, y: np.ndarray, label: str = "label") -> dict:
    """The dict of columns `ydf.Dataset.from_data` takes."""
    data = {f"f{i}": x[i] for i in range(x.shape[0])}
    data[label] = y
    return data
