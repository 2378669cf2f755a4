"""Decides `correct`: the readings of the plain reference over what the
timed path produced, each beside a limit of its own.

The limits are data (`benchmark/limits/<cell>.json`), set from readings
on the chip by the rule in PERF.md section 2: above the largest reading
of sound runs over a dozen seeds, below the smallest reading of the
control (the program's own lower-precision histogram) and of each
planted fault.

A configuration whose `reference` block names a `"module"` is read by
`benchmark/references/<module>.py` instead (`of_config`), which gives
`forest_arrays(model)` and `readings(x, y, hp, jobs, follow_trees,
devices)` as this file does; what `readings` returns is judged here.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from harness import manifest
from harness.reference import GbtReference

FOREST_KEYS = ("feature", "threshold", "left", "right", "is_leaf",
               "leaf_value", "cover", "num_nodes")


def of_config(config):
    """What reads this configuration's jobs: the module its `reference`
    block names, or this one."""
    name = config["reference"].get("module")
    if name is None:
        return sys.modules[__name__]
    return manifest.named_module("references", name,
                                 ("forest_arrays", "readings"))


def forest_arrays(model):
    """The host copy of what a job produced: the forest's arrays, the
    per-tree losses and the initial prediction."""
    f = model.forest.to_numpy()
    out = {k: np.asarray(f[k]) for k in FOREST_KEYS}
    out["leaf_value"] = out["leaf_value"][..., 0]
    logs = model.training_logs
    out["train_loss"] = np.asarray(logs["train_loss"], np.float64)
    out["valid_loss"] = (None if logs.get("valid_loss") is None
                         else np.asarray(logs["valid_loss"], np.float64))
    out["initial_prediction"] = float(
        np.asarray(model.initial_predictions).ravel()[0])
    out["bin_edges"] = np.asarray(model.binner.boundaries, np.float32)
    return out


def jobs_differ(jobs) -> int:
    """How many of the window's jobs produced another forest than the
    last one, bit for bit (they train one table with one seed)."""
    last = jobs[-1]
    return sum(
        any(not np.array_equal(j[k], last[k], equal_nan=True)
            for k in FOREST_KEYS + ("train_loss",))
        for j in jobs[:-1])


def readings(x, y, hp, jobs, follow_trees=3, devices=None,
             block_rows=1 << 19, ref=None):
    """{name: number} for the last job of `jobs` (forest_arrays of each
    job the window finished), by the reference run over the raw table,
    its row blocks divided over `devices` (the cell's chips; the first
    device if None). `ref`: a GbtReference already built for this table,
    to read several models against it (tools/limits.py)."""
    got = jobs[-1]
    if ref is None:
        ref = GbtReference(x, y, hp, block_rows=block_rows, devices=devices)
    ref.reset()
    t_trees = time.perf_counter()
    trees = min(follow_trees, len(got["train_loss"]))
    out = {
        "jobs_differ": jobs_differ(jobs),
        "bin_edges_differ": int(np.sum(got["bin_edges"] != ref.edges)),
        "init_gap": abs(got["initial_prediction"] - ref.initial_prediction),
        "thresholds_off_grid": 0, "leaf_rows_gap": 0.0, "leaf_gap": 0.0,
        "train_loss_gap": 0.0, "valid_loss_gap": 0.0,
    }
    leaf_gaps = []
    if trees < follow_trees:
        out["trees_missing"] = follow_trees - trees
    for t in range(trees):
        tree = {k: got[k][t] for k in FOREST_KEYS}
        r = ref.follow_tree(tree, with_regret=(t == 0))
        if t == 0:
            out["split_regret"] = r["split_regret"]
        out["thresholds_off_grid"] += r["thresholds_off_grid"]
        leaf_gaps.append(r["leaf_gaps"])
        for k in ("leaf_rows_gap", "leaf_gap"):
            out[k] = max(out[k], r[k])
        out["train_loss_gap"] = max(
            out["train_loss_gap"],
            abs(got["train_loss"][t] - r["train_loss"]) / r["train_loss"])
        if r["valid_loss"] is not None and got["valid_loss"] is not None:
            out["valid_loss_gap"] = max(
                out["valid_loss_gap"],
                abs(got["valid_loss"][t] - r["valid_loss"]) / r["valid_loss"])
    if leaf_gaps:
        # The worst leaf swings with one cancellation; the middle of the
        # leaves is steady from seed to seed and is what rounding every
        # gradient to a coarser grid moves.
        out["leaf_gap_median"] = float(np.median(np.concatenate(leaf_gaps)))
    ref.seconds["trees"] = time.perf_counter() - t_trees
    print("[reference.phases] " + json.dumps(dict(
        ref.seconds, chips=len(ref.parts), blocks=ref.blocks)),
        file=sys.stderr, flush=True)
    return out


def judge(numbers, limits):
    """(correct, {name: {"value", "limit"}}): every limit's number has to
    be there, finite and at or under its limit."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        table[name] = {"value": value, "limit": limit}
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
    if numbers.get("trees_missing"):
        table["trees_missing"] = {"value": numbers["trees_missing"], "limit": 0}
        ok = False
    return ok, table
