"""A cell at a size a CPU test run can hold: the real manifest, metrics,
limits and traffic mix, the configuration's rows and depth cut (depth 4
keeps every leaf thousands of rows wide, as the leaves of the chip's
cell are, so that float32 noise stays where the limits expect it)."""

import copy
import time

from harness import manifest, runner

ROWS = 40_000


def small_files(cell_name="synth100_gbt.sweep", rows=ROWS, depth=4,
                features=28, frontier=None, table=None, chips=1):
    m = manifest.load()
    files = list(manifest.cell_files(m, cell_name))
    files[0] = dict(files[0], chips=chips)  # the reference's devices
    cfg = copy.deepcopy(files[2])
    cfg["rows"], cfg["features"] = rows, features
    if table:  # the binary table and loss, through the same harness
        cfg["table"] = table
        cfg["hyperparameters"]["task"] = "CLASSIFICATION"
        cfg["reference"]["loss"] = "binomial"
    if frontier:
        cfg["hyperparameters"]["max_frontier"] = frontier
        cfg["reference"]["max_frontier"] = frontier
    cfg["hyperparameters"]["max_depth"] = depth
    cfg["reference"]["max_depth"] = depth
    files[2] = cfg
    return m, tuple(files)


def run_small(cell_name="synth100_gbt.sweep", seed=5, seconds=0.5, **kw):
    m, files = small_files(cell_name, **kw)
    _, result = runner.run_cell(m, cell_name, seed, seconds, 0, time.time(),
                                check_kwargs={"block_rows": 1 << 14},
                                files=files)
    return result
