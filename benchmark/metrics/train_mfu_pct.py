"""The whole step's share of the chip's peak: the least time the chip
could take for the trees the window kept (harness/work.py, from the
configuration's shapes and the published peaks of as many chips as the
cell has; bytes bound it at these shapes) over the window's time. Layer
`train_step`; moves train_rows_trees_per_s."""

from harness import work

META = {
    "layer": "train_step",
    "unit": "%",
    "better": "higher",
    "source": "host_clock",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}


def read(run):
    cfg = run["config"]
    hp = cfg["reference"]
    peaks = work.load_peaks(run["device_kind"])
    per_tree, _ = work.least_seconds_per_tree(
        work.training_rows(cfg["rows"], hp["validation_ratio"]),
        cfg["features"], hp["max_depth"], peaks, chips=run["chips"])
    trees = sum(j["trees"] for j in run["jobs"])
    return 100.0 * per_tree * trees / run["window_s"] if trees else None
