"""XLA dispatches of the boosting loop per tree grown in the window.
Source: `ops.device_loop.stats_snapshot()`, reset at the window's start.
Layer `ops.device_loop`; moves train_rows_trees_per_s."""

META = {
    "layer": "ops.device_loop",
    "unit": "count",
    "better": "lower",
    "source": "program_counter",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    s = run["loop_stats"]
    return s["dispatches"] / s["trees"] if s["trees"] else None
