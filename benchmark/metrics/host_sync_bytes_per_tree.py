"""Bytes fetched to the host at dispatch boundaries per tree grown.
Source: `ops.device_loop.stats_snapshot()`. Layer `ops.device_loop`;
moves train_rows_trees_per_s."""

META = {
    "layer": "ops.device_loop",
    "unit": "bytes",
    "better": "lower",
    "source": "program_counter",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    s = run["loop_stats"]
    return s["host_sync_bytes"] / s["trees"] if s["trees"] else None
