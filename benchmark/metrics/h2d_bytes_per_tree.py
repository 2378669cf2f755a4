"""Bytes `train()` sent host-to-device per tree grown in the window.
Source: `ops.device_loop.stats_snapshot()["h2d_bytes"]`, reset at the
window's start. Layer `ops.device_loop`; moves train_rows_trees_per_s."""

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
    if "h2d_bytes" not in s or not s["trees"]:
        return None
    return s["h2d_bytes"] / s["trees"]
