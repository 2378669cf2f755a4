"""Peak device memory on the fullest chip over the device's limit.
Source: `memory_stats()` after the window. Layer `device`; moves
train_rows_trees_per_s."""

META = {
    "layer": "device",
    "unit": "%",
    "better": "lower",
    "source": "program_counter",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    mem = run["memory"]
    if not mem.get("peak_bytes_in_use") or not mem.get("bytes_limit"):
        return None
    return 100.0 * mem["peak_bytes_in_use"] / mem["bytes_limit"]
