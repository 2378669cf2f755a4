"""Share of one traced job cycle (a job's `train()` call to where the
next would start) in which no operation ran on the device. Source: the
profiler's trace, reduced by harness/xplane.py. Layer `device`; moves
train_rows_trees_per_s."""

META = {
    "layer": "device",
    "unit": "%",
    "better": "lower",
    "source": "device_trace",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
