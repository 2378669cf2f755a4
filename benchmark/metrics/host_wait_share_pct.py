"""Share of the jobs' wall time (`train()` call to return) in which the
host was blocked on the device: `training_profile["device_loop.wait"]`,
the host span `ydf.device_loop.wait`, over `t1 - t0`. With
`device_idle_pct` it should add to about 100: the host waits while the
device works. Layer `ops.device_loop`; moves train_rows_trees_per_s."""

META = {
    "layer": "ops.device_loop",
    "unit": "%",
    "better": "higher",
    "source": "program_span",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    waits = [j["profile"].get("device_loop.wait") for j in run["jobs"]]
    wall = sum(j["t1"] - j["t0"] for j in run["jobs"])
    if not wall or None in waits:
        return None
    return 100.0 * sum(waits) / wall
