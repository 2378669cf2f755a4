"""Seconds a job's host spends enqueueing what `train()` sends to the
device (bin matrices, labels, weights of the training and validation
rows). Source: `model.training_profile["device_loop.h2d"]`, the host
span `ydf.device_loop.h2d`. Layer `ops.device_loop`; moves
train_rows_trees_per_s."""

META = {
    "layer": "ops.device_loop",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    spans = [j["profile"].get("device_loop.h2d") for j in run["jobs"]]
    if not spans or None in spans:
        return None
    return sum(spans) / len(spans)
