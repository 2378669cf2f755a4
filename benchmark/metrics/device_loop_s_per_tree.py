"""Seconds of `train()`'s `device_loop` stage (split, transfer, the
boosting dispatch, the fetch) per tree kept. Source:
`model.training_profile`. Layer `learner.train`; moves
train_rows_trees_per_s."""

META = {
    "layer": "learner.train",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    trees = sum(j["trees"] for j in run["jobs"])
    if not trees:
        return None
    return sum(j["profile"]["device_loop"] for j in run["jobs"]) / trees
