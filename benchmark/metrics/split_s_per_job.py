"""Seconds a job spends in `train()`'s `split` span: the validation
permutation and the host gathers of the training and validation rows
(bins, labels, weights). Source: `model.training_profile["split"]`, the
host span `ydf.split`. Layer `learner.train`; moves
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
    spans = [j["profile"].get("split") for j in run["jobs"]]
    if not spans or None in spans:
        return None
    return sum(spans) / len(spans)
