"""Seconds a job spends in `train()`'s `rank_groups` span: ordering the
rows by query, bucketing the queries by size and sending the query
structure to the device, which a job does only where its `Dataset` does
not hold the structure yet (then it is the look-up, microseconds).
Source: `model.training_profile["rank_groups"]`, the host span
`ydf.rank_groups`, mean over the window's jobs. A program without the
span (an older one) gives nothing. Layer `learner.train`; moves
train_rows_trees_per_s."""

META = {
    "layer": "learner.train",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "moves": "train_rows_trees_per_s",
    "workloads": ["mslr30k_rank.sweep"]
}

def read(run):
    spans = [j["profile"].get("rank_groups") for j in run["jobs"]]
    if not spans or None in spans:
        return None
    return sum(spans) / len(spans)
