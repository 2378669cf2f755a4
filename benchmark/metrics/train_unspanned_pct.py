"""Share of `train()`'s wall time under no named span: `other` (the
total less the top-level spans) over `total` of
`model.training_profile`, summed over the window's jobs. What the
program's own measurement cannot see. Layer `learner.train`; moves
train_rows_trees_per_s."""

META = {
    "layer": "learner.train",
    "unit": "%",
    "better": "lower",
    "source": "program_span",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    profiles = [j["profile"] for j in run["jobs"]]
    total = sum(p.get("total", 0.0) for p in profiles)
    if not total or any("other" not in p for p in profiles):
        return None
    return 100.0 * sum(p["other"] for p in profiles) / total
