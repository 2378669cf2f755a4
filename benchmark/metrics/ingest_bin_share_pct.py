"""Share of the window the jobs spent in `train()`'s `ingest_bin` stage
(dataspec, Binner.fit, label encoding; the bins themselves are cached on
the shared Dataset). Source: `model.training_profile`, a host clock
inside the program. Layer `learner.train`; moves train_rows_trees_per_s."""

META = {
    "layer": "learner.train",
    "unit": "%",
    "better": "lower",
    "source": "program_span",
    "moves": "train_rows_trees_per_s",
    "workloads": None
}

def read(run):
    return 100.0 * sum(j["profile"]["ingest_bin"] for j in run["jobs"]) / run["window_s"]
