"""Seconds the `ingest_bin` span took in the job that made the bins and
device inputs the window's first job reused (in set-up, the warm job):
the learner's column types on the Dataset, the binner's fit, the bin
matrix, the label's encoding. Source:
`model.training_profile["dataset.ingest_bin"]`, that job's span
`ydf.ingest_bin`, kept with the Dataset beside those arrays. A program
without that key (an older one) gives nothing. Layer `learner.train`;
moves setup_s."""

META = {
    "layer": "learner.train",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "moves": "setup_s",
    "workloads": None
}

def read(run):
    if not run["jobs"]:
        return None
    return run["jobs"][0]["profile"].get("dataset.ingest_bin")
