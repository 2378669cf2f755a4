"""Seconds the job that made the device inputs the window's first job
reused (in set-up, the warm job) spent making and sending them: the
query structure, the row split and the upload. Source:
`model.training_profile`'s `dataset.rank_groups` + `dataset.split` +
`dataset.device_loop.h2d`, that job's spans `ydf.rank_groups`,
`ydf.split` and `ydf.device_loop.h2d`, kept with the Dataset beside
those arrays. A program without those keys (an older one) gives
nothing. Layer `learner.train`; moves setup_s."""

META = {
    "layer": "learner.train",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "moves": "setup_s",
    "workloads": None
}

KEYS = ("dataset.rank_groups", "dataset.split", "dataset.device_loop.h2d")

def read(run):
    if not run["jobs"]:
        return None
    profile = run["jobs"][0]["profile"]
    if any(k not in profile for k in KEYS):
        return None
    return sum(profile[k] for k in KEYS)
