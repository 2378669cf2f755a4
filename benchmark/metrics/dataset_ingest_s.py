"""Seconds `Dataset.from_data` took to make the Dataset the window's
first job trained on: reading the columns and inferring the dataspec,
once, in set-up. Source: `model.training_profile["dataset.from_data"]`,
the seconds of the host span `ydf.dataset.from_data` that the Dataset
keeps beside its columns (`Dataset.build_seconds`) and every job on it
reports. A program without that key (an older one) gives nothing.
Layer `dataset`; moves setup_s."""

META = {
    "layer": "dataset",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "moves": "setup_s",
    "workloads": None
}

def read(run):
    if not run["jobs"]:
        return None
    return run["jobs"][0]["profile"].get("dataset.from_data")
