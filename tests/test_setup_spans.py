"""What set-up costs, measured where it happens (PR 38): the ingest that
makes a `Dataset` is timed by the Dataset (`dataset.from_data`), the
first job's `ingest_bin` splits into named children, and the job that
makes the bins and device inputs later jobs reuse keeps its seconds with
the Dataset, where every job on it reports them as `dataset.<span>`.
"""

import os
import sys
import time

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.dataset.dataset import release_device_inputs
from ydf_tpu.utils.profiling import BUILD_SPANS, StageTimer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

FEATURES = [f"f{i}" for i in range(6)]
CHILDREN = ("ingest_bin.dataspec", "ingest_bin.binner_fit",
            "ingest_bin.transform", "ingest_bin.targets")
DATASET_KEYS = ("dataset.from_data", "dataset.from_data.infer",
                *("dataset." + k for k in BUILD_SPANS))
# the label and group columns the learner forces a type on (re-typed) or
# does not (regression)
KINDS = {
    "regression": dict(label="y", task=Task.REGRESSION),
    "binary": dict(label="label", task=Task.CLASSIFICATION),
    "ranking": dict(label="y", task=Task.RANKING, ranking_group="q",
                    ranking_max_group_size=None),
}


def columns(n=100_000, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    cols = {name: x[:, i] for i, name in enumerate(FEATURES)}
    cols["label"] = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.int64)
    cols["y"] = (2 * x[:, 0] + x[:, 3]).astype(np.float32)
    cols["q"] = rng.integers(0, 2000, size=n)
    return cols


def profile(data, kind="regression"):
    return ydf.GradientBoostedTreesLearner(
        features=FEATURES, num_trees=2, max_depth=3, **KINDS[kind],
    ).train(data).training_profile


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_first_job_names_the_parts_of_its_ingest_bin(kind):
    ds = ydf.Dataset.from_data(columns())
    p = profile(ds, kind)
    assert all(p[k] >= 0 for k in CHILDREN)
    assert sum(p[k] for k in CHILDREN) <= p["ingest_bin"]
    assert set(DATASET_KEYS) <= set(p)
    # the job made the inputs: the record is its own seconds
    assert all(p["dataset." + k] == p[k] for k in BUILD_SPANS)
    assert 0 < p["dataset.from_data.infer"] <= p["dataset.from_data"]
    # A forced column type the Dataset lacks makes the job infer the
    # dataspec again, over every column; a regression label forces none.
    if kind == "regression":
        assert p["ingest_bin.dataspec"] < 0.25 * p["dataset.from_data.infer"]
    else:
        assert p["ingest_bin.dataspec"] > 0.5 * p["dataset.from_data.infer"]


@pytest.mark.parametrize("kind", ["regression", "binary"])
def test_a_second_job_reports_the_first_jobs_build(kind):
    ds = ydf.Dataset.from_data(columns())
    first, second = profile(ds, kind), profile(ds, kind)
    assert second["device_loop.inputs_cached"] == 1.0
    # its own steps are look-ups, or skipped (0.0)
    assert all(second[k] < 0.05 for k in CHILDREN)
    assert second["ingest_bin.targets"] == 0.0
    assert {k: second[k] for k in DATASET_KEYS} == {
        k: first[k] for k in DATASET_KEYS}


def test_a_job_that_makes_the_inputs_again_replaces_the_record():
    ds = ydf.Dataset.from_data(columns())
    first = profile(ds)
    profile(ydf.Dataset.from_data(columns(seed=1)))  # another table goes up
    release_device_inputs()
    third = profile(ds)
    assert third["device_loop.inputs_cached"] == 0.0
    assert all(third["dataset." + k] == third[k] for k in BUILD_SPANS)
    assert third["dataset.ingest_bin"] != first["dataset.ingest_bin"]
    assert third["dataset.from_data"] == first["dataset.from_data"]


def test_a_dict_reports_its_own_jobs_seconds():
    p = profile(columns(), "binary")
    assert all(p["dataset." + k] == p[k] for k in BUILD_SPANS)
    # the dict's ingest ran inside the job, in `ingest_bin.dataspec`
    assert 0 < p["dataset.from_data"] <= p["ingest_bin.dataspec"]
    assert p["dataset.from_data.infer"] <= p["dataset.from_data"]


def test_a_dataset_made_under_a_dataspec_records_nothing():
    ds = ydf.Dataset.from_data(columns(n=100))
    assert set(ds.build_seconds) == {"dataset.from_data",
                                     "dataset.from_data.infer"}
    again = ydf.Dataset.from_data(ds.data, dataspec=ds.dataspec)
    assert again.build_seconds == {}
    assert ydf.Dataset.from_data(ds) is ds


@pytest.mark.parametrize("made", [True, False])
def test_the_kept_record_leaves_other_and_total_alone(made):
    timer = StageTimer()
    with timer.stage("ingest_bin"):
        with timer.stage("ingest_bin.binner_fit"):
            time.sleep(0.01)
    with timer.stage("split"):
        time.sleep(0.01)
    record = {"dataset.from_data": 100.0, "dataset.ingest_bin": 100.0}
    timer.keep_build(record, made=made)
    p = timer.finish()
    assert p["total"] < 100.0
    assert p["other"] == pytest.approx(
        p["total"] - p["ingest_bin"] - p["split"], abs=1e-9)
    assert p["dataset.from_data"] == 100.0
    assert p["dataset.ingest_bin"] == (p["ingest_bin"] if made else 100.0)
    assert p["ingest_bin.dataspec"] == 0.0  # skipped, and reported


def test_the_ingest_spans_are_on_the_traces_clock(tmp_path):
    """A traced first job holds the children inside `ydf.ingest_bin`,
    and a dict's `ydf.dataset.from_data` inside `.dataspec`, on the
    host line beside the upload."""
    import jax
    from jax.profiler import ProfileData

    data = columns(n=5000)
    profile(dict(data))  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    profile(data)
    jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    (plane,) = [pl for pl in ProfileData.from_file(str(path)).planes
                if pl.name == "/host:CPU"]
    spans = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for line in plane.lines for ev in line.events
             if ev.name.startswith("ydf.")}

    def inside(child, parent):
        return (spans[parent][0] <= spans[child][0]
                and spans[child][1] <= spans[parent][1])

    for child in CHILDREN:
        assert inside("ydf." + child, "ydf.ingest_bin"), child
    assert inside("ydf.dataset.from_data", "ydf.ingest_bin.dataspec")
    assert inside("ydf.dataset.from_data.infer", "ydf.dataset.from_data")
    assert spans["ydf.ingest_bin"][1] <= spans["ydf.device_loop.h2d"][0]


@pytest.mark.parametrize("name, keys", [
    ("dataset_ingest_s", ("dataset.from_data",)),
    ("bin_build_s", ("dataset.ingest_bin",)),
    ("inputs_build_s", ("dataset.rank_groups", "dataset.split",
                        "dataset.device_loop.h2d")),
])
def test_the_metric_reads_the_first_jobs_record(name, keys):
    import importlib

    read = importlib.import_module("metrics." + name).read
    first = {k: 0.5 * (i + 1) for i, k in enumerate(keys)}
    later = {k: 9.0 for k in keys}
    run = {"jobs": [{"profile": first}, {"profile": later}]}
    assert read(run) == pytest.approx(sum(first.values()))
    for missing in keys:  # an older program
        older = {k: v for k, v in first.items() if k != missing}
        assert read({"jobs": [{"profile": older}]}) is None
    assert read({"jobs": []}) is None
