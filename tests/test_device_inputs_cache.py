"""A job's device inputs stay with the `Dataset` (PR 31): the six arrays
`train()` hands the boosting loop are kept, on the device, with the
Dataset they were made from; the next `train()` on it that would make
the same arrays encodes, gathers and sends nothing. At most one Dataset
in the process holds such arrays, the one trained on last.
"""

import gc
import sys
import threading
import weakref

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.dataset import dataset as dataset_lib
from ydf_tpu.ops import device_loop

FEATURES = [f"f{i}" for i in range(6)]
LABELS = {"binary": ("label", Task.CLASSIFICATION),
          "regression": ("y", Task.REGRESSION)}


def columns(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    cols = {name: x[:, i] for i, name in enumerate(FEATURES)}
    cols["label"] = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.int64)
    cols["label2"] = (x[:, 3] > 0.3).astype(np.int64)
    cols["y"] = (2 * x[:, 0] + x[:, 3]).astype(np.float32)
    cols["w"] = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    cols["q"] = rng.integers(0, 60, size=n)
    return cols


def make_dataset(seed=0):
    return ydf.Dataset.from_data(columns(seed=seed), label="label")


def learner(kind="binary", **kw):
    label, task = LABELS[kind]
    kw = {"label": label, "task": task, "features": FEATURES,
          "num_trees": 2, "max_depth": 3, **kw}
    return ydf.GradientBoostedTreesLearner(**kw)


def job(data, kind="binary", valid=None, **kw):
    """(model, bytes the job sent to the device)."""
    device_loop.reset_stats()
    model = learner(kind, **kw).train(data, valid=valid)
    return model, device_loop.stats_snapshot()["h2d_bytes"]


def cached(model):
    return model.training_profile["device_loop.inputs_cached"]


def holder(ds):
    """The Dataset the learner trained on: `ds`, or `ds` under the
    column types the learner forced, which hangs on `ds`."""
    held = [d for d in (ds, *ds._retyped.values()) if d._device_inputs]
    assert len(held) <= 1
    return held[0] if held else None


def assert_same_forest(a, b):
    a, b = a.forest.to_numpy(), b.forest.to_numpy()
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        if x.dtype == np.float32:  # leaf values carry NaN at inner nodes
            x, y = x.view(np.int32), y.view(np.int32)
        np.testing.assert_array_equal(x, y, err_msg=k)


@pytest.mark.parametrize("kind", ["regression", "binary"])
def test_second_job_sends_nothing_and_grows_the_same_forest(kind):
    ds = make_dataset()
    first, sent_first = job(ds, kind)
    second, sent_second = job(ds, kind)
    assert cached(first) == 0 and sent_first > 0
    assert cached(second) == 1 and sent_second == 0
    for span in ("ingest_bin", "split", "device_loop.h2d"):
        assert span in second.training_profile, span
    assert_same_forest(first, second)
    np.testing.assert_array_equal(
        first.predict(columns(seed=1)), second.predict(columns(seed=1)))
    # the six arrays: bins, labels and weights of both parts
    held = holder(ds)._device_inputs[2]
    assert len(held) == 6
    assert sent_first == sum(a.nbytes for a in held)
    assert holder(ds).device_inputs_bytes() == sent_first


def test_a_hit_encodes_and_gathers_nothing(monkeypatch):
    """On a hit nothing that goes by the row runs on the host: the
    label is not encoded, no weights are made, no row is gathered."""
    ds = make_dataset()
    job(ds)
    calls = []
    from ydf_tpu.learners import gbt

    monkeypatch.setattr(
        dataset_lib.Dataset, "encoded_label",
        lambda *a, **k: calls.append("encoded_label"))
    monkeypatch.setattr(
        gbt, "_split_rows", lambda *a, **k: calls.append("_split_rows"))
    monkeypatch.setattr(
        gbt.GradientBoostedTreesLearner, "_encode_targets",
        lambda *a, **k: calls.append("_encode_targets"))
    model, sent = job(ds)
    assert cached(model) == 1 and sent == 0 and calls == []


@pytest.mark.parametrize("other", [
    {"random_seed": 7},
    {"validation_ratio": 0.2},
    {"label": "label2"},
    {"weights": "w"},
    {"early_stopping": "NONE"},  # no validation rows at all
], ids=lambda kw: next(iter(kw)))
def test_another_key_is_a_miss_that_replaces_the_entry(other):
    ds = make_dataset()
    base, _ = job(ds)
    entry = holder(ds)._device_inputs
    miss, sent = job(ds, **other)
    assert cached(miss) == 0 and sent > 0
    # one Dataset, one entry: the other job's arrays took its place
    replaced = holder(ds)._device_inputs
    assert replaced[1] != entry[1]
    assert all(a is not b for a, b in zip(replaced[2], entry[2]))
    hit, sent = job(ds, **other)
    assert cached(hit) == 1 and sent == 0
    assert_same_forest(miss, hit)
    again, sent = job(ds)  # and the first key is a miss now
    assert cached(again) == 0 and sent > 0
    assert_same_forest(base, again)


def test_hyperparameters_are_no_part_of_the_key():
    ds = make_dataset()
    job(ds)
    for kw in ({"max_depth": 4}, {"num_trees": 3}, {"shrinkage": 0.3},
               {"l2_regularization": 1.0}):
        model, sent = job(ds, **kw)
        assert cached(model) == 1 and sent == 0, kw


def test_a_second_dataset_takes_the_residency():
    one, two = make_dataset(0), make_dataset(1)
    job(one)
    array = weakref.ref(holder(one)._device_inputs[2][0])
    assert dataset_lib._device_resident() is holder(one)
    job(two)
    assert holder(one) is None and holder(two) is not None
    assert dataset_lib._device_resident() is holder(two)
    gc.collect()
    assert array() is None  # the first's device arrays are gone
    model, sent = job(one)  # and it is a miss that takes it back
    assert cached(model) == 0 and sent > 0
    assert holder(two) is None and holder(one) is not None


@pytest.mark.parametrize("kind", ["regression", "binary"])
def test_dropping_the_dataset_frees_the_device_arrays(kind):
    ds = make_dataset()
    job(ds, kind)
    arrays = [weakref.ref(a) for a in holder(ds)._device_inputs[2]]
    ref = weakref.ref(ds)
    assert dataset_lib.device_inputs_bytes_total() > 0
    del ds
    gc.collect()
    assert ref() is None and all(a() is None for a in arrays)
    assert dataset_lib._device_resident() is None
    assert dataset_lib.device_inputs_bytes_total() == 0


def bypass_cases():
    cols = columns()
    return {
        "dict": lambda: dict(data=cols),
        "valid": lambda: dict(data=make_dataset(), valid=columns(seed=3)),
        "oblique": lambda: dict(
            data=make_dataset(), split_axis="SPARSE_OBLIQUE"),
    }


@pytest.mark.parametrize("case", ["dict", "valid", "oblique"])
def test_what_bypasses_never_hits(case):
    kw = bypass_cases()[case]()
    data = kw.pop("data")
    first, sent_first = job(data, **kw)
    second, sent_second = job(data, **kw)
    assert cached(first) == 0 and cached(second) == 0
    assert sent_first > 0 and sent_second == sent_first
    assert dataset_lib._device_resident() is None
    assert_same_forest(first, second)


def test_a_ranking_job_keeps_its_inputs_and_its_query_structure():
    """Since PR 36 a ranking job hits too: its rows go by query and the
    query structure is kept beside the six arrays (the whole of it:
    tests/test_ranking_reference.py)."""
    ds = ydf.Dataset.from_data(columns())
    kw = dict(kind="regression", task=Task.RANKING, ranking_group="q")
    first, sent_first = job(ds, **kw)
    second, sent_second = job(ds, **kw)
    assert cached(first) == 0 and sent_first > 0
    assert cached(second) == 1 and sent_second == 0
    assert len(holder(ds)._device_inputs[2]) == 7
    assert holder(ds).device_inputs_bytes() == sent_first
    assert_same_forest(first, second)
    third, sent_third = job(ds, ranking_max_group_size=16, **kw)  # another cap
    assert cached(third) == 0 and sent_third > 0


def test_a_job_that_bypasses_lets_go_of_the_kept_inputs():
    ds = make_dataset()
    job(ds)
    assert holder(ds) is not None
    job(columns(seed=2))  # a dict: its own table goes up
    assert holder(ds) is None and dataset_lib._device_resident() is None


@pytest.mark.parametrize("name", ["random_forest", "cart", "isolation"])
def test_other_learners_let_go_before_they_send_a_table(name):
    ds = make_dataset()
    job(ds)
    assert holder(ds) is not None
    make = {
        "random_forest": lambda: ydf.RandomForestLearner(
            label="label", features=FEATURES, num_trees=2, max_depth=3),
        "cart": lambda: ydf.CartLearner(
            label="label", features=FEATURES, max_depth=3),
        "isolation": lambda: ydf.IsolationForestLearner(
            features=FEATURES, num_trees=2),
    }[name]
    make().train(ds)
    assert holder(ds) is None and dataset_lib._device_resident() is None


def test_cached_host_arrays_are_read_only():
    ds = make_dataset()
    model, _ = job(ds)
    trained_on = holder(ds)
    held = [a for v in trained_on._bin_cache.values()
            for a in (v if isinstance(v, tuple) else (v,))
            if isinstance(a, np.ndarray)]
    assert len(held) >= 5  # the bins, and the split's four arrays
    assert not any(a.flags.writeable for a in held)
    with pytest.raises(ValueError):
        model.binner.transform(trained_on)[0, 0] = 1


def test_the_memory_ledger_has_the_device_row():
    from ydf_tpu.utils import telemetry

    ds = make_dataset()
    job(ds)
    subs = telemetry.ledger().snapshot()["subsystems"]
    assert subs["device_inputs"] == holder(ds).device_inputs_bytes() > 0
    dataset_lib.release_device_inputs()
    assert telemetry.ledger().snapshot()["subsystems"]["device_inputs"] == 0
    model, sent = job(ds)  # let go by hand: a miss again
    assert cached(model) == 0 and sent > 0


def test_threads_never_leave_two_holders():
    """More threads than cores take the device's place for their own
    Dataset over and over: whenever they stop, at most one Dataset
    holds arrays, and it is the one the module points at."""
    import jax.numpy as jnp

    sets = [ydf.Dataset.from_data({"a": np.arange(4.0)}) for _ in range(16)]
    bins, arrays = np.zeros((4, 1), np.uint8), (jnp.zeros(4),) * 6
    stop = threading.Event()

    def work(ds):
        while not stop.is_set():
            ds.keep_device_inputs(bins, ("k",), arrays)
            ds.device_inputs(bins, ("k",))
            dataset_lib.release_device_inputs()
            ds.keep_device_inputs(bins, ("k",), arrays)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=work, args=(d,)) for d in sets]
    try:
        for t in threads:
            t.start()
        stop.wait(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    holders = [d for d in sets if d._device_inputs is not None]
    assert len(holders) == 1
    assert dataset_lib._device_resident() is holders[0]
    dataset_lib.release_device_inputs()
