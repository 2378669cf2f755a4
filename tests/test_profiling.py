"""Per-stage training profile + per-engine inference benchmark
(reference: distributed GBT Monitoring per-stage logs, utils/usage.h,
utils/benchmark/inference.h:36-52)."""

import os
import shutil

import numpy as np
import pytest

import ydf_tpu as ydf
from ydf_tpu.config import Task


def _data(n=2000, seed=0):
    rng = np.random.RandomState(seed)
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    y = ((x1 + 0.5 * x2) > 0).astype(np.int64)
    return {"x1": x1, "x2": x2, "y": y}


def test_training_profile_gbt():
    m = ydf.GradientBoostedTreesLearner(
        label="y", num_trees=5, max_depth=3, validation_ratio=0.0,
        early_stopping="NONE",
    ).train(_data())
    p = m.training_profile
    assert p is not None
    for key in ("ingest_bin", "device_loop", "finalize", "total", "other"):
        assert key in p and p[key] >= 0, (key, p)
    assert p["total"] >= p["device_loop"]
    from ydf_tpu.utils.profiling import format_profile

    s = format_profile(p)
    assert "device_loop=" in s and "total=" in s


def _regression_data(n=2000, seed=0, features=4):
    rng = np.random.RandomState(seed)
    d = {f"x{i}": rng.normal(size=n).astype(np.float32)
         for i in range(features)}
    d["y"] = (d["x0"] + 0.5 * d["x1"]
              + 0.1 * rng.normal(size=n)).astype(np.float32)
    return d


def _regression_learner(**kw):
    kw = {"num_trees": 4, "max_depth": 3, **kw}
    return ydf.GradientBoostedTreesLearner(
        label="y", task=Task.REGRESSION, **kw)


@pytest.mark.parametrize("driver", ["single_scan", "early_stop", "checkpointed"])
def test_training_profile_holds_the_drivers_spans(driver, tmp_path):
    """Every job has every span of TRAIN_SPANS, whether the one boosting
    loop runs it as one chunk, as several, or with snapshots."""
    from ydf_tpu.utils.profiling import TRAIN_SPANS

    kw = {"single_scan": {},
          "early_stop": {"num_trees": 12,
                         "early_stopping_num_trees_look_ahead": 3},
          "checkpointed": {"working_dir": str(tmp_path),
                           "resume_training_snapshot_interval_trees": 2}}
    p = _regression_learner(**kw[driver]).train(_regression_data()).training_profile
    expected = {name[len("ydf."):] for name in TRAIN_SPANS}
    assert expected <= set(p), p
    assert all(p[k] >= 0 for k in expected)
    # A nested span lies inside its parent; `device_loop.compile`, the
    # program's build, lies inside `.dispatch` and is not summed twice.
    nested = sum(p[k] for k in expected if k.startswith("device_loop.")
                 and k != "device_loop.compile")
    assert nested <= p["device_loop"]
    assert 0 <= p["device_loop.compile"] <= p["device_loop.dispatch"]
    children = sum(p[k] for k in expected if k.startswith("ingest_bin."))
    assert children <= p["ingest_bin"]
    top_level = sum(p[k] for k in expected if "." not in k)
    assert p["other"] == pytest.approx(p["total"] - top_level, abs=1e-9)
    assert p["other"] < 0.1 * p["total"]


def test_stage_timer_other_counts_top_level_stages_only():
    import time

    from ydf_tpu.utils.profiling import StageTimer

    timer = StageTimer()
    with timer.stage("a"):
        with timer.stage("a.b"):
            time.sleep(0.02)
        with timer.stage("a.b"):  # a name met twice adds up
            time.sleep(0.01)
    p = timer.finish()
    assert 0.03 <= p["a.b"] <= p["a"] <= p["total"]
    # were the nested span subtracted too, `other` would be clamped to 0
    # and this difference would be about 0.03 s
    assert p["other"] == pytest.approx(p["total"] - p["a"], abs=1e-9)
    assert p["device_loop.compile"] == 0.0


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    (plane,) = [pl for pl in ProfileData.from_file(str(path)).planes
                if pl.name == "/host:CPU"]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for line in plane.lines for ev in line.events]


def test_trace_holds_host_spans_inside_the_callers_annotation(tmp_path):
    """Whoever traces gets the program's spans as TraceMe events on the
    profiler's clock, inside their own annotation."""
    import jax

    data = _regression_data()
    _regression_learner().train(data)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("callers_job"):
        _regression_learner().train(data)
    jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (job,) = [e for e in events if e[0] == "callers_job"]
    for name in ("ydf.split", "ydf.device_loop", "ydf.device_loop.wait"):
        (ev,) = [e for e in events if e[0] == name]
        assert job[1] <= ev[1] and ev[2] <= job[2], (name, ev, job)
    (loop,) = [e for e in events if e[0] == "ydf.device_loop"]
    (wait,) = [e for e in events if e[0] == "ydf.device_loop.wait"]
    assert loop[1] <= wait[1] and wait[2] <= loop[2]


def test_chunk_program_names_every_device_scope(monkeypatch):
    """The compiled HLO of the boosting chunk carries every
    `DEVICE_SCOPES` name in its operations' metadata (the path a trace's
    `tf_op` shows). The XLA routing chain is what a TPU runs; the CPU's
    fused native kernels replace the gradient and routing operations."""
    import jax.numpy as jnp

    from ydf_tpu.ops import device_loop
    from ydf_tpu.utils.profiling import DEVICE_SCOPES

    monkeypatch.setenv("YDF_TPU_ROUTE_IMPL", "xla")
    texts = []
    run_chunk = device_loop.run_chunk

    def spy(run, carry, start, chunk_len, *args, timer=None, **kwargs):
        if not texts:
            texts.append(device_loop.chunk_fn(run).lower(
                carry, jnp.asarray(start), chunk_len, *args, **kwargs
            ).compile().as_text())
        return run_chunk(run, carry, start, chunk_len, *args, timer=timer,
                         **kwargs)

    monkeypatch.setattr(device_loop, "run_chunk", spy)
    _regression_learner(
        num_trees=6, early_stopping_num_trees_look_ahead=3
    ).train(_regression_data())
    (text,) = texts
    # (`ydf.rank` is a ranking loss's: tests/test_ranking_reference.py)
    assert [s for s in DEVICE_SCOPES if f"/{s}/" not in text] == ["ydf.rank"]


@pytest.mark.parametrize("backend", ["cpu", "as_tpu"])
@pytest.mark.parametrize("driver", ["single_scan", "early_stop"])
def test_route_lookups_are_counted_with_the_program(driver, backend,
                                                    monkeypatch):
    """`device_loop.route_select` and `.route_gather`: the per-row
    look-ups of the traced boosting program that are compare-and-select
    passes and that are gathers (ops/lookup.py), kept with the compiled
    program, so a job that reuses it says the same. A CPU on the XLA
    chain gathers them all; traced as a TPU would, a table of 7 nodes
    and a row of 4 bins select them all."""
    from ydf_tpu.ops import lookup

    monkeypatch.setenv("YDF_TPU_ROUTE_IMPL", "xla")
    if backend == "as_tpu":
        monkeypatch.setattr(lookup, "is_tpu_backend", lambda: True)
    kw = {"single_scan": {"early_stopping": "NONE"},
          "early_stop": {"num_trees": 6,
                         "early_stopping_num_trees_look_ahead": 3}}[driver]
    # A seed of its own a case: the boosting function is cached by its
    # configuration, which does not know how its look-ups were traced.
    kw["random_seed"] = 2900 + 2 * (backend == "cpu") + (driver == "early_stop")
    data = _regression_data()
    first = _regression_learner(**kw).train(data).training_profile
    second = _regression_learner(**kw).train(data).training_profile
    # A tree of depth 3: a level looks up split, column, bin, cut, left
    # and right, and all but the last the rank and the histogram slot;
    # then the leaf's value. Validation rows (without early stopping
    # there are none): three depths of feature, bin, cut, left, right
    # and is_leaf, then the leaf's value.
    lookups = (3 * 6 + 2 * 2 + 1) + (3 * 6 + 1) * (driver == "early_stop")
    want = (lookups, 0) if backend == "as_tpu" else (0, lookups)
    for profile in (first, second):
        assert (profile["device_loop.route_select"],
                profile["device_loop.route_gather"]) == want
    assert second["device_loop.compile"] == 0.0


def test_h2d_bytes_counts_what_a_job_sends():
    from ydf_tpu.ops import device_loop

    n, features = 2000, 4
    device_loop.reset_stats()
    _regression_learner().train(_regression_data(n, features=features))
    # uint8 bins of every row (training and validation), f32 label and
    # weight of every row
    assert device_loop.stats_snapshot()["h2d_bytes"] == n * features + 8 * n
    device_loop.reset_stats()
    assert device_loop.stats_snapshot()["h2d_bytes"] == 0


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_seconds_by_scope_on_an_unscoped_trace(tmp_path):
    """benchmark/data/small_trace.xplane.pb was recorded on a v5e before
    the scan had scopes: every operation is `unscoped`, and the own
    times sum to the union the benchmark's reduction gives."""
    import sys

    from ydf_tpu.utils.profiling import device_seconds_by_scope

    pb = os.path.join(_ROOT, "benchmark", "data", "small_trace.xplane.pb")
    shutil.copy(pb, tmp_path)
    by_scope = device_seconds_by_scope(str(tmp_path))
    assert list(by_scope) == ["unscoped"]
    assert by_scope["unscoped"]["events"] == 1544
    assert by_scope["unscoped"]["flops"] > 0 < by_scope["unscoped"]["bytes"]
    sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
    try:
        from harness import xplane
    finally:
        sys.path.pop(0)
    events = xplane.device_op_events(xplane.load(pb))
    busy_s = xplane.busy_seconds(events, 0.0, float("inf"))
    # ProfileData rounds picoseconds to nanoseconds
    assert by_scope["unscoped"]["seconds"] == pytest.approx(busy_s, rel=1e-4)


def test_device_seconds_by_scope_on_a_scoped_trace(tmp_path):
    """tests/data/scoped_trace.xplane.pb: on a v5e, one warm job of
    `GradientBoostedTreesLearner(task=REGRESSION, num_trees=2,
    max_depth=2)` on 200,000 x 28 normal features, traced inside a
    `job` annotation with `python_tracer_level = 0`, compiled from this
    tree with the persistent compile cache off (PR 26). The
    `/host:metadata` plane (the HLO protos, 845 KB, which nothing here
    reads) was cut out of the file to bring it under 2 MB."""
    from ydf_tpu.utils.profiling import (
        DEVICE_SCOPES, TRAIN_SPANS, device_seconds_by_scope,
    )

    pb = os.path.join(_ROOT, "tests", "data", "scoped_trace.xplane.pb")
    assert os.path.getsize(pb) < 2_000_000
    trace_dir = tmp_path / "scoped"
    (trace_dir / "plugins").mkdir(parents=True)
    shutil.copy(pb, trace_dir / "plugins")
    by_scope = device_seconds_by_scope(str(trace_dir))
    assert next(iter(by_scope)) == "ydf.hist"
    assert set(by_scope) == set(DEVICE_SCOPES) - {"ydf.rank"} | {"unscoped"}
    busy = sum(row["seconds"] for row in by_scope.values())
    assert by_scope["unscoped"]["seconds"] < 0.01 * busy
    assert by_scope["ydf.hist"]["bytes"] > 0 < by_scope["ydf.hist"]["flops"]
    # the host's spans are in the same file, inside the caller's `job`
    events = _host_events(trace_dir)
    (job,) = [e for e in events if e[0] == "job"]
    spans = {e[0]: e for e in events if e[0] in TRAIN_SPANS}
    assert {"ydf.split", "ydf.device_loop.h2d", "ydf.device_loop.wait",
            "ydf.finalize"} <= set(spans)
    assert all(job[1] <= e[1] and e[2] <= job[2] for e in spans.values())


def test_model_trained_under_a_profiler_is_bit_identical(tmp_path):
    import jax

    data = _regression_data()
    plain = _regression_learner().train(data)
    with jax.profiler.trace(str(tmp_path)):
        traced = _regression_learner().train(data)
    ours, theirs = plain.forest.to_numpy(), traced.forest.to_numpy()
    assert ours.keys() == theirs.keys()
    for field in ours:
        assert ours[field].tobytes() == theirs[field].tobytes(), field
    assert plain.training_logs["train_loss"] == traced.training_logs["train_loss"]


def test_training_profile_rf():
    m = ydf.RandomForestLearner(
        label="y", num_trees=5, max_depth=4,
    ).train(_data())
    p = m.training_profile
    assert p is not None and "device_loop" in p


def test_profiler_trace_dir(tmp_path, monkeypatch):
    """YDF_TPU_PROFILE_DIR wraps train() in jax.profiler.trace."""
    monkeypatch.setenv("YDF_TPU_PROFILE_DIR", str(tmp_path))
    ydf.GradientBoostedTreesLearner(
        label="y", num_trees=2, max_depth=2, validation_ratio=0.0,
        early_stopping="NONE",
    ).train(_data(500))
    trace_root = tmp_path / "gbt_train"
    assert trace_root.exists()
    # xprof writes something under plugins/profile/<run>/
    found = list(trace_root.rglob("*"))
    assert found, "empty trace dir"


def test_benchmark_engines():
    data = _data(3000)
    m = ydf.GradientBoostedTreesLearner(
        label="y", num_trees=10, max_depth=4, validation_ratio=0.0,
        early_stopping="NONE",
    ).train(data)
    b = m.benchmark(data, num_runs=2, engines=True)
    assert b["ns_per_example"] > 0
    eng = b["engines_ns_per_example"]
    assert "routed" in eng and eng["routed"] > 0
    # Depth-4, 10-tree binary GBT is inside the QuickScorer envelope.
    assert "quickscorer" in eng and eng["quickscorer"] > 0
    assert "binned_quickscorer" in eng and eng["binned_quickscorer"] > 0


def test_benchmark_engines_multiclass_skips_quickscorer():
    rng = np.random.RandomState(3)
    n = 1500
    x = rng.normal(size=n)
    y = np.digitize(x, [-0.5, 0.5]).astype(np.int64)
    data = {"x": x, "z": rng.normal(size=n), "y": y}
    m = ydf.GradientBoostedTreesLearner(
        label="y", num_trees=6, max_depth=3, validation_ratio=0.0,
        early_stopping="NONE",
    ).train(data)
    b = m.benchmark(data, num_runs=1, engines=True)
    eng = b["engines_ns_per_example"]
    assert "routed" in eng
    assert "quickscorer" not in eng
