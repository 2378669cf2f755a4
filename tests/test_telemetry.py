"""Telemetry subsystem (utils/telemetry.py): histogram bucket math vs
numpy percentiles, span nesting + chrome-tracing JSONL round-trip, eager
env-grammar validation, the zero-allocation disabled fast path, and the
chaos invariant — a crashing exporter never perturbs training output."""

import gc
import json
import os
import tracemalloc

import numpy as np
import pytest

from ydf_tpu.utils import failpoints, log, telemetry
from ydf_tpu.utils.telemetry import LatencyHistogram


def _small_data(n=1500, seed=3):
    rng = np.random.RandomState(seed)
    data = {f"f{i}": rng.normal(size=n).astype(np.float32) for i in range(5)}
    data["label"] = (
        data["f0"] - 0.5 * data["f1"] + rng.normal(size=n) > 0
    ).astype(np.int64)
    return data


def _load_trace(td):
    evs = []
    for name in os.listdir(td):
        if name.startswith("trace-") and name.endswith(".jsonl"):
            with open(os.path.join(td, name)) as f:
                for line in f:
                    evs.append(json.loads(line))
    return evs


def _contains(parent, child):
    return (
        parent["ts"] <= child["ts"]
        and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    )


# --------------------------------------------------------------------- #
# Histogram bucket math
# --------------------------------------------------------------------- #


def test_histogram_percentiles_vs_numpy():
    rng = np.random.RandomState(7)
    vals = np.exp(rng.normal(loc=13.0, scale=1.5, size=20_000)).astype(
        np.int64
    )  # latency-shaped: lognormal around ~0.4 ms
    h = LatencyHistogram()
    for v in vals:
        h.observe_ns(int(v))
    assert h.count == len(vals)
    assert h.min == int(vals.min()) and h.max == int(vals.max())
    for p in (50, 90, 99):
        est = h.percentile_ns(p)
        ref = float(np.percentile(vals, p))
        # Log2 buckets with 8 linear sub-buckets: worst-case relative
        # resolution 12.5 %.
        assert abs(est - ref) / ref < 0.15, (p, est, ref)


def test_histogram_bucket_bounds_cover_value():
    rng = np.random.RandomState(11)
    for v in np.concatenate(
        [rng.randint(1, 1 << 40, size=200), [1, 2, 7, 8, 9, 1023, 1024]]
    ):
        i = LatencyHistogram.bucket_index(int(v))
        lo, hi = LatencyHistogram.bucket_bounds(i)
        assert lo <= v < hi or (v < 1), (v, i, lo, hi)


def test_histogram_edge_cases():
    h = LatencyHistogram()
    assert h.percentile_ns(50) is None  # empty
    h.observe_ns(0)
    h.observe_ns(5)
    assert h.count == 2 and h.min == 0 and h.max == 5
    assert 0 <= h.percentile_ns(50) <= 5
    assert h.percentile_ns(99) <= 5  # clamped to exact max
    h2 = LatencyHistogram()
    h2.observe_ns(1 << 70)  # beyond the top octave: clamped, not a crash
    assert h2.count == 1


def test_pow2_bucket():
    assert telemetry.pow2_bucket(1) == 1
    assert telemetry.pow2_bucket(2) == 2
    assert telemetry.pow2_bucket(1000) == 1024
    assert telemetry.pow2_bucket(1024) == 1024
    assert telemetry.pow2_bucket(1025) == 2048


# --------------------------------------------------------------------- #
# Registry / exporter
# --------------------------------------------------------------------- #


def test_counters_gauges_prometheus_text():
    with telemetry.active():
        telemetry.counter("ydf_test_total", kind="a").inc()
        telemetry.counter("ydf_test_total", kind="a").inc(2)
        telemetry.gauge("ydf_test_gauge").set(3.5)
        telemetry.histogram("ydf_test_latency_ns", engine="X").observe_ns(
            1000
        )
        txt = telemetry.metrics_text()
        assert 'ydf_test_total{kind="a"} 3' in txt
        assert "ydf_test_gauge 3.5" in txt
        # Histograms export REAL cumulative Prometheus series from the
        # log2 buckets (aggregatable by an actual scraper), not
        # percentile gauges: _bucket at octave bounds, +Inf, _sum,
        # _count.
        assert "# TYPE ydf_test_latency_ns histogram" in txt
        assert 'ydf_test_latency_ns_bucket{engine="X",le="1024"} 1' in txt
        assert 'ydf_test_latency_ns_bucket{engine="X",le="+Inf"} 1' in txt
        assert 'ydf_test_latency_ns_sum{engine="X"} 1000' in txt
        assert 'ydf_test_latency_ns_count{engine="X"} 1' in txt
        snap = telemetry.snapshot()
        assert snap["counters"]['ydf_test_total{kind="a"}'] == 3
        # The native-kernel wall counters ride every dump as registered
        # gauges (profiling.native_kernel_metrics default collector).
        assert "ydf_native_hist_kernel_seconds" in snap["gauges"]
        assert "ydf_native_route_kernel_seconds" in snap["gauges"]


def test_histogram_bucket_series_are_cumulative():
    """The _bucket series is monotone, its +Inf sample equals _count,
    and bucket boundaries are value-independent octave bounds — the
    property a scraper needs to aggregate across workers."""
    import re

    with telemetry.active():
        h = telemetry.histogram("ydf_test_latency_ns")
        for v in (3, 100, 100, 5_000, 70_000, 70_001):
            h.observe_ns(v)
        txt = telemetry.metrics_text()
    buckets = re.findall(
        r'ydf_test_latency_ns_bucket\{le="([^"]+)"\} (\d+)', txt
    )
    assert buckets[-1][0] == "+Inf" and int(buckets[-1][1]) == 6
    finite = [(float(le), int(c)) for le, c in buckets[:-1]]
    # Monotone cumulative counts over increasing power-of-two bounds.
    assert all(
        b[0] > a[0] and b[1] >= a[1] for a, b in zip(finite, finite[1:])
    )
    assert all(le == float(int(le)) and (int(le) & (int(le) - 1)) == 0
               for le, _ in finite)
    # Spot-check: everything <= 128 is 3 observations (3, 100, 100).
    by_le = dict(finite)
    assert by_le[128.0] == 3


def test_span_nesting_and_jsonl_roundtrip(tmp_path):
    td = str(tmp_path / "t")
    with telemetry.active(td):
        with telemetry.span("outer") as sp:
            sp.set(k="v")
            with telemetry.span("mid"):
                with telemetry.span("inner"):
                    pass
        telemetry.flush()
    evs = _load_trace(td)
    by = {e["name"]: e for e in evs}
    assert set(by) == {"outer", "mid", "inner"}
    for e in evs:
        assert e["ph"] == "X" and e["dur"] > 0 and e["pid"] == os.getpid()
    assert _contains(by["outer"], by["mid"])
    assert _contains(by["mid"], by["inner"])
    assert by["outer"]["args"] == {"k": "v"}
    assert by["outer"]["tid"] == by["inner"]["tid"]


def test_emit_span_and_events_buffer():
    with telemetry.active():
        telemetry.emit_span("synth", 1000, 500, {"attributed": True})
        evs = telemetry.events()
        assert len(evs) == 1
        assert evs[0]["name"] == "synth" and evs[0]["args"]["attributed"]


def test_active_restores_previous_state(tmp_path):
    was_enabled, was_dir = telemetry.ENABLED, telemetry.EXPORT_DIR
    with telemetry.active(str(tmp_path / "x")):
        assert telemetry.ENABLED
        telemetry.counter("ydf_scoped_total").inc()
        assert "ydf_scoped_total" in telemetry.metrics_text()
    assert telemetry.ENABLED == was_enabled
    assert telemetry.EXPORT_DIR == was_dir
    if not was_enabled:
        assert "ydf_scoped_total" not in telemetry.metrics_text()


# --------------------------------------------------------------------- #
# Env grammar (eager) + disabled fast path
# --------------------------------------------------------------------- #


def test_env_grammar_rejects_bad_flag():
    with pytest.raises(ValueError, match="YDF_TPU_TELEMETRY"):
        telemetry._parse_env("verbose", None)
    for ok in ("", "0", "1", "on", "off", None):
        telemetry._parse_env(ok, None)


def test_env_grammar_rejects_uncreatable_dir(tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("x")
    with pytest.raises(ValueError, match="YDF_TPU_TELEMETRY_DIR"):
        telemetry._parse_env(None, str(blocker / "sub"))


def test_log_level_grammar_eager():
    with pytest.raises(ValueError, match="YDF_TPU_LOG"):
        log._parse_level("verbose")
    assert log._parse_level(None) == "info"
    assert log._parse_level("QUIET") == "quiet"


@pytest.mark.skipif(
    telemetry.ENABLED, reason="telemetry armed via env in this run"
)
def test_disabled_span_is_singleton_noop():
    assert telemetry.span("a") is telemetry.span("b")
    with telemetry.span("x") as sp:
        sp.set(ignored=1)  # must be a no-op, never raise
    assert telemetry.events() == []


@pytest.mark.skipif(
    telemetry.ENABLED, reason="telemetry armed via env in this run"
)
def test_disabled_span_fast_path_zero_allocations():
    from itertools import repeat

    def loop():
        for _ in repeat(None, 2000):
            with telemetry.span("hot"):
                pass

    loop()  # warm caches
    tracemalloc.start()
    loop()  # warm under tracing (tracemalloc internals settle)
    gc.collect()
    base = tracemalloc.get_traced_memory()[0]
    loop()
    gc.collect()
    grown = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    # Zero allocations PER CALL: 2000 calls must not grow traced memory
    # by even one object per call (one span object would be ≥ 2000×48
    # bytes); a few stray bytes of interpreter bookkeeping are not the
    # span path.
    assert grown < 1000, (
        f"disabled span path allocated {grown} bytes over 2000 calls"
    )


# --------------------------------------------------------------------- #
# Acceptance: train + predict produce the nested trace and the metrics
# dump; training_logs carries one record per iteration.
# --------------------------------------------------------------------- #


def test_train_predict_trace_and_metrics_acceptance(tmp_path):
    import ydf_tpu as ydf

    data = _small_data()
    td = str(tmp_path / "telemetry")
    with telemetry.active(td):
        model = ydf.GradientBoostedTreesLearner(
            label="label", num_trees=6, max_depth=3
        ).train(data)
        model.predict(data)
        telemetry.flush()

    evs = _load_trace(td)
    trains = [e for e in evs if e["name"] == "train"]
    chunks = [e for e in evs if e["name"] == "train.chunk"]
    assert len(trains) == 1 and chunks
    trained = model.training_logs["num_trees_trained"]
    assert sum(c["args"]["iterations"] for c in chunks) >= trained
    # The measured children of `train`: one span per boundary of
    # train() (utils/profiling.TRAIN_SPANS), the same names and
    # intervals the profiler's trace gets; nothing is attributed.
    # A dict is ingested inside the job: the Dataset's own spans
    # (DATASET_SPANS) lie in `ingest_bin.dataspec`.
    spans = {e["name"]: e for e in evs if e["name"].startswith("ydf.")}
    from ydf_tpu.utils.profiling import DATASET_SPANS, TRAIN_SPANS

    assert {"ydf.ingest_bin", "ydf.ingest_bin.dataspec",
            "ydf.ingest_bin.binner_fit", "ydf.ingest_bin.transform",
            "ydf.ingest_bin.targets", "ydf.split", "ydf.device_loop",
            "ydf.device_loop.dispatch", "ydf.device_loop.wait",
            "ydf.finalize", *DATASET_SPANS} <= set(spans)
    assert set(spans) <= set(TRAIN_SPANS) | set(DATASET_SPANS)
    assert not any("attributed" in (e.get("args") or {}) for e in evs)
    # Nesting by containment: every chunk and span in the train span,
    # every dotted span in its parent.
    for c in chunks:
        assert _contains(trains[0], c)
    for name, e in spans.items():
        assert _contains(trains[0], e), name
        parent = {"ydf.dataset.from_data": "ydf.ingest_bin.dataspec"}.get(
            name, name.rsplit(".", 1)[0])
        if name.count(".") > 1:
            assert _contains(spans[parent], e), name
    serves = [e for e in evs if e["name"] == "serve.predict"]
    kernels = [e for e in evs if e["name"] == "serve.kernel"]
    assert serves and kernels
    assert any(_contains(s, k) for s in serves for k in kernels)

    # Metrics dump: the serving latency histogram is present.
    proms = [f for f in os.listdir(td) if f.endswith(".prom")]
    assert proms
    txt = open(os.path.join(td, proms[0])).read()
    assert "ydf_serve_latency_ns_count" in txt
    assert "ydf_train_iterations_total" in txt

    # training_logs: one YDF-style record per boosting iteration.
    its = model.training_logs["iterations"]
    assert len(its) == trained
    assert [r["iteration"] for r in its] == list(range(1, trained + 1))
    assert its[0]["train_loss"] == pytest.approx(
        model.training_logs["train_loss"][0]
    )
    assert all(r["seconds"] >= 0 for r in its)
    assert all(r["valid_loss"] is not None for r in its)


def test_iteration_records_without_validation():
    import ydf_tpu as ydf

    data = _small_data()
    m = ydf.GradientBoostedTreesLearner(
        label="label", num_trees=4, max_depth=3,
        validation_ratio=0.0, early_stopping="NONE",
    ).train(data)
    its = m.training_logs["iterations"]
    assert len(its) == 4
    assert all(r["valid_loss"] is None for r in its)
    assert sum(r["seconds"] for r in its) > 0


def test_training_logs_iterations_survive_save_load(tmp_path):
    import ydf_tpu as ydf

    data = _small_data()
    m = ydf.GradientBoostedTreesLearner(
        label="label", num_trees=3, max_depth=3
    ).train(data)
    m.save(str(tmp_path / "m"))
    m2 = ydf.load_model(str(tmp_path / "m"))
    assert m2.training_logs["iterations"] == m.training_logs["iterations"]


# --------------------------------------------------------------------- #
# Flush robustness + chaos invariant
# --------------------------------------------------------------------- #


def test_flush_never_raises_on_injected_fault(tmp_path):
    td = str(tmp_path / "t")
    with telemetry.active(td):
        with telemetry.span("ev"):
            pass
        with failpoints.active("telemetry.flush=error"):
            telemetry.flush()  # must swallow the injected crash
            assert "telemetry.flush" in failpoints.fired_sites()
        snap = telemetry.snapshot()
        assert snap["counters"]["ydf_telemetry_flush_errors_total"] == 1
        # The drained spans were restored; the next flush exports them.
        telemetry.flush()
        assert [e["name"] for e in _load_trace(td)] == ["ev"]


@pytest.mark.chaos
def test_telemetry_on_off_crashing_is_bit_identical(tmp_path):
    """Acceptance: a failpoint in telemetry flush never perturbs the
    training output — the model is bit-identical with telemetry off,
    on, and crashing in the exporter."""
    import ydf_tpu as ydf

    data = _small_data()

    def train():
        return ydf.GradientBoostedTreesLearner(
            label="label", num_trees=5, max_depth=3
        ).train(data)

    base = train()  # telemetry off
    with telemetry.active(str(tmp_path / "on")):
        m_on = train()
    with telemetry.active(str(tmp_path / "crash")):
        with failpoints.active("telemetry.flush=error"):
            m_crash = train()  # train() flushes → fault fires, swallowed
            assert "telemetry.flush" in failpoints.fired_sites()
    p = base.predict(data)
    np.testing.assert_array_equal(p, m_on.predict(data))
    np.testing.assert_array_equal(p, m_crash.predict(data))


# --------------------------------------------------------------------- #
# benchmark() percentile surface (the bench guard's source)
# --------------------------------------------------------------------- #


def test_benchmark_reports_percentiles():
    import ydf_tpu as ydf

    data = _small_data(n=800)
    m = ydf.GradientBoostedTreesLearner(
        label="label", num_trees=3, max_depth=3
    ).train(data)
    r = m.benchmark(data, num_runs=5)
    assert r["p50_ns_per_example"] > 0
    assert r["p99_ns_per_example"] >= r["p50_ns_per_example"]
