"""bench.py record protocol: one parseable JSON record per run, every
record names the device it ran on (platform, device_kind, device count),
a machine with no accelerator is an error unless --cpu is passed, and a
failure exits non-zero instead of degrading to a record."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _last_json(stdout: str):
    lines = [
        ln for ln in stdout.strip().splitlines()
        if ln.strip().startswith("{")
    ]
    assert lines, f"no JSON line in: {stdout[-500:]!r}"
    return json.loads(lines[-1])


@pytest.mark.slow
def test_small_cpu_run_emits_parseable_record():
    out = subprocess.run(
        [sys.executable, BENCH, "--cpu", "--small"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
    )
    assert out.returncode == 0
    rec = _last_json(out.stdout)
    assert rec["metric"] == "gbt_train_rows_x_trees_per_sec"
    assert rec["value"] > 0
    assert "vs_baseline" in rec
    # The record names the device it measured.
    assert rec["platform"] == "cpu" and rec["backend"] == "cpu"
    assert rec["device_kind"] and rec["device_count"] >= 1
    # The ingestion/binning split rides every headline record so the
    # trajectory tracks the fused-binning target (round 6).
    assert "ingest_s" in rec and rec["ingest_s"] >= 0
    assert "bin_s" in rec and rec["bin_s"] >= 0
    # Histogram timing, two ways (PR 3): hist_s is the real in-loop op
    # time (native kernel counter / profiler trace), hist_attrib_s the
    # historical same-shape attribution, hist_direct_s the
    # pre-subtraction comparison that makes the halved contraction
    # visible. hist_quant names the active quantization mode so
    # quantized and exact trajectories can't be conflated.
    assert "hist_s" in rec and rec["hist_s"] >= 0
    assert rec.get("hist_s_source") in (
        "native_kernel_counter", "profiler_trace"
    )
    assert "hist_attrib_s" in rec and rec["hist_attrib_s"] >= 0
    assert "hist_direct_s" in rec and rec["hist_direct_s"] >= 0
    assert rec["hist_quant"] in ("f32", "bf16x2", "int8")
    # Routing attribution (PR 4): every headline record names the active
    # routing impl and resolved native thread caps; with the native path
    # on, route_s/update_s carry the in-kernel wall time next to hist_s.
    assert rec["route_impl"] in ("xla", "native")
    assert rec["route_threads"] >= 1
    assert rec["hist_threads"] >= 1
    # Serving percentiles: every headline record carries p50/p99
    # per-example inference latency from the telemetry latency
    # histogram next to the historical best-of-runs floor — the
    # serving-regression guard ROADMAP item 1 reads.
    assert rec["infer_ns_per_example"] > 0
    assert rec["infer_p50_ns"] > 0
    assert rec["infer_p99_ns"] >= rec["infer_p50_ns"]
    # Serving-regression guard (this round): the --small shape
    # (20k rows, 5 trees) has a recorded CPU floor (640.5 ns); the
    # record must carry the comparison, and the
    # measured p50 must hold the floor (1.5x margin absorbs box
    # contention — the recorded runs show the native engine well
    # under it).
    assert rec["infer_p50_floor_ns"] == 640.5
    assert rec["infer_p50_within_floor"] in (True, False)
    assert rec["infer_p50_ns"] <= rec["infer_p50_floor_ns"] * 1.5
    # Serving bench family (this round): which engine actually served
    # the headline measurement, rows/sec at the best batch size, and
    # per-call p50/p99 at every bench batch size — per compatible
    # engine in infer_engines, headline engine flattened on the record.
    assert isinstance(rec["serve_engine"], str) and rec["serve_engine"]
    assert rec["infer_qps"] > 0
    for field in ("infer_batch_p50_ns", "infer_batch_p99_ns"):
        assert set(rec[field]) == {"1", "16", "256", "4096"}
        assert all(v > 0 for v in rec[field].values())
    assert rec["serve_engine"] in rec["infer_engines"]
    for eng, per in rec["infer_engines"].items():
        for b, row in per.items():
            assert row["p99_ns"] >= row["p50_ns"] > 0
            assert row["qps"] > 0
    # On this CPU image the native engine must actually be the one
    # serving — anything else means the build silently degraded.
    assert rec["serve_engine"] == "NativeBatch"
    # Serving-under-load family (this round): closed-loop sustained
    # capacity through the bounded request batcher, then an open-loop
    # Poisson run at 70% of it with latency measured from SCHEDULED
    # arrival (coordinated-omission-safe) — queue age and shed rate
    # ride the headline record (docs/serving.md "Serving under load").
    assert rec.get("serve_load_family_error") is None, rec.get(
        "serve_load_family_error"
    )
    assert rec["serve_sustained_qps"] > 0
    assert rec["serve_load_p99_ns"] >= rec["serve_load_p50_ns"] > 0
    assert rec["serve_queue_age_p99_ns"] >= 0
    assert 0.0 <= rec["serve_shed_rate"] <= 1.0
    assert rec["serve_load"]["closed"]["load_mode"] == "closed"
    assert rec["serve_load"]["open"]["load_mode"] == "open"
    assert rec["serve_load"]["open"]["schedule_fingerprint"]
    # Serving-fleet family (this round): a 2-replica pool over the
    # worker substrate, closed-loop capacity through the router with a
    # mid-run versioned hot-swap — replica count (a bench-diff pairing
    # shape field), sustained QPS, the p99 of the run spanning the
    # swap, and the failover count (0 on a healthy in-process fleet).
    # Zero errors/sheds attributable to the flip.
    assert rec.get("fleet_family_error") is None, rec.get(
        "fleet_family_error"
    )
    assert rec["fleet_replicas"] == 2
    assert rec["fleet_sustained_qps"] > 0
    assert rec["fleet_swap_p99_ns"] > 0
    assert rec["fleet_failover_count"] == 0
    assert rec["fleet"]["errors"] == 0 and rec["fleet"]["shed"] == 0
    assert rec["fleet"]["swap"]["to"] == "bench_v2"
    assert rec["fleet"]["active_version"] == "bench_v2"
    # Transport overhaul (this round): the whole fleet run — deploys
    # included — pays at most one TCP connect per replica on the
    # persistent pool, nearly every request reuses a pooled
    # connection, the wire splits into pickled header vs zero-copy
    # array payload bytes, and the per-RPC predict round-trip p50
    # rides the record.
    assert 1 <= rec["rpc_connects"] <= rec["fleet_replicas"]
    assert rec["rpc_conn_reuse_rate"] > 0.9
    assert rec["rpc_header_bytes"] > 0
    assert rec["rpc_payload_bytes"] > 0
    assert rec["fleet_predict_rtt_p50_ns"] > 0
    # Elastic membership (this round): without the env the fleet run is
    # STATIC and says so — fleet_elastic is a bench-diff pairing shape
    # field, so the default record must carry the 0 explicitly and none
    # of the elastic headline fields.
    assert rec["fleet_elastic"] == 0
    assert "fleet_join_to_serving_ns" not in rec
    assert "fleet_drain_ns" not in rec
    assert "fleet_scale_events" not in rec
    # Resource observability (round 15): pool utilization per stage —
    # busy / (lanes x pooled wall) from native/thread_pool.h's stats
    # block — and the memory headline fields. On this image the native
    # hist kernel and the NativeBatch serving engine both run, so the
    # hist and serve stages must report; utilization is a ratio
    # (clock-granularity slack allowed above 1.0).
    assert rec["pool_size"] >= 1
    util = rec["pool_utilization"]
    assert "hist" in util and "serve" in util, util
    for stage, u in util.items():
        assert 0.0 < u <= 1.2, (stage, u)
    assert rec["train_peak_rss_bytes"] > 0
    assert rec["serve_bank_bytes"] > 0
    assert rec["infer_peak_rss_delta_bytes"] >= 0
    if rec["route_impl"] == "native":
        assert "route_s" in rec and rec["route_s"] >= 0
        assert "update_s" in rec and rec["update_s"] >= 0
        assert rec.get("route_s_source") == "native_kernel_counter"
        # Fully-fused histogram+routing (native hist impl, the default
        # on CPU): the joint row-walk time rides its own field.
        if "fused_s" in rec:
            assert rec["fused_s"] >= 0


@pytest.mark.slow
def test_small_cpu_run_with_distributed_family():
    """YDF_TPU_BENCH_DIST_WORKERS=2 adds the distributed-training
    family to the headline record: worker count, steady train wall,
    reduce bytes (total + per-layer), per-verb RPC p50s from the
    exchange's latency histograms, and the recovery count (0 on a
    healthy in-process fleet)."""
    env = dict(os.environ, YDF_TPU_BENCH_DIST_WORKERS="2")
    out = subprocess.run(
        [sys.executable, BENCH, "--cpu", "--small", "--no-baseline"],
        capture_output=True, text=True, timeout=900, cwd=REPO, env=env,
    )
    assert out.returncode == 0
    rec = _last_json(out.stdout)
    assert rec.get("dist_family_error") is None, rec.get(
        "dist_family_error"
    )
    assert rec["dist_workers"] == 2
    assert rec["dist_train_s"] > 0
    assert rec["dist_reduce_bytes"] > 0
    assert rec["dist_reduce_bytes_per_layer"] > 0
    p50 = rec["dist_rpc_p50_ns"]
    assert p50.get("build_histograms", 0) > 0
    assert p50.get("load_cache_shard", 0) > 0
    assert rec["dist_recoveries"] == 0
    # Preemption-safe round: the bench train runs with a working_dir,
    # so the manager's tree-boundary snapshot wall (at least the final
    # boundary's durable write) rides the headline record.
    assert rec["dist_snapshot_s"] > 0
    # Fleet-total resident shard/state bytes the workers reported at
    # shard load (round 15's distributed memory headline).
    assert rec["dist_shard_bytes"] > 0
    # Per-layer wall attribution (this round): compute + net + wait
    # partition the summed layer wall, so distributed slowness is
    # attributable to compute, the network, or a straggler from the
    # headline record alone.
    assert rec["dist_layer_wall_s"] > 0
    for f in ("dist_compute_s", "dist_net_s", "dist_wait_s"):
        assert rec[f] >= 0
    total = (
        rec["dist_compute_s"] + rec["dist_net_s"] + rec["dist_wait_s"]
    )
    assert abs(total - rec["dist_layer_wall_s"]) <= 0.02 + 0.01 * rec[
        "dist_layer_wall_s"
    ]
    # Transport overhaul (this round): the steady-state distributed
    # run connects once per worker (persistent pool), reuses for every
    # per-layer RPC, and accounts its wire bytes split into pickled
    # header vs zero-copy array segments.
    assert 1 <= rec["dist_rpc_connects"] <= rec["dist_workers"]
    assert rec["dist_rpc_conn_reuse_rate"] > 0.8
    assert rec["dist_rpc_header_bytes"] > 0
    assert rec["dist_rpc_payload_bytes"] > 0


def test_bench_dist_workers_env_validation():
    """A malformed YDF_TPU_BENCH_DIST_WORKERS lands as a recorded
    family error, never a crashed bench (artifact protocol)."""
    mod = _load_bench()
    rec = {}
    os.environ["YDF_TPU_BENCH_DIST_WORKERS"] = "banana"
    try:
        mod.measure_distributed_family(1000, 2, 3, 4, rec)
    finally:
        del os.environ["YDF_TPU_BENCH_DIST_WORKERS"]
    assert "must be an integer >= 2" in rec["dist_family_error"]
    rec2 = {}
    mod.measure_distributed_family(1000, 2, 3, 4, rec2)  # unset: no-op
    assert rec2 == {}


@pytest.mark.slow
def test_small_cpu_run_with_cache_build_family():
    """YDF_TPU_BENCH_CACHE_WORKERS=2 adds the cache-build family to
    the headline record: single-machine build wall + peak RSS, the
    sketch-mode pass-1 wire footprint, and the 2-worker distributed
    build wall with the fleet-max per-worker transient from the
    build's commit record."""
    env = dict(os.environ, YDF_TPU_BENCH_CACHE_WORKERS="2")
    out = subprocess.run(
        [sys.executable, BENCH, "--cpu", "--small", "--no-baseline"],
        capture_output=True, text=True, timeout=900, cwd=REPO, env=env,
    )
    assert out.returncode == 0
    rec = _last_json(out.stdout)
    assert rec.get("cache_build_family_error") is None, rec.get(
        "cache_build_family_error"
    )
    assert rec["cache_build_s"] > 0
    assert rec["cache_build_peak_rss_bytes"] > 0
    assert rec["sketch_bytes"] > 0
    # Sketch-quality acceptance reads: measured rank error within the
    # certified per-instance bound, split drift vs exact boundaries
    # reported (both 0.0 when the stream fits the sketch exactly).
    assert rec["sketch_rank_error"] >= 0
    assert rec["sketch_rank_error_bound"] >= 0
    assert rec["sketch_rank_error_within_bound"] is True
    assert 0 <= rec["sketch_split_max_drift"] < 0.05
    assert rec["dist_cache_build_s"] > 0
    assert rec["dist_cache_build_workers"] == 2
    assert rec["dist_cache_peak_worker_build_bytes"] > 0
    # The sketch partial must be dramatically smaller than the peak
    # the build itself needs — that asymmetry is the point of
    # sketch-mode boundary inference.
    assert rec["sketch_bytes"] < rec["cache_build_peak_rss_bytes"]


def test_bench_cache_workers_env_validation():
    """A malformed YDF_TPU_BENCH_CACHE_WORKERS lands as a recorded
    family error, never a crashed bench (artifact protocol)."""
    mod = _load_bench()
    rec = {}
    os.environ["YDF_TPU_BENCH_CACHE_WORKERS"] = "one"
    try:
        mod.measure_cache_build_family(1000, 4, rec)
    finally:
        del os.environ["YDF_TPU_BENCH_CACHE_WORKERS"]
    assert "must be an integer >= 2" in rec["cache_build_family_error"]
    rec2 = {}
    mod.measure_cache_build_family(1000, 4, rec2)  # unset: no-op
    assert rec2 == {}


def test_bench_fleet_elastic_env_validation():
    """A malformed YDF_TPU_BENCH_FLEET_ELASTIC lands as a recorded
    family error, never a crashed bench (artifact protocol)."""
    mod = _load_bench()
    rec = {}
    os.environ["YDF_TPU_BENCH_FLEET_ELASTIC"] = "yes"
    try:
        mod.measure_fleet_family(None, None, 1000, rec)
    finally:
        del os.environ["YDF_TPU_BENCH_FLEET_ELASTIC"]
    assert "must be 0 or 1" in rec["fleet_family_error"]


def test_bench_fleet_family_elastic_mode():
    """YDF_TPU_BENCH_FLEET_ELASTIC=1 (in-process, tier-1): the fleet
    closed loop spans a live add_replica of a freshly spawned replica
    and a remove_replica drain of it, and the record carries the
    elastic headline fields — spawn->admitted wall, drain wall, the
    scale-event count — with fleet_elastic=1 joining the bench-diff
    pairing shape. Zero errors: the scale ops are invisible to
    callers."""
    import numpy as np

    import ydf_tpu as ydf
    from ydf_tpu.config import Task

    mod = _load_bench()
    rng = np.random.RandomState(0)
    rows = 1500
    data = {
        f"f{i}": rng.normal(size=rows).astype(np.float32)
        for i in range(5)
    }
    data["label"] = (data["f0"] + data["f1"] > 0).astype(np.int64)
    model = ydf.GradientBoostedTreesLearner(
        label="label", task=Task.CLASSIFICATION, num_trees=3,
        max_depth=3, validation_ratio=0.0, early_stopping="NONE",
    ).train(data)
    rec = {}
    os.environ["YDF_TPU_BENCH_FLEET_ELASTIC"] = "1"
    try:
        mod.measure_fleet_family(model, data, rows, rec)
    finally:
        del os.environ["YDF_TPU_BENCH_FLEET_ELASTIC"]
    assert rec.get("fleet_family_error") is None, rec.get(
        "fleet_family_error"
    )
    assert rec["fleet_elastic"] == 1
    assert rec["fleet_join_to_serving_ns"] > 0
    assert rec["fleet_drain_ns"] > 0
    # Exactly one join and one drain — an autoscaler-shaped run that
    # flapped would inflate this.
    assert rec["fleet_scale_events"] == 2
    el = rec["fleet"]["elastic"]
    assert el["join"]["joined"] is True
    assert el["drain"]["removed"] is True
    assert el["joins"] == 1 and el["drains"] == 1
    # The scale ops were invisible to the load: zero errors, and the
    # fleet ends on its founding replicas (the joiner drained away).
    assert rec["fleet"]["errors"] == 0
    assert rec["fleet_replicas"] == 2


def _load_bench():
    """Imports bench.py as a module (its top level only defines)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_accelerator_without_cpu_flag_is_an_error():
    """Without --cpu the default backend is used, and finding no
    accelerator exits non-zero with no record on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, BENCH, "--small"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert out.returncode != 0
    assert "no accelerator" in out.stderr and "'cpu'" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


def test_failure_propagates_instead_of_exiting_zero(monkeypatch):
    """A failing measurement is a failing run: main() lets the exception
    out (non-zero exit, traceback) and prints no record."""
    import ydf_tpu.config

    mod = _load_bench()

    def boom(*a, **k):
        raise RuntimeError("measurement failed")

    monkeypatch.setattr(mod, "run_bench", boom)
    monkeypatch.setattr(ydf_tpu.config, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--cpu", "--small"])
    with pytest.raises(RuntimeError, match="measurement failed"):
        mod.main()


def test_every_record_names_the_device(capsys):
    """emit() is the one way a record leaves bench.py, and it stamps the
    device as JAX reports it."""
    import jax

    mod = _load_bench()
    mod.emit({"metric": "m", "value": 1.0})
    rec = _last_json(capsys.readouterr().out)
    d = jax.devices()
    assert rec["platform"] == d[0].platform == "cpu"
    assert rec["device_kind"] == d[0].device_kind
    assert rec["device_count"] == len(d)
