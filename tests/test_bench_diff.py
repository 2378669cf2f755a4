"""scripts/bench_diff.py — the cross-round bench regression sentinel.

Tier-1 (pure python, no jax): the sentinel must (a) run over two rounds
in the driver's wrapper format and structurally kill the 640 ns shape
confound of rounds 4 and 5 (the small-shape record unpaired, same-shape
serving NOT a regression), and (b) flag a synthetically injected
per-stage regression past its noise threshold.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_diff.py")

# The headline figures of rounds 4 and 5 (CPU, one core), in the driver's
# wrapper format: {"tail": <the last stdout lines>}. Round 4 printed a
# (20k rows, 5 trees) record before its full record; round 5 printed a
# projection line before its full record.
_METRIC = "gbt_train_rows_x_trees_per_sec_per_chip"
_ROUND4_LINES = [
    "# a stderr line the loader must skip",
    {"metric": _METRIC, "value": 368735.9, "unit": "rows*trees/s",
     "backend": "cpu", "rows": 20000, "trees": 5, "depth": 6,
     "train_wall_s": 0.27, "train_wall_incl_compile_s": 8.15,
     "vs_baseline": 0.06, "infer_ns_per_example": 640.5},
    {"metric": _METRIC, "value": 599451.1, "unit": "rows*trees/s",
     "backend": "cpu", "rows": 500000, "trees": 20, "depth": 6,
     "train_wall_s": 16.68, "train_wall_incl_compile_s": 24.04,
     "vs_baseline": 0.202, "infer_ns_per_example": 1451.2},
]
_ROUND5_LINES = [
    {"metric": _METRIC + "_PROJECTED", "value": 29082813.7,
     "backend": "analytic_projection", "rows": 500000, "depth": 6},
    {"metric": _METRIC, "value": 1466165.9, "unit": "rows*trees/s",
     "backend": "cpu", "rows": 500000, "trees": 20, "depth": 6,
     "train_wall_s": 6.82, "train_wall_incl_compile_s": 13.9,
     "vs_baseline": 0.494, "infer_ns_per_example": 1380.7},
]


def _wrapper(path, lines):
    tail = "\n".join(
        ln if isinstance(ln, str) else json.dumps(ln) for ln in lines
    )
    path.write_text(json.dumps({"n": 1, "rc": 0, "tail": tail}))
    return str(path)


@pytest.fixture()
def rounds(tmp_path):
    return (_wrapper(tmp_path / "r04.json", _ROUND4_LINES),
            _wrapper(tmp_path / "r05.json", _ROUND5_LINES))


def _load():
    spec = importlib.util.spec_from_file_location("bench_diff", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bd():
    return _load()


# ---------------------------------------------------------------------- #
# Loading
# ---------------------------------------------------------------------- #


def test_loads_driver_wrapper_and_drops_projections(bd, rounds):
    r04, r05 = rounds
    recs = bd.load_records(r04)
    # r04's tail holds the small record + the full record.
    assert len(recs) == 2
    shapes = {bd.shape_key(r) for r in recs}
    assert len(shapes) == 2  # (20k, 5) and (500k, 20)
    # Projections must never survive loading.
    recs = bd.load_records(r05)
    assert len(recs) == 1
    assert all("PROJECTED" not in r["metric"] for r in recs)


def test_loads_jsonl_and_single_record(bd, tmp_path):
    rec = {"metric": "m", "backend": "cpu", "rows": 10, "trees": 2,
           "depth": 3, "value": 1.0, "train_wall_s": 2.0}
    p1 = tmp_path / "one.json"
    p1.write_text(json.dumps(rec))
    assert len(bd.load_records(str(p1))) == 1
    p2 = tmp_path / "many.jsonl"
    p2.write_text(json.dumps(rec) + "\n" + json.dumps(rec) + "\n")
    assert len(bd.load_records(str(p2))) == 2


def test_error_records_dropped(bd, tmp_path):
    bad = {"metric": "m", "value": 0.0, "error": "backend down"}
    p = tmp_path / "err.jsonl"
    p.write_text(json.dumps(bad) + "\n")
    assert bd.load_records(str(p)) == []


# ---------------------------------------------------------------------- #
# The real r04 → r05 confound
# ---------------------------------------------------------------------- #


def test_r04_r05_pairs_by_shape_and_flags_no_false_regression(bd, rounds):
    """Run on the figures of rounds 4 and 5, the small shape must be
    UNPAIRED (never compared — the 640 ns confound class is dead
    structurally) and the same-shape serving fields must not be flagged
    as a regression (they improved 5%)."""
    doc = bd.diff(*rounds)
    assert doc["ok"], doc["regressions"]
    assert doc["regressions"] == []
    # Exactly one shared shape: the (500000, 20) full record.
    assert len(doc["pairs"]) == 1
    shape = doc["pairs"][0]["shape"]
    assert (shape["rows"], shape["trees"]) == (500_000, 20)
    # The 640.5 ns small-shape record exists only in r04: unpaired.
    assert any("rows=20000" in s for s in doc["unpaired_a"])
    # Same-shape serving: 1451.2 -> 1380.7 is an improvement-direction
    # move inside the noise band — anything but "regression".
    infer = doc["pairs"][0]["fields"]["infer_ns_per_example"]
    assert infer["a"] == pytest.approx(1451.2)
    assert infer["b"] == pytest.approx(1380.7)
    assert infer["verdict"] != "regression"
    # And the train-side fields register the real 2.4x improvement.
    assert (
        doc["pairs"][0]["fields"]["train_wall_s"]["verdict"]
        == "improvement"
    )


# ---------------------------------------------------------------------- #
# Synthetic injected regression
# ---------------------------------------------------------------------- #


def _full_record():
    """A headline-shaped record with the per-stage + resource fields."""
    return {
        "metric": "gbt_train_rows_x_trees_per_sec_per_chip",
        "backend": "cpu", "rows": 500_000, "trees": 20, "depth": 6,
        "value": 1_000_000.0, "train_wall_s": 10.0, "ingest_s": 1.0,
        "bin_s": 0.5, "hist_s": 4.0, "route_s": 1.0, "update_s": 0.5,
        "fused_s": 3.0, "infer_ns_per_example": 1000.0,
        "infer_p50_ns": 950.0, "infer_p99_ns": 1200.0,
        "infer_qps": 2_000_000.0,
        "pool_utilization": {"hist": 0.9, "serve": 0.8},
        "pool_size": 8,
        "train_peak_rss_bytes": 2 << 30,
        "serve_bank_bytes": 40 << 20,
        "infer_peak_rss_delta_bytes": 0,
        "infer_batch_p50_ns": {"1": 15000.0, "256": 200000.0},
        "serve_sustained_qps": 18_000.0,
        "serve_load_p50_ns": 400_000.0,
        "serve_load_p99_ns": 1_500_000.0,
        "serve_queue_age_p99_ns": 900_000.0,
        "serve_shed_rate": 0.0,
    }


def test_injected_per_stage_regression_is_flagged(bd, tmp_path):
    a, b = _full_record(), _full_record()
    b["hist_s"] = a["hist_s"] * 1.5          # +50% in-loop histogram
    b["value"] = a["value"] * 0.8            # throughput drop rides along
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(a) + "\n")
    pb.write_text(json.dumps(b) + "\n")
    doc = bd.diff(str(pa), str(pb))
    assert not doc["ok"]
    flagged = " ".join(doc["regressions"])
    assert "hist_s" in flagged and "value" in flagged
    assert doc["pairs"][0]["fields"]["hist_s"]["verdict"] == "regression"


def test_noise_band_suppresses_small_moves(bd, tmp_path):
    a, b = _full_record(), _full_record()
    b["hist_s"] = a["hist_s"] * 1.04   # +4% < the 15% band: unchanged
    b["train_wall_s"] = a["train_wall_s"] + 0.1  # under the 0.2s floor
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(a) + "\n")
    pb.write_text(json.dumps(b) + "\n")
    doc = bd.diff(str(pa), str(pb))
    assert doc["ok"], doc["regressions"]
    assert doc["pairs"][0]["fields"]["hist_s"]["verdict"] == "unchanged"


def test_resource_fields_diff_directionally(bd, tmp_path):
    """The new utilization/memory fields carry direction: utilization
    DROP and memory GROWTH are the regressions."""
    a, b = _full_record(), _full_record()
    b["pool_utilization"] = {"hist": 0.45, "serve": 0.8}  # halved
    b["serve_bank_bytes"] = a["serve_bank_bytes"] * 2     # doubled
    b["infer_peak_rss_delta_bytes"] = 64 << 20            # 0 -> 64MB
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(a) + "\n")
    pb.write_text(json.dumps(b) + "\n")
    doc = bd.diff(str(pa), str(pb))
    fields = doc["pairs"][0]["fields"]
    assert fields["pool_utilization.hist"]["verdict"] == "regression"
    assert fields["pool_utilization.serve"]["verdict"] == "unchanged"
    assert fields["serve_bank_bytes"]["verdict"] == "regression"
    assert fields["infer_peak_rss_delta_bytes"]["verdict"] == "regression"
    # ...and the improvement direction is symmetric.
    doc2 = bd.diff(str(pb), str(pa))
    assert (
        doc2["pairs"][0]["fields"]["pool_utilization.hist"]["verdict"]
        == "improvement"
    )


def test_serving_load_fields_diff_directionally(bd, tmp_path):
    """The serving-under-load family carries direction: capacity DROP,
    tail GROWTH and shed-rate GROWTH are the regressions."""
    a, b = _full_record(), _full_record()
    b["serve_sustained_qps"] = a["serve_sustained_qps"] * 0.5
    b["serve_load_p99_ns"] = a["serve_load_p99_ns"] * 2.0
    b["serve_shed_rate"] = 0.25
    b["serve_load_p50_ns"] = a["serve_load_p50_ns"] * 1.05  # in-band
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(a) + "\n")
    pb.write_text(json.dumps(b) + "\n")
    doc = bd.diff(str(pa), str(pb))
    fields = doc["pairs"][0]["fields"]
    assert fields["serve_sustained_qps"]["verdict"] == "regression"
    assert fields["serve_load_p99_ns"]["verdict"] == "regression"
    assert fields["serve_shed_rate"]["verdict"] == "regression"
    assert fields["serve_load_p50_ns"]["verdict"] == "unchanged"
    # ...and the improvement direction is symmetric.
    doc2 = bd.diff(str(pb), str(pa))
    f2 = doc2["pairs"][0]["fields"]
    assert f2["serve_sustained_qps"]["verdict"] == "improvement"
    assert f2["serve_shed_rate"]["verdict"] == "improvement"


def _load_record(mode, qps, p99):
    """A scripts/bench_serve_load.py artifact record (load_mode joins
    the pairing shape)."""
    return {
        "metric": "serve_load_qps", "backend": "cpu", "rows": 20_000,
        "trees": 5, "depth": 6, "load_mode": mode, "value": qps,
        "achieved_qps": qps, "latency_p99_ns": p99, "shed": 0,
    }


def test_load_mode_joins_pairing_shape(bd, tmp_path):
    """A closed-loop capacity record must NEVER pair with an open-loop
    latency record (their latency fields measure different things —
    service time vs scheduled-arrival tail): same rounds pair per
    mode, and a round holding only one mode leaves the other unpaired."""
    a = [_load_record("closed", 18_000.0, 600_000.0),
         _load_record("open", 12_600.0, 1_500_000.0)]
    b = [_load_record("closed", 19_000.0, 610_000.0),
         _load_record("open", 12_800.0, 1_450_000.0)]
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text("\n".join(json.dumps(r) for r in a) + "\n")
    pb.write_text("\n".join(json.dumps(r) for r in b) + "\n")
    doc = bd.diff(str(pa), str(pb))
    assert len(doc["pairs"]) == 2
    modes = {p["shape"]["load_mode"] for p in doc["pairs"]}
    assert modes == {"closed", "open"}
    assert doc["ok"], doc["regressions"]
    # Drop the open record from b: it must go unpaired, not pair with
    # b's closed record.
    pb.write_text(json.dumps(b[0]) + "\n")
    doc2 = bd.diff(str(pa), str(pb))
    assert len(doc2["pairs"]) == 1
    assert doc2["pairs"][0]["shape"]["load_mode"] == "closed"
    assert any("load_mode=open" in s for s in doc2["unpaired_a"])
    # An injected open-loop tail regression is flagged on the pair.
    b2 = [b[0], dict(b[1], latency_p99_ns=4_000_000.0)]
    pb.write_text("\n".join(json.dumps(r) for r in b2) + "\n")
    doc3 = bd.diff(str(pa), str(pb))
    flagged = " ".join(doc3["regressions"])
    assert "latency_p99_ns" in flagged and "load_mode=open" in flagged


def test_different_shapes_never_compare(bd, tmp_path):
    a = _full_record()
    b = _full_record()
    b["trees"] = 5
    b["infer_ns_per_example"] = 640.5  # the confound, synthesized
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(a) + "\n")
    pb.write_text(json.dumps(b) + "\n")
    doc = bd.diff(str(pa), str(pb))
    assert doc["pairs"] == []
    assert doc["ok"]
    assert len(doc["unpaired_a"]) == 1 and len(doc["unpaired_b"]) == 1


# ---------------------------------------------------------------------- #
# CLI + report
# ---------------------------------------------------------------------- #


def test_cli_markdown_json_and_exit_codes(bd, tmp_path):
    a, b = _full_record(), _full_record()
    b["hist_s"] = a["hist_s"] * 2
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(a) + "\n")
    pb.write_text(json.dumps(b) + "\n")
    md_out = tmp_path / "diff.md"
    json_out = tmp_path / "diff.json"
    out = subprocess.run(
        [sys.executable, SCRIPT, str(pa), str(pb),
         "--md", str(md_out), "--json", str(json_out),
         "--fail-on-regression"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 1  # regression + --fail-on-regression
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert not summary["ok"]
    doc = json.loads(json_out.read_text())
    assert doc["pairs"][0]["fields"]["hist_s"]["verdict"] == "regression"
    md = md_out.read_text()
    assert "REGRESSION" in md and "hist_s" in md
    # Without --fail-on-regression the exit code stays 0 (report tool).
    out2 = subprocess.run(
        [sys.executable, SCRIPT, str(pa), str(pb)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out2.returncode == 0


def test_markdown_mentions_unpaired_confound_warning(bd, rounds):
    doc = bd.diff(*rounds)
    md = bd.to_markdown(doc)
    assert "NOT compared" in md
    assert "640" in md  # the lesson is named in the report itself


def _fleet_record(replicas, qps, swap_p99, failovers):
    """A headline record carrying the serving-fleet family
    (fleet_replicas joins the pairing shape)."""
    return {
        "metric": "gbt_train_rows_x_trees_per_sec_per_chip",
        "backend": "cpu", "rows": 20_000, "trees": 5, "depth": 6,
        "fleet_replicas": replicas, "value": 1.0,
        "fleet_sustained_qps": qps, "fleet_swap_p99_ns": swap_p99,
        "fleet_failover_count": failovers,
    }


def test_fleet_replicas_joins_pairing_shape_and_fields_directional(
    bd, tmp_path
):
    """fleet_replicas is a SHAPE field: a 2-replica round never pairs
    with a 4-replica one (per-replica QPS scales with the pool — the
    same confound class load_mode guards against). The fleet fields
    are direction-aware: capacity down and swap-spanning p99 /
    failover count up are regressions."""
    a = [_fleet_record(2, 50_000.0, 2_000_000.0, 0)]
    b = [_fleet_record(4, 90_000.0, 2_100_000.0, 0)]
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(a[0]) + "\n")
    pb.write_text(json.dumps(b[0]) + "\n")
    doc = bd.diff(str(pa), str(pb))
    assert doc["pairs"] == []
    assert any("fleet_replicas=2" in s for s in doc["unpaired_a"])
    assert any("fleet_replicas=4" in s for s in doc["unpaired_b"])
    # Same replica count pairs; regression directions honored.
    worse = _fleet_record(2, 30_000.0, 9_000_000.0, 3)
    pb.write_text(json.dumps(worse) + "\n")
    doc2 = bd.diff(str(pa), str(pb))
    assert len(doc2["pairs"]) == 1
    flagged = " ".join(doc2["regressions"])
    assert "fleet_sustained_qps" in flagged
    assert "fleet_swap_p99_ns" in flagged
    assert "fleet_failover_count" in flagged
    # Improvements flow the other way and stay ok.
    better = _fleet_record(2, 70_000.0, 1_200_000.0, 0)
    pb.write_text(json.dumps(better) + "\n")
    doc3 = bd.diff(str(pa), str(pb))
    assert doc3["ok"], doc3["regressions"]
    imp = " ".join(doc3["improvements"])
    assert "fleet_sustained_qps" in imp and "fleet_swap_p99_ns" in imp


def test_fleet_elastic_joins_pairing_shape_and_fields_directional(
    bd, tmp_path
):
    """fleet_elastic is a DEFAULT-0 SHAPE field: an elastic fleet
    record (the run spans live add_replica/remove_replica) never pairs
    with a static one — and a historical record WITHOUT the field is
    static (0), so pre-elastic artifacts keep pairing with new static
    rounds. The elastic fields are direction-aware: slower joins/
    drains and more scale events are regressions."""
    static = _fleet_record(2, 50_000.0, 2_000_000.0, 0)
    elastic = dict(
        _fleet_record(2, 48_000.0, 2_200_000.0, 0),
        fleet_elastic=1,
        fleet_join_to_serving_ns=30_000_000.0,
        fleet_drain_ns=3_000_000.0,
        fleet_scale_events=2,
    )
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    pa.write_text(json.dumps(static) + "\n")
    pb.write_text(json.dumps(elastic) + "\n")
    doc = bd.diff(str(pa), str(pb))
    assert doc["pairs"] == []
    assert any("fleet_elastic=1" in s for s in doc["unpaired_b"])
    # Static records suppress the default from the label (historical
    # artifacts never carried the field).
    assert not any("fleet_elastic" in s for s in doc["unpaired_a"])
    # A record with the explicit 0 pairs with a field-less one.
    explicit0 = dict(static, fleet_elastic=0)
    pb.write_text(json.dumps(explicit0) + "\n")
    doc2 = bd.diff(str(pa), str(pb))
    assert len(doc2["pairs"]) == 1
    # Elastic-with-elastic pairs; regression directions honored.
    worse = dict(
        elastic,
        fleet_join_to_serving_ns=90_000_000.0,
        fleet_drain_ns=9_000_000.0,
        fleet_scale_events=6,
    )
    pa.write_text(json.dumps(elastic) + "\n")
    pb.write_text(json.dumps(worse) + "\n")
    doc3 = bd.diff(str(pa), str(pb))
    assert len(doc3["pairs"]) == 1
    flagged = " ".join(doc3["regressions"])
    assert "fleet_join_to_serving_ns" in flagged
    assert "fleet_drain_ns" in flagged
    assert "fleet_scale_events" in flagged
    # Improvements flow the other way and stay ok.
    faster = dict(
        elastic,
        fleet_join_to_serving_ns=10_000_000.0,
        fleet_drain_ns=1_000_000.0,
    )
    pb.write_text(json.dumps(faster) + "\n")
    doc4 = bd.diff(str(pa), str(pb))
    assert doc4["ok"], doc4["regressions"]
    imp = " ".join(doc4["improvements"])
    assert "fleet_join_to_serving_ns" in imp
    assert "fleet_drain_ns" in imp
