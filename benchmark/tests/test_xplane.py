"""The trace reduction on a small trace recorded on the chip (a v5e, two
one-tree jobs of 20,000 rows with a 50 ms pause after each, PR 25's
sizing probe), on intervals worked by hand, and on that trace with a
second, less busy device plane beside the first (a four-chip cell's)."""

import os

import pytest

from harness import xplane
from tests.two_planes import with_second_device

TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data", "small_trace.xplane.pb")


def test_union_self_time_and_gaps_by_hand():
    ev = [(0, 100, "while"), (0, 40, "a"), (50, 90, "b"), (60, 70, "c"),
          (200, 300, "d")]
    assert xplane.union([(0, 40), (30, 60), (100, 110)]) == [(0, 60), (100, 110)]
    assert xplane.busy_seconds({"dev": ev}, 0, 400) == pytest.approx(200e-9)
    assert xplane.busy_seconds({"dev": ev}, 20, 250) == pytest.approx(130e-9)
    own = xplane.self_seconds(ev, 0, 400)
    assert own == pytest.approx(
        {"a": 40e-9, "b": 30e-9, "c": 10e-9, "while": 20e-9, "d": 100e-9})
    assert sum(own.values()) == pytest.approx(200e-9)
    gaps = xplane.idle_gaps(ev, 0, 400)
    assert gaps == [(100, 200), (300, 400)]
    named = xplane.name_gaps(gaps, [(0, 350, "job"), (350, 400, "between_jobs")],
                             [(0, 100), (200, 300)])
    assert named == pytest.approx({"job.between_device": 100e-9,
                                   "job.after_device": 50e-9,
                                   "between_jobs.no_device": 50e-9})


def test_recorded_trace():
    profile = xplane.load(TRACE)
    spans = xplane.host_spans(profile, {"job", "between_jobs"})
    assert [s[2] for s in spans] == ["job", "between_jobs"] * 2
    events = xplane.device_op_events(profile)
    assert list(events) == ["/device:TPU:0"] and len(events["/device:TPU:0"]) == 1544
    r = xplane.reduce(TRACE, "job", ["job", "between_jobs"])
    # first `job` start to last `job` end, from the spans above
    assert r["window_s"] == pytest.approx((207627960 - 41616308) / 1e9)
    # the union of the 60 `XLA Modules` events is 5.927869 ms; the
    # operations inside them leave 54 us of it uncovered
    assert r["busy_s"] == pytest.approx(0.005873796, rel=1e-6)
    assert r["busy_s"] < 0.005927869
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"]
    assert r["device_ops"][0][0].startswith("%fusion.269 = f32[28,128,3]")
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    # the pause between the jobs is the 50 ms the probe slept
    assert gaps["between_jobs.no_device"] == pytest.approx(0.0509, abs=1e-4)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_a_trace_without_the_span_gives_nothing():
    assert xplane.reduce(TRACE, "no_such_span", ["job"]) is None


def test_two_device_planes_mean_busy_and_name_the_busier(tmp_path):
    one = xplane.reduce(TRACE, "job", ["job", "between_jobs"])
    with open(TRACE, "rb") as f:
        both = with_second_device(f.read())
    path = tmp_path / "two.xplane.pb"
    path.write_bytes(both)
    events = xplane.device_op_events(xplane.load(str(path)))
    assert list(events) == ["/device:TPU:0", "/device:TPU:1"]
    assert [len(v) for v in events.values()] == [1544, 772]
    lo = min(s for s, _, _ in events["/device:TPU:0"])
    hi = max(e for _, e, _ in events["/device:TPU:0"])
    alone = [xplane.busy_seconds({d: ev}, lo, hi) for d, ev in events.items()]
    assert alone[1] < alone[0]
    two = xplane.reduce(str(path), "job", ["job", "between_jobs"])
    assert two["devices"] == 2 and two["n_device_events"] == 1544 + 772
    assert two["window_s"] == one["window_s"]
    assert one["busy_s"] == pytest.approx(alone[0], rel=1e-9)
    assert two["busy_s"] == pytest.approx((alone[0] + alone[1]) / 2, rel=1e-9)
    # operations and gaps are the busier plane's: those of the one-chip trace
    assert two["device_ops"] == one["device_ops"]
    assert two["idle_gaps"] == one["idle_gaps"]
