"""Reduction of a JAX profiler trace (`*.xplane.pb`) to the numbers the
benchmark reports: the seconds in which an operation ran on the device,
the device operations that took most time, and the idle gaps named by
what the host was doing. Read with `jax.profiler.ProfileData` and
nothing else. Checked on the small recorded trace in `benchmark/data/`
by `tests/test_xplane.py`.

A device plane is named `/device:TPU:<n>`. Its line `XLA Ops` holds one
event per executed operation; an operation that contains others (a
`while`, a fusion's call) spans them, so busy time is the UNION of the
events' intervals and an operation's own time is its span less what its
children cover. The host's `TraceMe` spans (this benchmark's
`TraceAnnotation`s among them) are on the plane `/host:CPU`, on the
same clock.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_trace(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane.pb under {directory}, "
                           f"found {len(files)}")
    return files[0]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_op_events(profile):
    """{device plane name: [(start_ns, end_ns, name), ...] sorted by
    start, longer first on ties} from each device's `XLA Ops` line."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                events.append((start, start + float(ev.duration_ns), ev.name))
        events.sort(key=lambda e: (e[0], -e[1]))
        out[plane.name] = events
    return out


def host_spans(profile, names):
    """[(start_ns, end_ns, name)] of the host's TraceMe spans whose name
    is in `names`, sorted by start."""
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    start = float(ev.start_ns)
                    spans.append((start, start + float(ev.duration_ns),
                                  ev.name))
    spans.sort()
    return spans


def union(intervals):
    """Merged, sorted, disjoint [(start, end)] of `intervals`."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(events_by_device, lo_ns, hi_ns):
    """Seconds inside [lo, hi] in which an operation ran, averaged over
    the devices given."""
    if not events_by_device:
        return 0.0
    total = 0.0
    for events in events_by_device.values():
        merged = clip(union((s, e) for s, e, _ in events), lo_ns, hi_ns)
        total += sum(e - s for s, e in merged)
    return total / len(events_by_device) / 1e9


def self_seconds(events, lo_ns, hi_ns):
    """{name: seconds} of each operation's own time inside [lo, hi]:
    its span less the spans of the operations nested in it."""
    own = defaultdict(float)
    stack = []  # (end, name, covered_by_children, start)

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, covered, start = stack.pop()
            own[name] += max(end - start - covered, 0.0)
            if stack:
                stack[-1][2] += end - start

    for start, end, name in events:
        s, e = max(start, lo_ns), min(end, hi_ns)
        if e <= s:
            continue
        close(s)
        if stack:
            e = min(e, stack[-1][0])
        stack.append([e, name, 0.0, s])
    close(float("inf"))
    return {name: ns / 1e9 for name, ns in own.items()}


def idle_gaps(events, lo_ns, hi_ns):
    """[(start_ns, end_ns)] inside [lo, hi] in which nothing ran."""
    gaps = []
    cursor = lo_ns
    for start, end in clip(union((s, e) for s, e, _ in events), lo_ns, hi_ns):
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if hi_ns > cursor:
        gaps.append((cursor, hi_ns))
    return gaps


def name_gaps(gaps, spans, busy):
    """{label: seconds}: each idle gap is cut at the host spans' edges
    and each piece named `<span>.<before|between|after>_device`, by
    whether the device had not yet run, ran before and after, or had
    finished running inside that span; `outside_spans` where no span
    covers it."""
    named = defaultdict(float)
    for g0, g1 in gaps:
        cuts = sorted({g0, g1, *(t for s, e, _ in spans for t in (s, e)
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [sp for sp in spans if sp[0] <= mid < sp[1]]
            if not inside:
                named["outside_spans"] += b - a
                continue
            s0, s1, name = max(inside, key=lambda sp: sp[0])  # innermost
            ran_before = any(s < mid and e > s0 for s, e in busy if s < mid)
            ran_after = any(e > mid and s < s1 for s, e in busy if e > mid)
            where = ("between" if ran_before and ran_after else
                     "before" if ran_after else "after" if ran_before
                     else "no")
            named[f"{name}.{where}_device"] += b - a
    return {k: v / 1e9 for k, v in named.items()}


def reduce(path: str, window_span: str, span_names):
    """The traced window is the union of the host spans named
    `window_span` (first start to last end). Returns busy_s, window_s,
    device_ops and idle_gaps (top 10 each, seconds), or None where the
    trace holds no such span or no device operation."""
    profile = load(path)
    spans = host_spans(profile, set(span_names) | {window_span})
    win = [sp for sp in spans if sp[2] == window_span]
    by_device = device_op_events(profile)
    if not win or not any(by_device.values()):
        return None
    lo, hi = min(s for s, _, _ in win), max(e for _, e, _ in win)
    busy_s = busy_seconds(by_device, lo, hi)
    # Operations and gaps are named on the device that was busiest.
    first = max(by_device, key=lambda d: busy_seconds({d: by_device[d]}, lo, hi))
    events = by_device[first]
    ops = sorted(self_seconds(events, lo, hi).items(), key=lambda kv: -kv[1])
    busy = clip(union((s, e) for s, e, _ in events), lo, hi)
    others = [sp for sp in spans if sp[2] != window_span or len(win) > 1]
    gaps = name_gaps(idle_gaps(events, lo, hi), others or spans, busy)
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, s] for n, s in ops[:10]],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        "n_device_events": sum(len(v) for v in by_device.values()),
        "devices": len(by_device),
    }


def describe(path: str):
    """What a trace holds, for a first look by hand."""
    profile = load(path)
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            n, total, names = 0, 0.0, defaultdict(float)
            t0, t1 = float("inf"), 0.0
            for ev in line.events:
                n += 1
                total += ev.duration_ns
                names[ev.name] += ev.duration_ns
                t0 = min(t0, ev.start_ns)
                t1 = max(t1, ev.start_ns + ev.duration_ns)
            if n:
                top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
                lines.append({"line": line.name, "events": n,
                              "sum_s": total / 1e9, "span_s": (t1 - t0) / 1e9,
                              "top": [[k[:80], v / 1e9] for k, v in top]})
        if lines:
            planes.append({"plane": plane.name, "lines": lines[:12]})
    return {"planes": planes}
