"""Per-stage training profiling + JAX profiler trace hooks.

Counterpart of the reference's per-stage `Monitoring` logs in the
distributed GBT manager (`distributed_gradient_boosted_trees.cc:832-836`
logs stage durations per iteration) and the usage/benchmark hooks
(`utils/usage.h`, `utils/benchmark/inference.h:36-52`). The TPU build's
training loop is one fused XLA program, so the honest decomposition is:

* **Host spans** — `StageTimer.stage(name)` times every boundary of
  train() (`TRAIN_SPANS`): the seconds land in
  ``model.training_profile[name]`` on every train(), and the same
  interval is a `jax.profiler.TraceAnnotation("ydf." + name)`, so
  whenever anyone traces (the benchmark's `--trace 1`, an operator's
  ``YDF_TPU_PROFILE_DIR``) the span sits on `/host:CPU` on the device's
  clock. With telemetry armed the same name and interval go to its
  JSONL.
* **Device scopes** — the boosting scan's body carries
  `jax.named_scope`s (`DEVICE_SCOPES`); they land in each device
  operation's `tf_op` metadata, and `device_seconds_by_scope(dir)`
  reduces a trace to seconds, flops and bytes per scope.
* **An xprof trace** — set ``YDF_TPU_PROFILE_DIR=/path`` and every
  train() runs inside ``jax.profiler.trace``.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import time
from collections import defaultdict
from typing import Dict, Iterator, Optional, Tuple

import jax

from ydf_tpu.utils import telemetry

# The host spans of train(), as the profiler's trace names them
# (`training_profile` keys are the same without the "ydf." prefix).
# Dots nest: `device_loop.*` lie inside `device_loop`.
TRAIN_SPANS = tuple(
    "ydf." + name
    for name in (
        "ingest_bin",
        "ingest_bin.dataspec",
        "ingest_bin.binner_fit",
        "ingest_bin.transform",
        "ingest_bin.targets",
        "rank_groups",
        "split",
        "device_loop",
        "device_loop.h2d",
        "device_loop.init",
        "device_loop.dispatch",
        "device_loop.compile",
        "device_loop.wait",
        "device_loop.fetch",
        "device_loop.merge",
        "finalize",
    )
)

# The spans of the ingest that makes a Dataset (`Dataset.from_data`
# where it infers a dataspec), kept with it (`Dataset.build_seconds`)
# and reported by every train() on it under the same keys.
DATASET_SPANS = ("ydf.dataset.from_data", "ydf.dataset.from_data.infer")

# The spans a job runs to make what later jobs on its Dataset reuse:
# the bins (BinnedDataset.create's memo), the query structure and the
# device arrays (Dataset.keep_device_inputs). The job that makes them
# keeps their seconds with the Dataset (`StageTimer.keep_build`), and
# every job on it reports them as `dataset.<span>`.
BUILD_SPANS = (
    "ingest_bin",
    "ingest_bin.dataspec",
    "ingest_bin.binner_fit",
    "ingest_bin.transform",
    "ingest_bin.targets",
    "rank_groups",
    "split",
    "device_loop.h2d",
)

# Spans a job may skip, reported at 0.0 where it does.
_SKIPPABLE = (
    "ingest_bin.dataspec",
    "ingest_bin.binner_fit",
    "ingest_bin.transform",
    "ingest_bin.targets",
    "device_loop.compile",
)

# The `jax.named_scope`s of the boosting scan's body, each a string
# literal where the work is (learners/gbt.py, ops/grower.py,
# ops/histogram.py).
DEVICE_SCOPES = (
    "ydf.grad",
    "ydf.rank",
    "ydf.hist",
    "ydf.sibling",
    "ydf.gain",
    "ydf.route",
    "ydf.leaf",
    "ydf.valid",
    "ydf.loss",
)

class StageTimer:
    """Named wall-time spans of one train() call: seconds per name for
    `training_profile`, each also a TraceAnnotation `ydf.<name>`."""

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}
        # The compiled boosting programs this call dispatched, each once:
        # program -> (seconds its build took, whenever that was; whether
        # the persistent cache answered; the routing look-ups its trace
        # made as selects; as gathers). ops/device_loop.dispatch fills it.
        self.programs: Dict[object, Tuple[float, bool, int, int]] = {}
        # Facts of this call that are no spans, under their profile keys
        # (`device_loop.inputs_cached`: 1.0 where the job's device
        # inputs were kept with its Dataset and nothing was sent).
        self.counts: Dict[str, float] = {}
        self._t0 = time.perf_counter_ns()

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        t = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation("ydf." + name):
                yield
        finally:
            dur = time.perf_counter_ns() - t
            self.seconds[name] = self.seconds.get(name, 0.0) + dur / 1e9
            telemetry.emit_span("ydf." + name, t, dur)  # no-op unless armed

    def keep_build(self, record: Dict[str, float], made: bool) -> None:
        """Reports `record`, the build record of the Dataset this call
        trained on (`Dataset.build_seconds`: `dataset.from_data` and
        the `dataset.<span>` of `BUILD_SPANS`), whichever call paid it.
        A call that `made` its inputs first replaces the record's
        `BUILD_SPANS` with its own seconds. The keys are counts, no
        spans: `other` and `total` do not see them."""
        if made:
            record.update(
                {"dataset." + k: self.seconds.get(k, 0.0) for k in BUILD_SPANS}
            )
        self.counts.update(record)

    def finish(self) -> Dict[str, float]:
        """The profile: every span's seconds (0.0 for one the call
        skipped: `device_loop.compile`, the build of a boosting program
        inside `device_loop.dispatch`, on a warm call; an `ingest_bin.*`
        step that an on-disk cache does not run), `total`, and `other` = total less the
        top-level (undotted) spans. A call that dispatched a boosting
        program also says what that program's build cost, whichever
        call paid it: `device_loop.program_build_s`, and
        `device_loop.program_from_cache` (1.0 where the persistent
        compile cache answered), and how that program's routing looks
        its small tables up: `device_loop.route_select` and
        `device_loop.route_gather`, the per-row look-ups of its trace
        that are compare-and-select passes and that are gathers
        (ops/lookup.py)."""
        out = {**self.seconds, **self.counts}
        for name in _SKIPPABLE:
            out.setdefault(name, 0.0)
        if self.programs:
            builds = list(self.programs.values())
            out["device_loop.program_build_s"] = sum(b[0] for b in builds)
            out["device_loop.program_from_cache"] = float(
                all(b[1] for b in builds)
            )
            out["device_loop.route_select"] = sum(b[2] for b in builds)
            out["device_loop.route_gather"] = sum(b[3] for b in builds)
        out["total"] = (time.perf_counter_ns() - self._t0) / 1e9
        top_level = sum(v for k, v in self.seconds.items() if "." not in k)
        out["other"] = max(out["total"] - top_level, 0.0)
        return out


@contextlib.contextmanager
def maybe_trace(label: str = "train") -> Iterator[None]:
    """jax.profiler trace around the whole of train() when
    YDF_TPU_PROFILE_DIR is set; no-op (and no overhead) otherwise."""
    trace_dir = os.environ.get("YDF_TPU_PROFILE_DIR")
    if not trace_dir:
        yield
        return
    path = os.path.join(trace_dir, label)
    os.makedirs(path, exist_ok=True)
    with jax.profiler.trace(path):
        yield


# Field numbers are interface facts of tsl/profiler/protobuf/
# xplane.proto: XSpace.planes=1; XPlane.name=2, .lines=3,
# .event_metadata=4, .stat_metadata=5 (map entries key=1/value=2);
# XLine.name=2, .timestamp_ns=3, .events=4; XEvent.metadata_id=1,
# .offset_ps=2, .duration_ps=3; XEventMetadata.name=2, .stats=5;
# XStatMetadata.name=2; XStat.metadata_id=1, .uint64_value=3,
# .int64_value=4, .str_value=5, .ref_value=7.


def _walk_xplanes(trace_dir: str):
    """Yields (plane name, line name, events) for every line of every
    `*.xplane.pb` under `trace_dir`, events as (start_ps, duration_ps,
    name, metadata stats {stat name: int or str}). Read with the
    schema-less protowire reader (utils/protowire.py): no tensorflow or
    tensorboard dependency, and, unlike jax.profiler.ProfileData, the
    event METADATA's stats (`tf_op`, `flops`, `bytes_accessed`)."""
    import pathlib

    from ydf_tpu.utils import protowire as pw

    def table(plane, field):
        out = {}
        for entry_b in plane.get(field, []):
            entry = pw.decode(bytes(entry_b))
            if entry.get(2):
                out[pw.get_int(entry, 1)] = pw.decode(bytes(entry[2][-1]))
        return out

    for path in sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb")):
        try:
            space = pw.decode(path.read_bytes())
        except Exception:
            continue  # partial/foreign file: skip, never fail the caller
        for plane_b in space.get(1, []):
            plane = pw.decode(bytes(plane_b))
            stat_names = {
                k: pw.get_str(md, 2) for k, md in table(plane, 5).items()
            }
            events_md = {}
            for mid, md in table(plane, 4).items():
                stats = {}
                for st_b in md.get(5, []):
                    st = pw.decode(bytes(st_b))
                    if 5 in st:
                        value = pw.get_str(st, 5)
                    elif 7 in st:
                        value = stat_names.get(pw.get_int(st, 7), "")
                    else:
                        value = pw.get_int(st, 3) or pw.get_int(st, 4)
                    stats[stat_names.get(pw.get_int(st, 1), "")] = value
                events_md[mid] = (pw.get_str(md, 2), stats)
            for line_b in plane.get(3, []):
                line = pw.decode(bytes(line_b))
                t0_ps = pw.get_int(line, 3) * 1000
                events = []
                for ev_b in line.get(4, []):
                    ev = pw.decode(bytes(ev_b))
                    name, stats = events_md.get(
                        pw.get_int(ev, 1), ("", {})
                    )
                    if name:
                        events.append(
                            (t0_ps + pw.get_int(ev, 2), pw.get_int(ev, 3),
                             name, stats)
                        )
                yield pw.get_str(plane, 2), pw.get_str(line, 2), events


def trace_event_seconds(
    trace_dir: str, substrings: Optional[tuple] = None
) -> Dict[str, float]:
    """Seconds per event name, summed over all planes and lines of a
    jax.profiler trace directory; `substrings` keeps the names that
    contain any of the fragments (None keeps everything). bench.py reads
    the CPU path's custom-call events with it."""
    out: Dict[str, float] = defaultdict(float)
    for _plane, _line, events in _walk_xplanes(trace_dir):
        for _start, dur_ps, name, _stats in events:
            if substrings is None or any(s in name for s in substrings):
                out[name] += dur_ps / 1e12
    return dict(out)


def device_op_times(trace_dir: str):
    """Yields (own seconds, name, metadata stats) for every event on
    the `XLA Ops` line of every `/device:TPU:*` plane under
    `trace_dir`. An event's OWN time is its span less its children's (a
    `while` spans its body), so the own times sum to the device's busy
    time."""
    for plane, line, events in _walk_xplanes(trace_dir):
        if not plane.startswith("/device:TPU:") or line != "XLA Ops":
            continue
        # Open events, outermost first: [end_ps, start_ps, ps covered by
        # children, name, stats]. The last pass closes what is open.
        stack = []
        events.sort(key=lambda e: (e[0], -e[1]))
        for start, dur, name, stats in itertools.chain(
            events, [(float("inf"), 0, "", {})]
        ):
            while stack and stack[-1][0] <= start:
                end, begin, covered, *event = stack.pop()
                yield (max(end - begin - covered, 0) / 1e12, *event)
                if stack:
                    stack[-1][2] += end - begin
            end = min(start + dur, stack[-1][0]) if stack else start + dur
            stack.append([end, start, 0, name, stats])


def scope_of(stats: Dict[str, object]) -> str:
    """The innermost `ydf.*` component of the name stack in an event's
    `tf_op` (`unscoped` where there is none)."""
    for part in reversed(str(stats.get("tf_op", "")).split("/")):
        if part.startswith("ydf."):
            return part.rstrip(":")
    return "unscoped"


def device_seconds_by_scope(trace_dir: str) -> Dict[str, Dict[str, float]]:
    """{scope: {"seconds", "events", "flops", "bytes"}} of a trace
    directory's device operations by `DEVICE_SCOPES`, largest first:
    own seconds (`device_op_times`), and the compiler's own counts from
    the event metadata (`flops`, `bytes_accessed`) summed over
    occurrences, which is what a roofline share of the scope divides
    by."""
    out: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"seconds": 0.0, "events": 0, "flops": 0, "bytes": 0}
    )
    for seconds, _name, stats in device_op_times(trace_dir):
        row = out[scope_of(stats)]
        row["seconds"] += seconds
        row["events"] += 1
        row["flops"] += stats.get("flops", 0)
        row["bytes"] += stats.get("bytes_accessed", 0)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["seconds"]))


def device_loop_metrics() -> Dict[str, float]:
    """The device-resident boosting loop's host-side accounting
    (ops/device_loop.py stats window) in metric form: XLA dispatches,
    host-sync bytes, and the derived per-tree rates bench.py emits on
    headline records (docs/device_loop.md has the boundary
    inventory)."""
    from ydf_tpu.ops import device_loop

    snap = device_loop.stats_snapshot()
    return {
        "ydf_train_dispatches": float(snap["dispatches"]),
        "ydf_train_host_sync_bytes": float(snap["host_sync_bytes"]),
        "ydf_train_dispatches_per_tree": float(
            snap["dispatches_per_tree"]
        ),
        "ydf_train_host_sync_bytes_per_tree": float(
            snap["host_sync_bytes_per_tree"]
        ),
    }


def native_hist_kernel_seconds() -> float:
    """Cumulative wall seconds spent INSIDE the native histogram custom
    call (both precisions) — the exact in-loop attribution for the CPU
    path, measured by the kernel itself (native/histogram_ffi.cc
    counters). 0.0 when the native kernel is unavailable."""
    from ydf_tpu.ops import histogram_native

    return histogram_native.kernel_seconds()


def reset_native_hist_kernel_counters() -> None:
    from ydf_tpu.ops import histogram_native

    histogram_native.reset_kernel_counters()


def native_route_kernel_seconds() -> float:
    """Cumulative wall seconds spent INSIDE the native routing custom
    calls (per-layer ydf_route_update + full-tree ydf_route_tree) —
    the non-histogram in-loop attribution for the CPU path, measured by
    the kernels themselves (native/routing_ffi.cc counters; bench.py's
    route_s). 0.0 when the native kernels are unavailable."""
    from ydf_tpu.ops import routing_native

    return routing_native.route_kernel_seconds()


def native_update_kernel_seconds() -> float:
    """Cumulative wall seconds spent INSIDE the native prediction-update
    custom calls (ydf_leaf_update + ydf_leaf_update_grad; bench.py's
    update_s). 0.0 when the native kernels are unavailable."""
    from ydf_tpu.ops import routing_native

    return routing_native.update_kernel_seconds()


def reset_native_route_kernel_counters() -> None:
    from ydf_tpu.ops import routing_native

    routing_native.reset_kernel_counters()


def native_pool_stats() -> Dict[str, object]:
    """Structured thread-pool utilization snapshot (per kernel family:
    busy-ns, tasks, queue-wait-ns, run-wall-ns and the derived
    busy / (lanes × wall) utilization) — the read side of
    native/thread_pool.h's stats block, via ops/pool_stats.py. Empty
    when the native library is unavailable."""
    from ydf_tpu.ops import pool_stats

    return pool_stats.pool_stats()


def reset_native_pool_stats() -> None:
    from ydf_tpu.ops import pool_stats

    pool_stats.reset_pool_stats()


def native_kernel_metrics() -> Dict[str, float]:
    """The native kernels' cumulative in-kernel wall counters as
    registered telemetry gauges — the accessor functions above, exposed
    through the metrics registry (utils/telemetry.py registers this as
    a default collector, so every metrics dump carries them instead of
    callers knowing five one-off functions). Unavailable kernels report
    0.0, matching the accessors. The thread-pool utilization family
    (`ydf_pool_busy_ns_total{pool,worker}` etc., ops/pool_stats.py)
    rides the same collector with label-suffixed sample keys, which
    telemetry's exposition splits back into name + labels."""
    from ydf_tpu.ops import routing_native

    out = {
        "ydf_native_hist_kernel_seconds": native_hist_kernel_seconds(),
        "ydf_native_route_kernel_seconds": native_route_kernel_seconds(),
        "ydf_native_update_kernel_seconds": native_update_kernel_seconds(),
    }
    try:
        out["ydf_native_fused_kernel_seconds"] = (
            routing_native.fused_kernel_seconds()
        )
    except Exception:
        out["ydf_native_fused_kernel_seconds"] = 0.0
    try:
        from ydf_tpu.serving import native_serve

        out["ydf_native_serve_kernel_seconds"] = (
            native_serve.serve_kernel_seconds()
        )
    except Exception:
        out["ydf_native_serve_kernel_seconds"] = 0.0
    try:
        from ydf_tpu.ops import pool_stats

        out.update(pool_stats.pool_metrics())
    except Exception:
        pass  # pool metrics degrade silently like the kernel counters
    return out


def format_profile(profile: Optional[Dict[str, float]]) -> str:
    """One-line human summary, largest stages first."""
    if not profile:
        return "(no profile)"
    total = profile.get("total", 0.0)
    parts = [
        f"{k}={v:.3f}s"
        for k, v in sorted(profile.items(), key=lambda kv: -kv[1])
        if k != "total"
    ]
    return f"total={total:.3f}s  " + " ".join(parts)
