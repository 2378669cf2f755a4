#!/usr/bin/env python3
"""Where one job's time goes, by the program's own names: runs one
traced job of a cell (after the cell's warm jobs, which compile with
the persistent cache off: about a minute more than a run) and prints

- the job's `training_profile` (the host spans' seconds),
- the device's seconds by `ydf.*` scope with the compiler's flops and
  bytes (`profiling.device_seconds_by_scope`), and the largest
  operations with their scope,
- the device's idle gaps named by the innermost host span
  (`xplane.name_gaps` over `job`, `between_jobs` and
  `profiling.TRAIN_SPANS`).

How PERF.md section 5 is filled. It feeds no metric.

    python3 benchmark/tools/spans.py --workload synth100_gbt.sweep --seed 2147483929
    python3 benchmark/tools/spans.py --trace-dir <a kept trace>
"""

import argparse
import json
import os
import shutil
import sys
from collections import defaultdict

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def traced_job(workload, seed, trace_dir):
    """One traced job of the cell, as the runner traces its first; the
    job's record."""
    import jax

    from harness import manifest
    from harness.runner import Traffic

    if jax.devices()[0].platform != "tpu":
        sys.exit("spans: JAX reports no TPU. Nothing was run.")
    # Compile this tree's program: the persistent cache's key leaves an
    # operation's metadata out, so an executable cached before a scope
    # was named (or renamed) is served with the names it was built with.
    jax.config.update("jax_enable_compilation_cache", False)
    cell, _, config, mix, _ = manifest.cell_files(manifest.load(), workload)
    traffic = Traffic(config, mix, seed, jax.devices()[:cell["chips"]])
    for _ in range(mix["warm_jobs"]):
        traffic.job()
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("job"):
        job = traffic.job()
    with jax.profiler.TraceAnnotation("between_jobs"):
        pass
    jax.profiler.stop_trace()
    return job


def report(trace_dir):
    from harness import xplane
    from ydf_tpu.utils import profiling

    by_scope = profiling.device_seconds_by_scope(trace_dir)
    busy = sum(row["seconds"] for row in by_scope.values())
    scopes = {scope: dict(row, share_of_busy=row["seconds"] / busy)
              for scope, row in by_scope.items()} if busy else {}
    ops = defaultdict(float)
    for seconds, name, stats in profiling.device_op_times(trace_dir):
        ops[f"{profiling.scope_of(stats)} {name.split(' = ')[0]} "
            f"{stats.get('hlo_category', '')}"] += seconds
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:24]

    names = ("job", "between_jobs") + profiling.TRAIN_SPANS
    profile = xplane.load(xplane.find_trace(trace_dir))
    spans = xplane.host_spans(profile, set(names))
    jobs = [sp for sp in spans if sp[2] == "job"]
    gaps, window_s = {}, None
    by_device = xplane.device_op_events(profile)
    if jobs and any(by_device.values()):
        lo, hi = jobs[0][0], jobs[0][1]
        events = max(by_device.values(), key=len)
        busy_iv = xplane.clip(xplane.union((s, e) for s, e, _ in events), lo, hi)
        gaps = xplane.name_gaps(xplane.idle_gaps(events, lo, hi), spans, busy_iv)
        window_s = (hi - lo) / 1e9
    return {
        "device_busy_s": busy,
        "job_span_s": window_s,
        "named_share_of_busy": 1.0 - scopes.get("unscoped", {}).get(
            "share_of_busy", 0.0) if scopes else None,
        "device_seconds_by_scope": scopes,
        "largest_operations": [[k, v] for k, v in top],
        "idle_gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
        "host_spans": [[name, (e - s) / 1e9] for s, e, name in spans],
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2147483929)
    ap.add_argument("--trace-dir", help="read this trace; run nothing")
    ap.add_argument("--keep", action="store_true",
                    help="leave the trace of the job under .bench_trace/")
    args = ap.parse_args()
    out = {}
    trace_dir = args.trace_dir
    if not trace_dir:
        if not args.workload:
            ap.error("--workload or --trace-dir")
        from harness.runner import TRACE_DIR as trace_dir

        job = traced_job(args.workload, args.seed, trace_dir)
        out["job_wall_s"] = job["t1"] - job["t0"]
        out["training_profile"] = job["profile"]
    out.update(report(trace_dir))
    if not args.trace_dir and not args.keep:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
