#!/usr/bin/env python3
"""Sizing probe (chip only): trains the cell's shape at a few row counts
in one process, ascending, and prints for each the peak device memory,
the stage walls and the time per tree; then traces one warm job cycle
at the last size and prints what the trace holds. This is how R and T
of `configs/*.json` were chosen (PERF.md section 4); it is no cell.

    python3 benchmark/tools/size_probe.py --rows 1000000,4000000 --trees 2
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def say(tag, **kw):
    print(f"[{tag}] " + json.dumps(kw, default=str), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="1000000,4000000")
    ap.add_argument("--trees", type=int, default=2)
    ap.add_argument("--hp", default="{}")
    ap.add_argument("--hp2", default="", help="a second learner, last size only")
    ap.add_argument("--seed", type=int, default=2025)
    ap.add_argument("--trace", type=int, default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not os.environ.get("PROBE_REHEARSE"):
        sys.exit("size_probe: no TPU")

    import ydf_tpu as ydf
    from ydf_tpu.config import enable_compile_cache
    from ydf_tpu.ops import device_loop

    from harness.compiles import CompileCounter
    from harness.datagen import as_columns, make_table

    enable_compile_cache()
    cc = CompileCounter()
    hp = json.loads(args.hp)
    say("device", kind=dev.device_kind, mem=dev.memory_stats())
    for rows in [int(r) for r in args.rows.split(",")]:
        t = time.perf_counter()
        x, y = make_table(rows, 28, args.seed, "binary_logit")
        t_table = time.perf_counter() - t
        t = time.perf_counter()
        ds = ydf.Dataset.from_data(as_columns(x, y), label="label")
        t_ingest = time.perf_counter() - t
        for rep in range(3):
            b0 = cc.builds
            device_loop.reset_stats()
            t = time.perf_counter()
            m = ydf.GradientBoostedTreesLearner(
                label="label", num_trees=args.trees, **hp
            ).train(ds)
            wall = time.perf_counter() - t
            ms = dev.memory_stats() or {"peak_bytes_in_use": 0, "bytes_in_use": 0, "bytes_limit": 1}
            say("train", rows=rows, rep=rep, wall=wall, builds=cc.builds - b0,
                profile=m.training_profile, peak=ms["peak_bytes_in_use"],
                in_use=ms["bytes_in_use"], limit=ms["bytes_limit"],
                per_row=ms["peak_bytes_in_use"] / rows,
                loop=device_loop.stats_snapshot(), trees=m.num_trees(),
                impl=m.training_logs["implementations"],
                t_table=t_table, t_ingest=t_ingest,
                tl=m.training_logs["train_loss"])
        del m
    if args.hp2:
        hp2 = json.loads(args.hp2)
        for rep in range(2):
            b0 = cc.builds
            t = time.perf_counter()
            m = ydf.GradientBoostedTreesLearner(
                label="label", num_trees=args.trees, **hp2).train(ds)
            ms = dev.memory_stats() or {"peak_bytes_in_use": 0, "bytes_in_use": 0, "bytes_limit": 1}
            say("train2", rows=rows, rep=rep, wall=time.perf_counter() - t,
                builds=cc.builds - b0, profile=m.training_profile,
                peak=ms["peak_bytes_in_use"], trees=m.num_trees(),
                tl=m.training_logs["train_loss"])
        del m
    if args.trace:
        from harness import xplane

        d = os.path.join(ROOT, "chiprun_out", "probe_trace")
        os.makedirs(d, exist_ok=True)
        t = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("job"):
            ydf.GradientBoostedTreesLearner(
                label="label", num_trees=args.trees, **hp).train(ds)
        jax.profiler.stop_trace()
        say("traced", wall=time.perf_counter() - t)
        pb = xplane.find_trace(d)
        say("trace_file", bytes=os.path.getsize(pb))
        t = time.perf_counter()
        out = xplane.describe(pb)
        say("trace", parse_s=time.perf_counter() - t, **out)
        t = time.perf_counter()
        say("reduced", r=xplane.reduce(pb, "job", ["job"]),
            reduce_s=time.perf_counter() - t)
        os.remove(pb)
        # A small trace to keep beside the reduction as its test input.
        xs, ys = make_table(20_000, 28, 7, "binary_logit")
        small = ydf.Dataset.from_data(as_columns(xs, ys), label="label")
        mk = lambda: ydf.GradientBoostedTreesLearner(
            label="label", num_trees=1, max_depth=3).train(small)
        mk()
        d2 = os.path.join(ROOT, "chiprun_out", "small_trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d2, profiler_options=opts)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("job"):
                mk()
            with jax.profiler.TraceAnnotation("between_jobs"):
                time.sleep(0.05)
        jax.profiler.stop_trace()
        pb2 = xplane.find_trace(d2)
        say("small_trace", bytes=os.path.getsize(pb2),
            reduced=xplane.reduce(pb2, "job", ["job", "between_jobs"]))


if __name__ == "__main__":
    main()
