"""One run of one cell: set-up, the measured window, the check against
the plain reference, the result line. `run.py` looks for the chip and
calls `run_cell`; the fault tests call it without that look.

The traffic generator is general: it reads the mix's file (closed loop,
how many warm jobs, whether jobs share one ingested `ydf.Dataset` or
ingest a fresh table each) and the configuration's file (shapes and
hyperparameters) and drives the `train()` of the learner the
configuration names (`GradientBoostedTreesLearner` where it names none).
A cell of more than one chip hands the learner its chips as the program
takes them, `mesh=ydf.make_mesh(devices)`: every chip takes rows.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time

from harness import compare, manifest, xplane
from harness.compiles import CompileCounter
from harness.datagen import as_columns, make_table

TRACE_DIR = os.path.join(manifest.ROOT, ".bench_trace")
SPANS = ("job", "between_jobs")


def log(tag, **fields):
    print(f"[{tag}] " + json.dumps(fields, default=str), file=sys.stderr,
          flush=True)


def learner_of(ydf, config, devices=None):
    """What builds the configuration's learner on the cell's chips, from
    the configuration's file alone: call it once a job. One chip: the
    learner as it always was built, no `mesh` handed. More: the program's
    mesh over exactly those devices, every chip taking rows."""
    name = config.get("learner", "GradientBoostedTreesLearner")
    if not (name.endswith("Learner") and hasattr(ydf, name)):
        known = sorted(n for n in dir(ydf) if n.endswith("Learner"))
        raise ValueError(f"no learner {name!r} in the program; "
                         f"known: {known}")
    hp = dict(config["hyperparameters"])
    hp["task"] = ydf.Task[hp["task"]]
    if devices is not None and len(devices) > 1:
        hp["mesh"] = ydf.make_mesh(list(devices))
    return lambda: getattr(ydf, name)(label="label", **hp)


class Traffic:
    """Closed-loop training jobs from a mix's parameters, on the cell's
    `devices` (None: the program's default, one chip)."""

    def __init__(self, config, mix, seed, devices=None):
        import ydf_tpu as ydf

        if mix["loop"] != "closed" or mix["clients"] != 1:
            raise ValueError("this generator drives one closed-loop client")
        self.ydf, self.config, self.mix, self.seed = ydf, config, mix, seed
        self.rows, self.features = config["rows"], config["features"]
        self.new_learner = learner_of(ydf, config, devices)
        self.reference = compare.of_config(config)
        self.started = 0
        self.table = self._table(seed)
        self.shared = None
        if mix["dataset"] == "shared":
            self.shared = self._ingest(self.table)

    def _table(self, seed):
        return make_table(self.rows, self.features, seed, self.config["table"])

    def _ingest(self, table):
        return self.ydf.Dataset.from_data(as_columns(*table), label="label")

    def job(self):
        """Runs one job to its end and returns its record."""
        self.started += 1
        t0 = time.perf_counter()
        if self.shared is None:  # a fresh table a job, ingest inside it
            self.table = self._table(self.seed + self.started)
        ds = self.shared or self._ingest(self.table)
        model = self.new_learner().train(ds)
        t1 = time.perf_counter()
        return {
            "t0": t0, "t1": t1, "rows": self.rows,
            "trees": int(model.num_trees()),
            "profile": dict(model.training_profile),
            "implementations": model.training_logs["implementations"],
            "arrays": self.reference.forest_arrays(model),
        }

    def release(self):
        """Drops what the program holds, keeps the last raw table."""
        self.shared = None
        gc.collect()
        return self.table


def _device_info(devices, chips):
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(fullest.get("peak_bytes_in_use", 0))}, fullest


def run_cell(m, cell_name, seed, seconds, trace, t_start, check_kwargs=None,
             files=None):
    """Runs the cell and returns (exit code, result dict). `files`
    overrides what `manifest.cell_files` would load (the tests' small
    sizes); `t_start` is time.time() at the start of the process."""
    import jax

    from ydf_tpu.config import enable_compile_cache
    from ydf_tpu.ops import device_loop

    cell, entry, config, mix, limits = files or manifest.cell_files(m, cell_name)
    devices = jax.devices()
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    log("run", cell=cell_name, seed=seed, seconds=seconds, trace=trace,
        device=devices[0].device_kind, compile_cache=cache_dir,
        config=entry["file"])

    # ---- set-up: table, ingest, warm jobs of the cell's own shape -------
    traffic = Traffic(config, mix, seed, devices[:cell["chips"]])
    t_table = time.time() - t_start
    for _ in range(mix["warm_jobs"]):
        warm = traffic.job()
    setup_s = time.time() - t_start
    log("setup", setup_s=setup_s, table_and_ingest_s=t_table,
        warm_job_s=warm["t1"] - warm["t0"], warm_profile=warm["profile"],
        programs_built=counter.builds, cache_hits=counter.cache_hits,
        implementations=warm["implementations"])

    # ---- the measured window ---------------------------------------------
    # A traced run traces the window's first job cycle: the job's train()
    # call to where the next would start, the host's work between inside it.
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    device_loop.reset_stats()
    builds0 = counter.builds
    jobs = []
    t_w0 = time.perf_counter()
    while time.perf_counter() - t_w0 < seconds:
        if trace and not jobs:
            with jax.profiler.TraceAnnotation("cycle"):
                with jax.profiler.TraceAnnotation("job"):
                    job = traffic.job()
                with jax.profiler.TraceAnnotation("between_jobs"):
                    jobs.append(job)
            jax.profiler.stop_trace()
        else:
            jobs.append(traffic.job())
    window_s = jobs[-1]["t1"] - t_w0
    loop_stats = device_loop.stats_snapshot()
    builds_in_window = counter.builds - builds0
    row_trees = sum(j["rows"] * j["trees"] for j in jobs)
    device, memory = _device_info(devices, cell["chips"])
    for j in jobs:
        log("job", wall_s=j["t1"] - j["t0"], trees=j["trees"],
            profile=j["profile"])
    log("window", window_s=window_s, jobs=len(jobs), row_trees=row_trees,
        programs_built_in_window=builds_in_window, loop=loop_stats)

    # ---- correct: the plain reference over what the window produced ------
    table = traffic.release()
    t_ref = time.perf_counter()
    numbers = traffic.reference.readings(
        table[0], table[1], config["reference"],
        [j["arrays"] for j in jobs],
        follow_trees=min(3, config["num_trees"]),
        devices=devices[:cell["chips"]], **(check_kwargs or {}))
    numbers["programs_built_in_window"] = builds_in_window
    correct, compared = compare.judge(numbers, limits)
    log("reference", seconds=time.perf_counter() - t_ref)

    run = {"jobs": jobs, "window_s": window_s, "row_trees": row_trees,
           "config": config, "mix": mix, "loop_stats": loop_stats,
           "memory": memory, "device_kind": device["kind"],
           "chips": cell["chips"], "trace": None}
    breakdown = None
    if trace:
        t_red = time.perf_counter()
        run["trace"] = traced = xplane.reduce(
            xplane.find_trace(TRACE_DIR), "cycle", SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log("trace", reduce_s=time.perf_counter() - t_red, reduced=traced)
        metrics = {}
        for e in manifest.metrics_of(m, cell_name, per_layer=True):
            value = manifest.reader(e["name"])(run)
            if value is not None:
                metrics[e["name"]] = {"value": value, "unit": e["unit"]}
        if traced:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
            breakdown = {"device_ops": traced["device_ops"],
                         "idle_gaps": traced["idle_gaps"]}
    else:
        values = {"setup_s": setup_s,
                  "train_rows_trees_per_s": row_trees / window_s}
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in manifest.metrics_of(m, cell_name, per_layer=False)}
    result = {"correct": bool(correct),
              "attempted": traffic.started - mix["warm_jobs"], "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = compared  # last on the line, by the contract
    for name, c in compared.items():
        print(f"compared {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    return 0, result
