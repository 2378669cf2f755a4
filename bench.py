"""Benchmark: GBT training throughput (the flagship metric of BASELINE.json).

Prints one JSON record on stdout: {"metric", "value", "unit", "platform",
"device_kind", "device_count", ...}. The record names the device it ran
on. Without --cpu the default JAX backend is used and a machine with no
accelerator is an error; --cpu is the only way onto the CPU and is
explicit. Any failure exits non-zero with a traceback.

value = rows × trees / wall-seconds of an end-to-end train() call —
dataspec inference + binning + the jitted boosting loop + model assembly,
compile excluded (second call, cached executables) — on a Higgs-like
synthetic dataset (28 numerical features, binary label). End-to-end is
the honest unit: the reference's wall-clock includes its dataset
ingestion too.

Baseline. pip `ydf` is not installed in this image, so vs_baseline divides
by a MEASURED number: sklearn HistGradientBoostingClassifier trained at the
identical shape (rows, trees, depth, 255 bins) on this same machine's CPU —
the closest available stand-in for CPU YDF's histogram GBT (both are
single-pass histogram learners; sklearn is the documented proxy in
BASELINE.md). The measurement is cached in BASELINE_measured.json keyed by
shape. The old 64-core YDF engineering estimate is still reported as
`vs_ydf64_estimate` for continuity.

On an accelerator the record also carries a matmul-vs-segment histogram
timing and a compiled (non-interpret) QuickScorer check; a failure of
either fails the run.

One process per chip: this process holds the accelerator, so the only
children it starts (measure_core_scaling, which times CPU kernels) get
JAX_PLATFORMS=cpu in their environment.
"""

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_YDF64_ESTIMATE_ROWS_TREES_PER_SEC = 6.1e6  # engineering estimate
BASELINE_CACHE = os.path.join(os.path.dirname(__file__), "BASELINE_measured.json")

# Recorded per-example serving floors on the CPU bench box, keyed by the
# (rows, trees) shape of the record that measured them. An earlier
# reading of "640 ns (round 4) → 1381 ns (round 5)" as a serving
# regression was a SHAPE CONFOUND: 640.5 ns came from a (20k rows,
# 5 trees) record while 1380.7 ns is the (500k rows, 20 trees,
# n_inf = 100k) record — round 4's own full record measured 1451.2 ns,
# so same-shape serving IMPROVED 5 % between the rounds. ns/example
# scales ~linearly with tree count (4× trees ≈ 2.2× measured,
# sub-linear because fixed per-call costs amortize over the larger
# n_inf), so floors are only comparable per shape. The guard below
# emits infer_p50_floor_ns / infer_p50_within_floor on every CPU record
# whose shape has a recorded floor (docs/serving.md "The 640 ns story").
INFER_P50_FLOOR_NS = {
    (20_000, 5): 640.5,
    (500_000, 20): 1380.7,
}

# Same guard for serving MEMORY: recorded per-shape ceilings on the
# peak-RSS growth across model.benchmark()'s measured (post-warmup)
# predict runs (`infer_peak_rss_delta_bytes`). Populated the same way
# the latency floors were — from observed rounds; shapes without an
# entry emit the measurement only. A steady-state serving path should
# allocate ~nothing: a delta regression here is caught by the identical
# floor machinery as the latency guard (infer_rss_within_floor).
INFER_RSS_DELTA_FLOOR_BYTES = {}


def emit(record):
    """Print one JSON result line. Every record leaves through here, so
    every record names the device it ran on."""
    sys.stdout.write(json.dumps({**record, **device_fields()}) + "\n")
    sys.stdout.flush()


def use_cpu():
    """--cpu: select the CPU backend before JAX initialises one."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def device_fields():
    """The device every record names, as JAX reports it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def measure_sklearn_baseline(x, y, trees, depth):
    """Measured same-box baseline: sklearn HistGradientBoostingClassifier
    at the identical (rows, trees, depth) shape with 255 bins — the
    documented CPU-YDF proxy (BASELINE.md). Cached by shape."""
    rows = x.shape[0]
    key = f"hgb_{rows}x{x.shape[1]}_t{trees}_d{depth}"
    cache = {}
    if os.path.exists(BASELINE_CACHE):
        with open(BASELINE_CACHE) as f:
            cache = json.load(f)
    if key in cache:
        return cache[key], "sklearn_hgb_cached"
    from sklearn.ensemble import HistGradientBoostingClassifier

    clf = HistGradientBoostingClassifier(
        max_iter=trees,
        max_depth=depth,
        max_bins=255,
        early_stopping=False,
        validation_fraction=None,
    )
    t0 = time.time()
    clf.fit(x, y)
    wall = time.time() - t0
    value = rows * trees / wall
    cache[key] = round(value, 1)
    cache[key + "_wall_s"] = round(wall, 2)
    with open(BASELINE_CACHE, "w") as f:
        json.dump(cache, f, indent=1)
    return value, "sklearn_hgb_measured"


def hardware_extras(model, data, record):
    """On-accelerator evidence: matmul vs segment histogram timing and a
    compiled (non-interpret) QuickScorer run against the routed scan. A
    failure here fails the bench."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from ydf_tpu.dataset.dataset import Dataset
    from ydf_tpu.ops.histogram import histogram
    from ydf_tpu.ops.routing import forest_predict_values

    rng = np.random.RandomState(1)
    n, f = 1_000_000, 28
    binned = jnp.asarray(rng.randint(0, 256, size=(n, f)).astype(np.int32))
    slot = jnp.asarray(rng.randint(0, 8, size=(n,)).astype(np.int32))
    stats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    timings = {}
    outs = {}
    for impl in ("matmul", "segment"):
        o = histogram(binned, slot, stats, num_slots=8, num_bins=256, impl=impl)
        jax.block_until_ready(o)
        t0 = time.time()
        for _ in range(3):
            o = histogram(
                binned, slot, stats, num_slots=8, num_bins=256, impl=impl
            )
        jax.block_until_ready(o)
        timings[impl] = (time.time() - t0) / 3
        outs[impl] = np.asarray(o, np.float64)
    record["hist_matmul_s"] = round(timings["matmul"], 4)
    record["hist_segment_s"] = round(timings["segment"], 4)
    record["hist_impl_max_abs_diff"] = float(
        np.max(np.abs(outs["matmul"] - outs["segment"]))
    )

    # Compiled (non-interpret) QuickScorer vs the routed oracle on the
    # freshly trained model.
    sample = {k: v[:4096] for k, v in data.items()}
    ds = Dataset.from_data(sample, dataspec=model.dataspec)
    x_num, x_cat, _ = model._encode_inputs(ds)
    eng = model._fast_engine()
    if eng is None:
        raise RuntimeError(
            "no fast serving engine was selected on platform "
            f"{jax.devices()[0].platform!r}"
        )
    qs = np.asarray(eng(jnp.asarray(x_num)))
    routed = np.asarray(
        forest_predict_values(
            model.forest,
            jnp.asarray(x_num),
            jnp.asarray(x_cat),
            num_numerical=model.binner.num_numerical,
            max_depth=model.max_depth,
            combine="sum",
        )
    )[:, 0]
    record["quickscorer_compiled_max_abs_diff"] = float(
        np.max(np.abs(qs - routed))
    )


def measure_in_loop_hist(train, record):
    """The REAL in-loop kernel attribution: one extra steady-state
    train() runs under jax.profiler.trace with the native kernels' wall
    counters reset. `hist_s` is the time measured INSIDE the in-loop
    histogram op (ROADMAP open item closed by PR 3); `route_s` /
    `update_s` are the same measurement for the fused row-routing and
    prediction-update kernels (PR 4 — the NON-histogram half of the
    loop; 0.0 and absent when YDF_TPU_ROUTE_IMPL=xla, where those ops
    live inside XLA fusions and cannot be attributed). The histogram
    falls back to the trace's custom-call events parsed via
    profiling.trace_event_seconds (no tensorboard dependency) on
    non-native impls. The historical outside-the-scan re-measurement
    stays emitted as `hist_attrib_s` (measure_hist_attribution) for
    trajectory continuity. Failures are recorded, never fatal."""
    import shutil
    import tempfile

    import jax

    from ydf_tpu.utils.profiling import (
        native_hist_kernel_seconds,
        native_route_kernel_seconds,
        native_update_kernel_seconds,
        reset_native_hist_kernel_counters,
        reset_native_route_kernel_counters,
        trace_event_seconds,
    )

    from ydf_tpu.utils.profiling import (
        native_pool_stats,
        reset_native_pool_stats,
    )

    td = tempfile.mkdtemp(prefix="ydf_hist_trace_")
    try:
        reset_native_hist_kernel_counters()
        reset_native_route_kernel_counters()
        reset_native_pool_stats()
        with jax.profiler.trace(td):
            _, wall, _ = train()
        record["hist_profiled_train_wall_s"] = round(wall, 2)
        # Thread-pool utilization per training stage (busy ÷ (lanes ×
        # pooled wall), native/thread_pool.h stats): THE number ROADMAP
        # item 3's native-vs-XLA flip is judged by — a stage whose
        # utilization stays low on a many-core box is not saturating it,
        # whatever its wall says. Serving utilization is added by
        # measure_serving_family from its own bracketed reset.
        ps = native_pool_stats()
        if ps:
            record["pool_size"] = ps["size"]
            util = {
                fam: f["utilization"]
                for fam, f in ps["families"].items()
                if f["runs"] > 0 and fam != "serve"
            }
            if util:
                record["pool_utilization"] = util
            # Both denominators ride the record: `pool_utilization` is
            # busy / (ALL lanes × wall) — what the box-level provisioner
            # sees — while `engaged_utilization` divides by only the
            # lanes a run actually engaged (min(size, blocks, cap)), so
            # a small run on a big pool is not misread as the pool
            # sitting idle. The gap between them IS the oversizing
            # signal (work-stealing round).
            eng = {
                fam: f["engaged_utilization"]
                for fam, f in ps["families"].items()
                if f["runs"] > 0 and fam != "serve"
            }
            if eng:
                record["engaged_utilization"] = eng
        native_s = native_hist_kernel_seconds()
        if native_s > 0:
            record["hist_s"] = round(native_s, 3)
            record["hist_s_source"] = "native_kernel_counter"
        else:
            # Non-native impls: sum the histogram-shaped custom-call /
            # dot events from the trace (best-effort — XLA-CPU names
            # fusions opaquely, so only custom calls attribute cleanly).
            ev = trace_event_seconds(td, substrings=("custom-call",))
            total = sum(ev.values())
            if total > 0:
                record["hist_s"] = round(total, 3)
                record["hist_s_source"] = "profiler_trace"
        route_s = native_route_kernel_seconds()
        update_s = native_update_kernel_seconds()
        if route_s > 0 or update_s > 0:
            record["route_s"] = round(route_s, 3)
            record["update_s"] = round(update_s, 3)
            record["route_s_source"] = "native_kernel_counter"
        # Fully-fused histogram+routing calls (route_impl=native AND
        # hist_impl=native): the per-layer routing rides the histogram
        # kernel's own row walk, so its time is inseparable from the
        # contraction — reported whole as fused_s (route_s then counts
        # only the standalone last-layer/validation passes).
        from ydf_tpu.ops.routing_native import fused_kernel_seconds

        fused_s = fused_kernel_seconds()
        if fused_s > 0:
            record["fused_s"] = round(fused_s, 3)
    except Exception as e:
        record["hist_in_loop_error"] = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(td, ignore_errors=True)


def measure_hist_attribution(rows, features, depth, trees, record):
    """Same-shape per-layer histogram wall OUTSIDE the fused scan,
    emitted as `hist_attrib_s` (sibling-subtraction slot counts — what
    the grower runs; this field was `hist_s` before PR 3 moved the real
    in-loop number there) and `hist_direct_s` (the pre-subtraction
    full-frontier counts), both scaled to the whole train call
    (× trees). Failures are recorded, never fatal."""
    import numpy as np
    import jax

    try:
        from ydf_tpu.config import resolve_max_frontier
        from ydf_tpu.ops.histogram import histogram, resolve_hist_impl

        impl = resolve_hist_impl("auto")
        L = min(
            2 ** max(depth - 1, 0), resolve_max_frontier("auto", rows, 5)
        )
        B = 256
        rng = np.random.RandomState(7)
        bins = jax.numpy.asarray(
            rng.randint(0, B, size=(rows, features)).astype(np.uint8)
        )
        stats = jax.numpy.asarray(
            rng.normal(size=(rows, 3)).astype(np.float32)
        )

        def timed(slot_np, num_slots):
            slot = jax.numpy.asarray(slot_np)
            o = histogram(
                bins, slot, stats, num_slots=num_slots, num_bins=B,
                impl=impl,
            )
            jax.block_until_ready(o)  # warm (compile)
            t0 = time.time()
            o = histogram(
                bins, slot, stats, num_slots=num_slots, num_bins=B,
                impl=impl,
            )
            jax.block_until_ready(o)
            return time.time() - t0

        t_sub = t_direct = 0.0
        for d in range(depth):
            Ld = min(2**d, L)
            if d == 0:
                t_layer = timed(np.zeros(rows, np.int32), 1)
                t_sub += t_layer
                t_direct += t_layer
                continue
            # Subtraction layer: Lh live slots, ~half the rows (the
            # larger children) on the trash slot.
            Lh = max(1, min(2 ** (d - 1), L // 2))
            raw = rng.randint(0, 2 * Lh, size=rows).astype(np.int32)
            t_sub += timed(np.where(raw < Lh, raw, Lh), Lh)
            # Direct layer: every row live across the full Ld slots.
            t_direct += timed(
                rng.randint(0, Ld, size=rows).astype(np.int32), Ld
            )
        record["hist_attrib_s"] = round(t_sub * trees, 3)
        record["hist_direct_s"] = round(t_direct * trees, 3)
        record["hist_impl"] = impl
    except Exception as e:
        record["hist_extra_error"] = f"{type(e).__name__}: {e}"


def measure_serving_family(model, data, rows, record):
    """The serving bench family (ROADMAP item 1's measurement half):
    per-call p50/p99 latency at batch sizes {1, 16, 256, 4096} for every
    compatible serving engine on pre-encoded inputs, plus the binned
    native fast path. `serve_engine` names the engine predict() actually
    selects for this model (registry fastest-compatible); the headline
    `infer_qps` / `infer_batch_p50_ns` / `infer_batch_p99_ns` fields
    are that engine's numbers — rows/sec at the best batch size, and
    per-call latency per batch size (the "millions of users" figures;
    docs/serving.md "Bench fields"). Failures recorded, never fatal."""
    import numpy as np
    import jax.numpy as jnp

    from ydf_tpu.dataset.dataset import Dataset
    from ydf_tpu.ops.routing import forest_predict_values
    from ydf_tpu.utils.telemetry import LatencyHistogram

    SIZES = (1, 16, 256, 4096)
    CALLS = {1: 200, 16: 100, 256: 40, 4096: 15}
    try:
        from ydf_tpu.utils.profiling import (
            native_pool_stats,
            reset_native_pool_stats,
        )

        reset_native_pool_stats()  # serve-stage utilization bracketing
        sample = {k: v[: min(rows, 8192)] for k, v in data.items()}
        ds = Dataset.from_data(sample, dataspec=model.dataspec)
        x_num, x_cat, _ = model._encode_inputs(ds)
        n_av = x_num.shape[0]
        jx_num, jx_cat = jnp.asarray(x_num), jnp.asarray(x_cat)

        sel = model._fast_engine()
        serve_engine = (
            type(sel).__name__.replace("Engine", "")
            if sel is not None
            else "Routed"
        )
        record["serve_engine"] = serve_engine

        # name -> {batch: zero-arg callable} with inputs pre-sliced
        # outside the timed region.
        per_engine = {}

        def routed_calls():
            calls = {}
            for b in SIZES:
                if b > n_av:
                    continue
                xn, xc = jx_num[:b], jx_cat[:b]

                def run(xn=xn, xc=xc):
                    return np.asarray(
                        forest_predict_values(
                            model.forest, xn, xc,
                            num_numerical=model.binner.num_numerical,
                            max_depth=model.max_depth, combine="sum",
                        )
                    )

                calls[b] = run
            return calls

        per_engine["Routed"] = routed_calls()

        from ydf_tpu.serving.registry import compatible_engines

        for f in compatible_engines(model):
            if f.name == "Routed" or f.name in per_engine:
                continue
            try:
                eng = f.build(model)
            except Exception:
                continue
            if eng is None:
                continue
            calls = {}
            for b in SIZES:
                if b > n_av:
                    continue
                xn = np.ascontiguousarray(x_num[:b])
                xc = np.ascontiguousarray(x_cat[:b])

                def run(eng=eng, xn=xn, xc=xc):
                    return np.asarray(eng(xn, xc))

                calls[b] = run
            per_engine[f.name] = calls

        try:
            from ydf_tpu.serving.native_serve import (
                build_native_binned_engine,
            )

            nbb = build_native_binned_engine(model)
            if nbb is not None:
                bins = np.ascontiguousarray(
                    model.binner.transform(ds)[:, : model.binner.num_scalar]
                )
                calls = {}
                for b in SIZES:
                    if b > n_av:
                        continue
                    bn = np.ascontiguousarray(bins[:b])

                    def run(nbb=nbb, bn=bn):
                        return np.asarray(nbb(bn))

                    calls[b] = run
                per_engine["NativeBinned"] = calls
        except Exception:
            pass

        res = {}
        for name, calls in per_engine.items():
            per = {}
            for b, run in calls.items():
                run()  # warmup / compile
                hist = LatencyHistogram()
                for _ in range(CALLS[b]):
                    t0 = time.perf_counter()
                    run()
                    hist.observe_s(time.perf_counter() - t0)
                p50 = hist.percentile_ns(50)
                p99 = hist.percentile_ns(99)
                per[str(b)] = {
                    "p50_ns": round(p50, 1),
                    "p99_ns": round(p99, 1),
                    "qps": round(b * 1e9 / max(p50, 1.0), 1),
                }
            res[name] = per
        record["infer_engines"] = res
        chosen = res.get(serve_engine) or res["Routed"]
        record["infer_qps"] = max(v["qps"] for v in chosen.values())
        record["infer_batch_p50_ns"] = {
            b: v["p50_ns"] for b, v in chosen.items()
        }
        record["infer_batch_p99_ns"] = {
            b: v["p99_ns"] for b, v in chosen.items()
        }
        # Serving memory accounting: bytes held by the flat serving
        # data banks built above (the flatten-at-load footprint — what
        # a serving host pays per loaded model), and the serve-stage
        # pool utilization over the measured loops.
        try:
            from ydf_tpu.serving.native_serve import bank_bytes_total

            record["serve_bank_bytes"] = int(bank_bytes_total())
        except Exception:
            record["serve_bank_bytes"] = 0
        ps = native_pool_stats()
        if ps and ps["families"].get("serve", {}).get("runs"):
            record.setdefault("pool_size", ps["size"])
            record.setdefault("pool_utilization", {})["serve"] = (
                ps["families"]["serve"]["utilization"]
            )
            record.setdefault("engaged_utilization", {})["serve"] = (
                ps["families"]["serve"]["engaged_utilization"]
            )
    except Exception as e:
        record["serve_family_error"] = f"{type(e).__name__}: {e}"


def measure_serving_load_family(model, data, rows, record):
    """Serving-UNDER-LOAD bench family (serving/loadgen.py — ROADMAP
    item 1's "multi-process closed+open-loop load generator"): the
    per-call engine numbers above are unloaded microbenchmarks; these
    fields say what the batcher front sustains and at what tail.

      serve_sustained_qps     closed-loop capacity: 4 lanes, think-time
                              0, through a bounded model_batcher
      serve_load_p50_ns       open-loop Poisson run at 70% of that
      serve_load_p99_ns       capacity; latency measured from the
                              SCHEDULED arrival (coordinated-omission-
                              safe — queueing delay is charged to the
                              requests, never hidden)
      serve_queue_age_p99_ns  dispatch lag p99 of the same run (actual
                              fire − scheduled arrival)
      serve_shed_rate         shed / (ok + shed) of the open-loop run
                              (0.0 on a healthy 0.7x run)

    The full run records (log2 latency buckets, shed-by-reason, ledger
    peak) ride record["serve_load"] without the bucket arrays.
    Failures recorded, never fatal."""
    import numpy as np

    from ydf_tpu.dataset.dataset import Dataset

    try:
        from ydf_tpu.serving import loadgen
        from ydf_tpu.serving.registry import model_batcher

        sample = {k: v[: min(rows, 2048)] for k, v in data.items()}
        ds = Dataset.from_data(sample, dataspec=model.dataspec)
        x_num, x_cat, _ = model._encode_inputs(ds)
        x_num = np.ascontiguousarray(x_num)
        x_cat = np.ascontiguousarray(x_cat)
        n_av = x_num.shape[0]
        workers = 4
        n_req = 1200
        with model_batcher(
            model, max_batch=64, timeout_us=200.0,
            max_queue=4096, deadline_us=100_000.0,
        ) as bat:
            def call(i):
                j = i % n_av
                bat.predict_one(x_num[j], x_cat[j])

            closed = loadgen.run_closed_loop(
                call, n_req, workers=workers, seed=0
            )
            capacity = max(closed["achieved_qps"], 1.0)
            sched = loadgen.arrival_schedule_ns(
                n_req, capacity * 0.7, arrival="poisson", seed=1
            )
            opened = loadgen.run_open_loop(
                call, sched, workers=workers, seed=1,
                arrival="poisson", offered_qps=capacity * 0.7,
            )
        record["serve_sustained_qps"] = closed["achieved_qps"]
        record["serve_load_p50_ns"] = opened["latency_p50_ns"]
        record["serve_load_p99_ns"] = opened["latency_p99_ns"]
        record["serve_queue_age_p99_ns"] = opened["queue_age_p99_ns"]
        accepted = opened["ok"] + opened["shed"]
        record["serve_shed_rate"] = round(
            opened["shed"] / max(accepted, 1), 4
        )
        record["serve_load"] = {
            "closed": loadgen.record_summary(closed),
            "open": loadgen.record_summary(opened),
        }
    except Exception as e:
        record["serve_load_family_error"] = f"{type(e).__name__}: {e}"


def measure_fleet_family(model, data, rows, record):
    """Serving-FLEET bench family (serving/fleet.py — ROADMAP item 1's
    tier half): a replica pool over the RPC worker substrate, driven by
    the round-16 load generator at sustained QPS across a versioned
    hot-swap. Headline fields:

      fleet_replicas          replica count (YDF_TPU_BENCH_FLEET_REPLICAS,
                              default 2, 0 disables the family; part of
                              the bench-diff pairing shape so 2-replica
                              and 4-replica rounds never cross-compare)
      fleet_sustained_qps     closed-loop capacity through the router: 4
                              lanes, think-time 0, single-row predicts
                              spread round-robin over the replicas
      fleet_swap_p99_ns       accepted-request p99 of the SAME run —
                              which spans a mid-run hot-swap to a
                              second model version, so the tail carries
                              whatever the flip cost (zero-downtime
                              means it stays bounded)
      fleet_failover_count    failovers the run needed (0 on a healthy
                              in-process fleet)
      rpc_connects            TCP connects the whole run paid (<= 1
                              per replica under the persistent pool),
      rpc_conn_reuse_rate     the fraction of requests that reused a
                              pooled connection,
      rpc_header_bytes        wire bytes: pickled headers vs zero-copy
      rpc_payload_bytes       array segments, and
      fleet_predict_rtt_p50_ns  the per-RPC predict round-trip p50 on
                              the pooled connection (no routing/
                              failover retries in it)

    YDF_TPU_BENCH_FLEET_ELASTIC=1 adds the elastic mode: the SAME
    closed-loop run additionally spans a live `add_replica` of a
    freshly spawned replica and a `remove_replica` drain of it,
    emitting

      fleet_join_to_serving_ns  spawn -> admitted wall (the time to
                              serving: port bind, worker start, frame
                              ship, verify, rotation admit)
      fleet_drain_ns          whole drain+teardown wall
      fleet_scale_events      join+drain count the run performed
      fleet_elastic           1 — part of the bench-diff pairing shape
                              so elastic records never cross-compare
                              with static ones

    The run detail (swap result, shed/error counts, router status)
    rides record["fleet"]. Replicas are in-process localhost workers —
    like the distributed family, this measures PROTOCOL cost, not
    scaling; a multi-host fleet is where replica-count speedup appears.
    Failures recorded, never fatal."""
    env = os.environ.get("YDF_TPU_BENCH_FLEET_REPLICAS")
    try:
        nrep = int(env) if env else 2
        if nrep < 0 or nrep == 1:
            raise ValueError
    except ValueError:
        record["fleet_family_error"] = (
            f"YDF_TPU_BENCH_FLEET_REPLICAS={env!r} must be an integer "
            ">= 2 (or 0 to disable the fleet family)"
        )
        return
    if nrep == 0:
        return
    elastic_env = os.environ.get("YDF_TPU_BENCH_FLEET_ELASTIC", "")
    if elastic_env not in ("", "0", "1"):
        record["fleet_family_error"] = (
            f"YDF_TPU_BENCH_FLEET_ELASTIC={elastic_env!r} must be "
            "0 or 1"
        )
        return
    elastic = elastic_env == "1"
    import socket as _socket
    import threading

    import numpy as np

    from ydf_tpu.dataset.dataset import Dataset

    try:
        from ydf_tpu.parallel.worker_service import (
            WorkerPool,
            start_worker,
        )
        from ydf_tpu.serving import loadgen
        from ydf_tpu.serving.fleet import FleetRouter

        sample = {k: v[: min(rows, 2048)] for k, v in data.items()}
        ds = Dataset.from_data(sample, dataspec=model.dataspec)
        x_num, x_cat, _ = model._encode_inputs(ds)
        x_num = np.ascontiguousarray(x_num)
        x_cat = np.ascontiguousarray(x_cat)
        n_av = x_num.shape[0]
        ports = []
        for _ in range(nrep):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            s.close()
        for p in ports:
            start_worker(p, host="127.0.0.1", blocking=False)
        addrs = [f"127.0.0.1:{p}" for p in ports]
        router = FleetRouter(addrs)
        elastic_state = {}
        try:
            router.deploy(model, "bench_v1")
            # The swap target: the same forest under a new version id —
            # the swap mechanics (ship, verify, flip, drain, free) are
            # identical, and bit-identity across the flip is trivially
            # checkable.
            router.deploy(model, "bench_v2", activate=False)
            n_req = 600
            swap_at = n_req // 3
            swap_result = {}
            swap_thread = []
            swap_lock = threading.Lock()

            def do_swap():
                swap_result.update(router.swap_to("bench_v2"))

            # Elastic mode: a live join and a live drain mid-run —
            # spawn->admitted wall is the headline "time to serving",
            # and the drain wall covers rotation removal + in-flight
            # drain + bank teardown. The joiner is the one drained
            # (the autoscaler's LIFO discipline).
            join_at = n_req // 2
            drain_at = (5 * n_req) // 6
            join_thread = []
            drain_thread = []

            def do_join():
                t0 = time.perf_counter_ns()
                s2 = _socket.socket()
                s2.bind(("127.0.0.1", 0))
                p2 = s2.getsockname()[1]
                s2.close()
                start_worker(p2, host="127.0.0.1", blocking=False)
                addr = f"127.0.0.1:{p2}"
                res = router.add_replica(addr)
                elastic_state["join_ns"] = (
                    time.perf_counter_ns() - t0
                )
                elastic_state["joiner"] = addr
                elastic_state["join"] = res

            def do_drain():
                for t in join_thread:
                    t.join(timeout=30)
                addr = elastic_state.get("joiner")
                if addr is None:
                    return
                t0 = time.perf_counter_ns()
                res = router.remove_replica(addr)
                elastic_state["drain_ns"] = (
                    time.perf_counter_ns() - t0
                )
                elastic_state["drain"] = res

            def call(i):
                if i == swap_at:
                    with swap_lock:
                        if not swap_thread:
                            t = threading.Thread(
                                target=do_swap, daemon=True
                            )
                            t.start()
                            swap_thread.append(t)
                if elastic and i == join_at:
                    with swap_lock:
                        if not join_thread:
                            t = threading.Thread(
                                target=do_join, daemon=True
                            )
                            t.start()
                            join_thread.append(t)
                if elastic and i == drain_at:
                    with swap_lock:
                        if not drain_thread:
                            t = threading.Thread(
                                target=do_drain, daemon=True
                            )
                            t.start()
                            drain_thread.append(t)
                j = i % n_av
                router.predict(
                    x_num[j: j + 1], x_cat[j: j + 1], req_id=i
                )

            closed = loadgen.run_closed_loop(
                call, n_req, workers=4, seed=0
            )
            for t in swap_thread + join_thread + drain_thread:
                t.join(timeout=30)
            status = router.status()
            record["fleet_replicas"] = nrep
            record["fleet_sustained_qps"] = closed["achieved_qps"]
            record["fleet_swap_p99_ns"] = closed["latency_p99_ns"]
            record["fleet_failover_count"] = status["failovers"]
            # Transport-overhaul headline fields: the whole run's TCP
            # connects (<= 1 per replica under the persistent pool),
            # the connection-reuse rate, wire bytes split into pickled
            # header vs zero-copy array payload, and the per-RPC
            # predict round-trip p50 (one replica request on the
            # pooled connection — the protocol-overhead instrument the
            # localhost bench actually measures).
            tsnap = router.pool.transport_snapshot()
            record["rpc_connects"] = int(tsnap["rpc_connects"])
            record["rpc_conn_reuse_rate"] = float(
                tsnap["rpc_conn_reuse_rate"]
            )
            record["rpc_header_bytes"] = int(tsnap["rpc_header_bytes"])
            record["rpc_payload_bytes"] = int(
                tsnap["rpc_payload_bytes"]
            )
            record["fleet_predict_rtt_p50_ns"] = round(
                status["predict_rtt_p50_ns"], 1
            )
            record["fleet"] = {
                "swap": swap_result,
                "errors": closed["errors"],
                "shed": closed["shed"],
                "ok": closed["ok"],
                "active_version": status["active_version"],
                "swaps": status["swaps"],
                "latency_ns": status["latency_ns"],
            }
            record["fleet_elastic"] = int(elastic)
            if elastic:
                record["fleet_join_to_serving_ns"] = int(
                    elastic_state.get("join_ns", 0)
                )
                record["fleet_drain_ns"] = int(
                    elastic_state.get("drain_ns", 0)
                )
                record["fleet_scale_events"] = int(
                    status["joins"] + status["drains"]
                )
                record["fleet"]["elastic"] = {
                    "join": elastic_state.get("join"),
                    "drain": elastic_state.get("drain"),
                    "joins": status["joins"],
                    "drains": status["drains"],
                }
        finally:
            router.close()
            try:
                extra = (
                    [elastic_state["joiner"]]
                    if elastic and elastic_state.get("joiner")
                    else []
                )
                WorkerPool(
                    addrs + extra, timeout_s=10.0
                ).shutdown_all()
            except Exception:
                pass
    except Exception as e:
        record["fleet_family_error"] = f"{type(e).__name__}: {e}"


def measure_distributed_family(rows, trees, depth, features, record):
    """Distributed training measurement (ROADMAP item 2's bench half),
    gated on YDF_TPU_BENCH_DIST_WORKERS=N (N >= 2): spins N in-process
    localhost workers, streams the bench table into a sharded dataset
    cache, trains the same (trees, depth) GBT through the
    manager–worker exchange, and records

      dist_mode               {feature,row,hybrid} — the sharding mode
                              (YDF_TPU_BENCH_DIST_MODE, default
                              feature; part of the bench-diff pairing
                              shape so modes never cross-compare)
      dist_workers            worker count
      dist_train_s            steady-state distributed train wall
      dist_reduce_bytes       total histogram bytes reduced at the
                              manager (feature mode: f32 slices; row
                              mode: accumulation-domain f64 partials)
      dist_reduce_bytes_per_layer   the per-layer average of the same
      dist_merge_s            manager-side histogram merge wall
                              (row-mode fixed-order sum / feature-mode
                              concat), summed over layers
      dist_shard_rows         rows per row shard (row/hybrid; rows for
                              feature mode — every worker holds all)
      dist_shard_bytes        fleet-total resident worker shard/state
      dist_shard_bytes_per_worker   ... and the per-worker maximum —
                              row mode's ~1/N-of-the-bin-matrix memory
                              contract, straight from the workers'
                              `dist_shard` ledger reports
      dist_rpc_p50_ns         per-verb RPC p50 from the run's latency
                              histograms (telemetry-keyed by verb)
      dist_recoveries         reassignments the run needed (0 healthy)
      dist_snapshot_s         manager tree-boundary snapshot wall (the
                              preemption-safe round: the bench train
                              runs with a working_dir so the durable
                              forest-so-far snapshot the resume
                              contract depends on is part of the
                              measured protocol cost)
      dist_compute_s          per-layer wall attribution, summed over
      dist_net_s              the run: compute (worker kernels +
      dist_wait_s             manager search), network (median RPC −
                              median worker handle), straggler wait
                              (slowest − median histogram RPC); the
                              three sum to dist_layer_wall_s
                              (docs/observability.md)
      dist_layer_wall_s       summed measured per-layer wall

    on the headline record. In-process workers measure PROTOCOL cost
    (serialization, reduction, routing exchange) — they share this
    box's core, so dist_train_s is an overhead figure, not a scaling
    figure; a multi-host run is where speedup appears
    (docs/distributed_training.md). Failures recorded, never fatal."""
    env = os.environ.get("YDF_TPU_BENCH_DIST_WORKERS")
    if not env:
        return
    try:
        nw = int(env)
        if nw < 2:
            raise ValueError
    except ValueError:
        record["dist_family_error"] = (
            f"YDF_TPU_BENCH_DIST_WORKERS={env!r} must be an integer >= 2"
        )
        return
    mode = (
        os.environ.get("YDF_TPU_BENCH_DIST_MODE", "").strip().lower()
        or "feature"
    )
    if mode not in ("feature", "row", "hybrid"):
        record["dist_family_error"] = (
            f"YDF_TPU_BENCH_DIST_MODE={mode!r} must be one of "
            "feature/row/hybrid"
        )
        return
    try:
        import socket as _socket
        import tempfile

        import numpy as np

        import ydf_tpu as ydf
        from ydf_tpu.config import Task
        from ydf_tpu.dataset.cache import create_dataset_cache
        from ydf_tpu.parallel.worker_service import (
            WorkerPool,
            start_worker,
        )

        rng = np.random.RandomState(0xD157)
        x, y = synth_higgs_chunk(rng, rows, features)
        frame = {f"f{i}": x[:, i] for i in range(features)}
        frame["label"] = y
        ports = []
        for _ in range(nw):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            s.close()
        for p in ports:
            start_worker(p, host="127.0.0.1", blocking=False)
        addrs = [f"127.0.0.1:{p}" for p in ports]
        with tempfile.TemporaryDirectory() as td:
            shard_kw = {"feature_shards": nw}
            if mode == "row":
                shard_kw = {"row_shards": nw}
            elif mode == "hybrid":
                # R×C grid sized to the fleet: 2 row groups × the rest
                # as column groups.
                shard_kw = {
                    "row_shards": 2, "feature_shards": max(nw // 2, 2),
                }
            cache = create_dataset_cache(
                frame, os.path.join(td, "cache"), label="label",
                task=Task.CLASSIFICATION, **shard_kw,
            )

            def train_dist(run):
                # A working_dir per run arms the tree-boundary
                # snapshot machinery (at least the final boundary's
                # durable snapshot) — dist_snapshot_s measures it.
                learner = ydf.GradientBoostedTreesLearner(
                    label="label", num_trees=trees, max_depth=depth,
                    validation_ratio=0.0, early_stopping="NONE",
                    distributed_workers=addrs,
                    working_dir=os.path.join(td, f"wd_{run}"),
                )
                t0 = time.time()
                model = learner.train(cache)
                return model, time.time() - t0

            train_dist(0)                  # compile + shard placement
            model, wall = train_dist(1)    # steady state
            d = model.training_logs["distributed"]
            record["dist_mode"] = d.get("mode", "feature")
            record["dist_workers"] = nw
            record["dist_train_s"] = round(wall, 2)
            record["dist_reduce_bytes"] = int(d["reduce_bytes"])
            record["dist_reduce_bytes_per_layer"] = round(
                d["reduce_bytes"] / max(trees * depth, 1), 1
            )
            record["dist_merge_s"] = round(d.get("merge_s", 0.0), 4)
            record["dist_shard_rows"] = int(d.get("shard_rows", rows))
            record["dist_rpc_p50_ns"] = d["rpc_p50_ns"]
            record["dist_recoveries"] = int(d["recoveries"])
            record["dist_snapshot_s"] = round(
                d.get("snapshot_s", 0.0), 4
            )
            # Fleet-total resident shard/state bytes the workers
            # reported at shard load — the distributed row of the
            # memory headline (docs/observability.md) — plus the
            # per-worker maximum: row mode's memory contract is that
            # each worker holds ~1/N of the single-machine bin matrix
            # (streamed loads, no full-slice materialization).
            record["dist_shard_bytes"] = int(d.get("shard_bytes", 0))
            per_worker = d.get("worker_shard_bytes") or {}
            record["dist_shard_bytes_per_worker"] = int(
                max(per_worker.values()) if per_worker
                else d.get("shard_bytes", 0)
            )
            record["dist_compute_s"] = round(d["compute_s"], 3)
            record["dist_net_s"] = round(d["net_s"], 3)
            record["dist_wait_s"] = round(d["wait_s"], 3)
            record["dist_layer_wall_s"] = round(d["layer_wall_s"], 3)
            # Transport-overhaul fields (mirrors the fleet family's
            # rpc_* under the dist_ prefix): per-run TCP connects and
            # reuse over the manager's pooled worker connections, and
            # the wire split between pickled headers and zero-copy
            # array segments.
            record["dist_rpc_connects"] = int(d.get("rpc_connects", 0))
            record["dist_rpc_conn_reuse_rate"] = float(
                d.get("rpc_conn_reuse_rate", 0.0)
            )
            record["dist_rpc_header_bytes"] = int(
                d.get("rpc_header_bytes", 0)
            )
            record["dist_rpc_payload_bytes"] = int(
                d.get("rpc_payload_bytes", 0)
            )
        try:
            WorkerPool(addrs).shutdown_all()
        except Exception:
            pass
    except Exception as e:
        record["dist_family_error"] = f"{type(e).__name__}: {e}"


def measure_cache_build_family(rows, features, record):
    """Dataset-cache build measurement (the distributed-ingest round's
    bench half), gated on YDF_TPU_BENCH_CACHE_WORKERS=N (N >= 2): streams
    the bench table to CSV once, then records

      cache_build_s               single-machine create_dataset_cache
                                  wall (CSV -> binned shards + meta)
      cache_build_peak_rss_bytes  process peak RSS right after the
                                  single-machine build — the streaming
                                  ingest's memory headline
      sketch_bytes                total pass-1 state of a sketch-mode
                                  ingest over the same stream (the bytes
                                  a worker ships the manager per
                                  partial; exact mode ships the raw
                                  distinct-or-spill summaries instead)
      sketch_rank_error           max measured rank error of the
                                  sketch across features (vs the raw
                                  sorted columns), with the max
                                  certified per-instance bound beside
                                  it (sketch_rank_error_bound) and the
                                  within-bound verdict
                                  (sketch_rank_error_within_bound) —
                                  the acceptance read that the bound
                                  documented in docs/binning_pipeline
                                  holds on real bench data
      sketch_split_max_drift      max quantile-space drift of
                                  sketch-derived bin boundaries vs the
                                  exact build's boundaries (split
                                  parity, docs/distributed_training.md
                                  "Distributed cache build")
      dist_cache_build_s          distributed build wall through N
                                  in-process localhost workers (ingest
                                  exchange + bin/shard-write exchange +
                                  commit) — protocol cost, not a
                                  scaling figure, same caveat as
                                  dist_train_s
      dist_cache_build_workers    worker count
      dist_cache_peak_worker_build_bytes
                                  fleet-max per-worker transient from
                                  the build's commit record — the
                                  ~1/N-of-the-bin-matrix memory
                                  contract, MemoryLedger-asserted by
                                  tests/test_dist_cache.py

    on the headline record. Failures recorded, never fatal."""
    env = os.environ.get("YDF_TPU_BENCH_CACHE_WORKERS")
    if not env:
        return
    try:
        nw = int(env)
        if nw < 2:
            raise ValueError
    except ValueError:
        record["cache_build_family_error"] = (
            f"YDF_TPU_BENCH_CACHE_WORKERS={env!r} must be an integer >= 2"
        )
        return
    try:
        import socket as _socket
        import tempfile

        import numpy as np

        from ydf_tpu.config import Task
        from ydf_tpu.dataset.cache import (
            _always_categorical,
            _iter_chunks,
            create_dataset_cache,
        )
        from ydf_tpu.dataset.sketch import IngestPartial
        from ydf_tpu.parallel.dist_cache import (
            create_dataset_cache_distributed,
        )
        from ydf_tpu.parallel.worker_service import (
            WorkerPool,
            start_worker,
        )
        from ydf_tpu.utils import telemetry

        rng = np.random.RandomState(0xCACE)
        x, y = synth_higgs_chunk(rng, rows, features)
        chunk_rows = max(rows // 8, 1)
        with tempfile.TemporaryDirectory() as td:
            csv_path = os.path.join(td, "bench.csv")
            cols = [f"f{i}" for i in range(features)] + ["label"]
            with open(csv_path, "w") as f:
                f.write(",".join(cols) + "\n")
                for r in range(rows):
                    f.write(
                        ",".join(repr(float(v)) for v in x[r])
                        + f",{int(y[r])}\n"
                    )

            t0 = time.time()
            single = create_dataset_cache(
                csv_path, os.path.join(td, "single"), label="label",
                task=Task.CLASSIFICATION, chunk_rows=chunk_rows,
            )
            record["cache_build_s"] = round(time.time() - t0, 3)
            record["cache_build_peak_rss_bytes"] = int(
                telemetry.peak_rss_bytes()
            )

            # Sketch-mode pass-1 footprint over the same stream: what a
            # worker's per-chunk partial costs on the wire when
            # boundaries="sketch" (bounded by O(k log n) per feature,
            # vs. the unbounded distinct-value spill of exact mode).
            always_cat = _always_categorical(
                "label", Task.CLASSIFICATION, None
            )
            partial = IngestPartial(mode="sketch", sketch_k=4096)
            raw_cols = {}
            for chunk in _iter_chunks([csv_path], chunk_rows):
                partial.observe_chunk(chunk, always_cat)
                for cname, cvals in chunk.items():
                    if cname != "label":
                        raw_cols.setdefault(cname, []).append(
                            np.asarray(cvals, np.float64)
                        )
            record["sketch_bytes"] = int(partial.nbytes())

            # Measured sketch quality vs the raw columns: max rank
            # error across features against each summary's certified
            # per-instance bound, and the quantile-space drift of
            # sketch-derived boundaries vs the exact build's — the
            # split-parity evidence the sketch mode documents.
            from ydf_tpu.dataset.binning import boundaries_from_sketch

            max_err = max_bound = max_drift = 0.0
            for i, name in enumerate(single.binner.feature_names):
                s = partial.num.get(name)
                if s is None or name not in raw_cols:
                    continue
                # Ranks measured against the PARSED column (the stream
                # the sketch actually saw — the CSV parse can differ
                # from the pre-write array in the last ulp).
                col = np.sort(np.concatenate(raw_cols[name]))
                col = col[np.isfinite(col)]
                v, w = s.weighted_items()
                est = np.cumsum(w) - w / 2.0
                lo = np.searchsorted(col, v, side="left")
                hi = np.searchsorted(col, v, side="right")
                err = np.maximum(np.maximum(lo - est, est - hi), 0)
                max_err = max(
                    max_err, float(err.max() / max(col.size, 1))
                )
                max_bound = max(max_bound, s.rank_error_bound())
                nb = int(single.binner.feature_num_bins[i])
                sk_b = boundaries_from_sketch(
                    v, w, nb, s.distinct_exact()
                )
                ex_b = single.binner.boundaries[i, : nb - 1]
                m = min(sk_b.size, ex_b.size)
                if m:
                    qe = np.searchsorted(col, ex_b[:m]) / col.size
                    qs = np.searchsorted(col, sk_b[:m]) / col.size
                    max_drift = max(
                        max_drift, float(np.abs(qe - qs).max())
                    )
            record["sketch_rank_error"] = round(max_err, 6)
            record["sketch_rank_error_bound"] = round(max_bound, 6)
            record["sketch_rank_error_within_bound"] = bool(
                max_err <= max_bound
            )
            record["sketch_split_max_drift"] = round(max_drift, 6)

            ports = []
            for _ in range(nw):
                s = _socket.socket()
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
                s.close()
            for p in ports:
                start_worker(p, host="127.0.0.1", blocking=False)
            addrs = [f"127.0.0.1:{p}" for p in ports]
            try:
                t0 = time.time()
                dist = create_dataset_cache_distributed(
                    csv_path, os.path.join(td, "dist"), label="label",
                    workers=addrs, task=Task.CLASSIFICATION,
                    chunk_rows=chunk_rows,
                )
                record["dist_cache_build_s"] = round(time.time() - t0, 3)
                record["dist_cache_build_workers"] = nw
                build = dist._meta.get("build") or {}
                record["dist_cache_peak_worker_build_bytes"] = int(
                    build.get("peak_worker_build_bytes", 0)
                )
            finally:
                try:
                    WorkerPool(addrs).shutdown_all()
                except Exception:
                    pass
    except Exception as e:
        record["cache_build_family_error"] = f"{type(e).__name__}: {e}"


#: Per-thread-count probe run by measure_core_scaling in a FRESH
#: subprocess. It has to be a subprocess: the thread pool's lane count
#: (and its NUMA block placement) is resolved ONCE at singleton
#: creation, so sweeping T requires the YDF_TPU_*_THREADS envs to be set
#: BEFORE the first ydf_tpu import — exactly the boundary
#: tests/test_pool_scaling.py exercises. The probe times each of the
#: four pool families at a fixed shape (best-of-3 steady walls, warmup
#: excluded) and prints ONE machine-readable line with the walls and the
#: pool's own counters.
_CORE_SCALING_DRIVER = r"""
import ctypes
import json
import os
import time

import numpy as np

n = int(os.environ["YDF_TPU_CS_ROWS"])
F = int(os.environ["YDF_TPU_CS_FEATURES"])

import jax.numpy as jnp
from ydf_tpu.ops import pool_stats
from ydf_tpu.ops.histogram import histogram
from ydf_tpu.ops.native_ffi import KERNELS_LIB

lib = KERNELS_LIB.load()
assert lib is not None, "native kernels unavailable"

rng = np.random.default_rng(0)
L, B = 8, 64
bins = rng.integers(0, B, (n, F), dtype=np.int64).astype(np.uint8)
slot = rng.integers(0, L, n).astype(np.int32)
stats = rng.standard_normal((n, 3)).astype(np.float32)
jbins, jslot, jstats = map(jnp.asarray, (bins, slot, stats))


def best_of(fn, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def family(name, fn):
    fn()  # warmup: compile, page in, resolve the pool
    pool_stats.reset_pool_stats()
    w = best_of(fn)
    s = pool_stats.pool_stats()["families"][name]
    return {
        "wall_s": round(w, 5),
        "pool_utilization": s["utilization"],
        "engaged_utilization": s["engaged_utilization"],
        "steals": s["steals"],
        "straggler_wait_ns": s["straggler_wait_ns"],
    }


out = {"families": {}}

out["families"]["hist"] = family("hist", lambda: np.asarray(
    histogram(jbins, jslot, jstats, num_slots=L, num_bins=B,
              impl="native")))

mb = 255
vals = rng.standard_normal((F, n)).astype(np.float32)
bounds = np.sort(rng.standard_normal((F, mb)).astype(np.float32), axis=1)
nbounds = np.full(F, mb, np.int32)
imp = np.zeros(F, np.float32)
bout = np.empty((n, F), np.uint8)


def run_bin():
    lib.ydf_bin_columns(
        vals.ctypes.data_as(ctypes.c_void_p),
        bounds.ctypes.data_as(ctypes.c_void_p),
        nbounds.ctypes.data_as(ctypes.c_void_p),
        imp.ctypes.data_as(ctypes.c_void_p),
        bout.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n), ctypes.c_int64(F), ctypes.c_int64(mb),
        ctypes.c_int64(F), ctypes.c_int32(0))


out["families"]["bin"] = family("bin", run_bin)

# Standalone per-layer routing pass over synthetic split tables (the
# same construction tests/test_routing_native.py proves correct);
# bins_t is the FEATURE-major transpose the kernel consumes.
from ydf_tpu.ops import routing_native

bins_t = jnp.asarray(np.ascontiguousarray(bins.T))
leaf = rng.integers(0, 15, n).astype(np.int32)
do_split = rng.random(L + 1) < 0.7
do_split[L] = False
route_f = rng.integers(0, F, L + 1).astype(np.int32)
go_left = rng.random((L + 1, B)) < 0.5
left_id = rng.integers(0, 15, L + 1).astype(np.int32)
right_id = rng.integers(0, 15, L + 1).astype(np.int32)
split_rank = np.minimum(
    np.cumsum(do_split) - 1, L // 2 - 1
).clip(0).astype(np.int32)
hmap = np.arange(L + 1, dtype=np.int32)
is_set = np.zeros(L + 1, np.uint8)
set_gl = np.zeros(1, np.uint8)
rargs = [jnp.asarray(a) for a in (
    slot, leaf, do_split, route_f, go_left, left_id, right_id,
    split_rank, hmap, is_set, set_gl)]
out["families"]["route"] = family("route", lambda: [
    np.asarray(o)
    for o in routing_native.route_update(bins_t, *rargs)])

# Serving through the native ctypes engine of a small trained model,
# batch tiled up to the probe's row count (many 512-row serve blocks).
import pandas as pd
import ydf_tpu as ydf
from ydf_tpu.config import Task
from ydf_tpu.dataset.dataset import Dataset
from ydf_tpu.serving import native_serve

rs = np.random.RandomState(3)
df = pd.DataFrame({f"g{i}": rs.normal(size=4000) for i in range(5)})
df["y"] = (df["g0"] + df["g1"] * df["g2"]).astype(np.float32)
m = ydf.GradientBoostedTreesLearner(
    label="y", task=Task.REGRESSION, num_trees=20, max_depth=6,
    validation_ratio=0.0, early_stopping="NONE",
).train(df)
ds = Dataset.from_data(df, dataspec=m.dataspec)
x_num, x_cat, _ = m._encode_inputs(ds)
eng = native_serve.build_native_engine(m)
assert eng is not None, "native serve engine unavailable"
reps = max(1, n // len(df))
x_num = np.ascontiguousarray(np.tile(x_num, (reps, 1)))
if x_cat is not None:
    x_cat = np.ascontiguousarray(np.tile(x_cat, (reps, 1)))
out["families"]["serve"] = family(
    "serve", lambda: np.asarray(eng(x_num, x_cat)))

out["pool_size"] = pool_stats.pool_size()
print("CORE_SCALING_JSON " + json.dumps(out))
"""


def measure_core_scaling(rows, features, record):
    """Core-scaling bench family (the many-core round's headline
    instrument): sweeps the four pool families {hist, bin, route, serve}
    across thread counts T in {1, 2, 4, ..., nproc}, each T a FRESH
    subprocess with every YDF_TPU_*_THREADS env set to T before import
    (the pool's lane count resolves once per process). Emits, under
    record["core_scaling"], per-family curves keyed by str(T):

      wall_s               best-of-3 steady wall at the probe shape
      scaling_speedup      wall(1) / wall(T)
      parallel_efficiency  scaling_speedup / T
      pool_utilization     busy / (ALL lanes × wall) at that T
      engaged_utilization  busy / (engaged lanes × wall) at that T
      steals               work-stealing count over the measured reps

    On a 1-core box the sweep degrades to T = [1]: the curves have one
    point, the counters are still real, and nothing fails — the
    graceful-degradation half of the acceptance bar. Gate with
    YDF_TPU_BENCH_CORE_SCALING=off. Failures recorded, never fatal."""
    gate = os.environ.get(
        "YDF_TPU_BENCH_CORE_SCALING", "auto"
    ).strip().lower()
    if gate == "off":
        return
    if gate not in ("", "auto", "on"):
        record["core_scaling_error"] = (
            f"YDF_TPU_BENCH_CORE_SCALING={gate!r} must be auto|on|off"
        )
        return
    try:
        ncpu = os.cpu_count() or 1
        counts, t = [], 1
        while t < ncpu:
            counts.append(t)
            t *= 2
        counts.append(ncpu)
        counts = sorted(set(counts))
        # Smaller than the headline shape: the probe runs once per T and
        # the scaling read needs enough blocks per lane (32k-row blocks)
        # at the largest T, not maximal wall.
        sub_rows = max(131_072, min(rows, 400_000))
        by_family = {}
        pool_size_by_t = {}
        for T in counts:
            env = dict(
                os.environ,
                JAX_PLATFORMS="cpu",
                YDF_TPU_CS_ROWS=str(sub_rows),
                YDF_TPU_CS_FEATURES=str(features),
            )
            for fam in ("HIST", "BIN", "ROUTE", "SERVE"):
                env[f"YDF_TPU_{fam}_THREADS"] = str(T)
            out = subprocess.run(
                [sys.executable, "-c", _CORE_SCALING_DRIVER],
                capture_output=True, text=True, timeout=900,
                cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
            )
            lines = [
                ln for ln in out.stdout.splitlines()
                if ln.startswith("CORE_SCALING_JSON ")
            ]
            if not lines:
                record["core_scaling_error"] = (
                    f"T={T}: rc={out.returncode} "
                    f"stderr={out.stderr[-400:]!r}"
                )
                return
            data = json.loads(lines[-1][len("CORE_SCALING_JSON "):])
            pool_size_by_t[str(T)] = data["pool_size"]
            for fam, f in data["families"].items():
                by_family.setdefault(fam, {})[str(T)] = f
        curves = {}
        for fam, by_t in by_family.items():
            wall_1 = by_t.get("1", {}).get("wall_s")
            cur = {
                "wall_s": {}, "scaling_speedup": {},
                "parallel_efficiency": {}, "pool_utilization": {},
                "engaged_utilization": {}, "steals": {},
            }
            for ts, f in sorted(by_t.items(), key=lambda kv: int(kv[0])):
                T = int(ts)
                cur["wall_s"][ts] = f["wall_s"]
                if wall_1 and f["wall_s"] > 0:
                    speedup = wall_1 / f["wall_s"]
                    cur["scaling_speedup"][ts] = round(speedup, 3)
                    cur["parallel_efficiency"][ts] = round(
                        speedup / T, 3
                    )
                cur["pool_utilization"][ts] = f["pool_utilization"]
                cur["engaged_utilization"][ts] = f["engaged_utilization"]
                cur["steals"][ts] = f["steals"]
            curves[fam] = cur
        record["core_scaling"] = {
            "thread_counts": counts,
            "rows": sub_rows,
            "pool_size": pool_size_by_t,
            "families": curves,
        }
        # Flat copies of the highest-T numbers for the two headline
        # families, so bench_diff's flatten (one nesting level) sees
        # them: the acceptance read is parallel_efficiency >= 0.7 at
        # the highest core count for {hist, serve} on a many-core box.
        top = str(counts[-1])
        for fam in ("hist", "serve"):
            eff = curves.get(fam, {}).get("parallel_efficiency", {})
            if top in eff:
                record.setdefault("scaling_speedup", {})[fam] = (
                    curves[fam]["scaling_speedup"][top]
                )
                record.setdefault("parallel_efficiency", {})[fam] = (
                    eff[top]
                )
    except Exception as e:
        record["core_scaling_error"] = f"{type(e).__name__}: {e}"


def synth_higgs_chunk(rng, rows, features):
    """One chunk of the synthetic Higgs-shaped table — the ONE label
    model shared by the bench rows and the north-star flow, so their AUC
    numbers stay comparable."""
    import numpy as np

    x = rng.normal(size=(rows, features)).astype(np.float32)
    logit = x[:, 0] - 0.5 * x[:, 1] + np.sin(2 * x[:, 2]) + x[:, 3] * x[:, 4]
    y = (rng.uniform(size=rows) < 1 / (1 + np.exp(-logit))).astype(np.int64)
    return x, y


def make_data(rows, features):
    import numpy as np

    x, y = synth_higgs_chunk(np.random.RandomState(0), rows, features)
    data = {f"f{i}": x[:, i] for i in range(features)}
    data["label"] = y
    return data, x, y


def run_bench(rows, trees, depth, features, with_baseline):
    """Train twice (compile, then cached) and assemble the record.

    Ingestion is measured explicitly: the raw columns are converted to a
    Dataset ONCE (`ingest_s`, dataspec inference included) and both
    train() calls take that Dataset — so the steady-state call hits the
    Dataset-level bin cache (dataset/binning.py), exactly like a tuner
    or CV loop. `bin_s` is the COLD fit+transform cost from the first
    call's learner timings; both fields ride the headline record so the
    trajectory tracks the fused-binning target."""
    import ydf_tpu as ydf
    from ydf_tpu.dataset.dataset import Dataset
    from ydf_tpu.dataset.dataspec import ColumnType

    data, x, y = make_data(rows, features)
    t0 = time.time()
    ds = Dataset.from_data(
        data, label="label",
        column_types={"label": ColumnType.CATEGORICAL},
    )
    ingest_s = time.time() - t0

    def train():
        learner = ydf.GradientBoostedTreesLearner(
            label="label",
            num_trees=trees,
            max_depth=depth,
            validation_ratio=0.0,
            early_stopping="NONE",
        )
        t0 = time.time()
        model = learner.train(ds)
        return model, time.time() - t0, model.training_profile

    from ydf_tpu.ops import device_loop

    _, wall_compile, cold = train()  # compile + cold ingest/bin
    bin_s = cold["ingest_bin.binner_fit"] + cold["ingest_bin.transform"]
    device_loop.reset_stats()
    model, wall, _ = train()                 # cached steady state
    dl_snap = device_loop.stats_snapshot()
    # Process peak RSS right after the steady-state train: the training
    # half of the memory headline (an absolute process-lifetime figure —
    # the compile pass above is included by construction, which is the
    # honest bound a box must provision for).
    try:
        from ydf_tpu.utils.telemetry import peak_rss_bytes

        train_peak_rss = int(peak_rss_bytes())
    except Exception:
        train_peak_rss = 0

    from ydf_tpu.ops.histogram import resolve_hist_quant
    from ydf_tpu.ops.routing_native import (
        resolve_route_impl,
        resolved_route_threads,
    )

    def _resolved_env_threads(env_name):
        try:
            v = int(os.environ.get(env_name, "0"))
        except ValueError:
            v = 0
        return v if v > 0 else (os.cpu_count() or 1)

    value = rows * trees / wall
    platform = device_fields()["platform"]
    record = {
        "metric": "gbt_train_rows_x_trees_per_sec",
        "value": round(value, 1),
        "unit": "rows*trees/s",
        # Repeats the `platform` emit() stamps: scripts/bench_diff.py
        # pairs records on `backend`.
        "backend": platform,
        "rows": rows,
        "trees": trees,
        "depth": depth,
        "train_wall_s": round(wall, 2),
        "train_wall_incl_compile_s": round(wall_compile, 2),
        # Cold-path attribution of the ingest+bin term (the round-6
        # fused-binning target), from the cold train()'s profile:
        # dataset construction + the rest of `ingest_bin` (dataspec,
        # label/weight encode), and Binner fit+transform, in seconds.
        "ingest_s": round(ingest_s + cold["ingest_bin"] - bin_s, 3),
        "bin_s": round(bin_s, 3),
        # Active gradient-quantization mode (YDF_TPU_HIST_QUANT): every
        # headline record names it so quantized and exact trajectories
        # can never be conflated.
        "hist_quant": resolve_hist_quant(None),
        # Active example-routing impl (YDF_TPU_ROUTE_IMPL) and the
        # native thread caps the kernels will resolve — a many-core host
        # shows the persistent pool compounding across the histogram AND
        # routing kernels (ROADMAP multi-core wave validation,
        # measurement side).
        "route_impl": resolve_route_impl(None),
        "route_threads": resolved_route_threads(),
        "hist_threads": _resolved_env_threads("YDF_TPU_HIST_THREADS"),
        "bin_threads": _resolved_env_threads("YDF_TPU_BIN_THREADS"),
        "serve_threads": _resolved_env_threads("YDF_TPU_SERVE_THREADS"),
        "train_peak_rss_bytes": train_peak_rss,
        # Device-resident loop accounting (ops/device_loop.py window
        # around the steady train): XLA dispatches and host-materialized
        # bytes per boosting tree.
        "dispatches_per_tree": dl_snap["dispatches_per_tree"],
        "host_sync_bytes_per_tree": dl_snap["host_sync_bytes_per_tree"],
        "vs_ydf64_estimate": round(
            value / BASELINE_YDF64_ESTIMATE_ROWS_TREES_PER_SEC, 3
        ),
    }
    if with_baseline:
        base, source = measure_sklearn_baseline(x, y, trees, depth)
        if base:
            record["baseline_rows_trees_per_sec"] = round(base, 1)
            record["baseline_source"] = source
            record["vs_baseline"] = round(value / base, 3)
    record.setdefault("vs_baseline", record["vs_ydf64_estimate"])
    # Histogram timing, two ways on every headline record: `hist_s` is
    # the REAL in-loop op time (profiler trace / native kernel counter,
    # one extra steady train), `hist_attrib_s` the historical same-shape
    # attribution outside the scan (trajectory continuity with pre-PR-3
    # records, where this field was named hist_s).
    measure_in_loop_hist(train, record)
    measure_hist_attribution(rows, features, depth, trees, record)
    try:
        # Batched inference throughput on the same model (reference
        # benchmark_inference.cc's ns/example) — any backend; reuses the
        # warmup + best-of-runs measurement in model.benchmark().
        # p50/p99 come from the serving latency histogram
        # (utils/telemetry.LatencyHistogram over the per-run walls) —
        # the percentile guard ROADMAP item 1 (serving at traffic)
        # regresses against, next to the historical best-of-runs floor.
        n_inf = min(rows, 100_000)
        sample = {k: v[:n_inf] for k, v in data.items()}
        bres = model.benchmark(sample, num_runs=10)
        record["infer_ns_per_example"] = round(bres["ns_per_example"], 1)
        record["infer_p50_ns"] = round(bres["p50_ns_per_example"], 1)
        record["infer_p99_ns"] = round(bres["p99_ns_per_example"], 1)
        # Serving memory guard: how much the process RSS peak grew
        # across the measured (post-warmup) predict runs — a serving
        # path that allocates per call regresses HERE, under the same
        # per-shape floor machinery as the latency guard.
        record["infer_peak_rss_delta_bytes"] = int(
            bres.get("peak_rss_delta_bytes", 0)
        )
        rss_floor = INFER_RSS_DELTA_FLOOR_BYTES.get((rows, trees))
        if rss_floor is not None:
            record["infer_rss_delta_floor_bytes"] = rss_floor
            record["infer_rss_within_floor"] = bool(
                record["infer_peak_rss_delta_bytes"] <= rss_floor
            )
        # Serving-regression guard (ROADMAP item 1): compare against the
        # recorded same-shape floor — floors at different (rows, trees)
        # shapes are NOT comparable (the r04→r05 "regression" was a
        # shape confound, see INFER_P50_FLOOR_NS).
        floor = INFER_P50_FLOOR_NS.get((rows, trees))
        if floor is not None and platform == "cpu":
            record["infer_p50_floor_ns"] = floor
            record["infer_p50_within_floor"] = bool(
                record["infer_p50_ns"] <= floor
            )
    except Exception as e:
        record["infer_extra_error"] = f"{type(e).__name__}: {e}"
    # Serving bench family: per-engine QPS + p50/p99 per batch size, and
    # which engine actually serves (serve_engine) — rides every headline
    # record (ROADMAP item 1's "millions of users" measurement).
    measure_serving_family(model, data, rows, record)
    # Serving-under-load family: sustained QPS + coordinated-omission-
    # safe open-loop tail through the bounded request batcher.
    measure_serving_load_family(model, data, rows, record)
    # Serving-fleet family: replica pool over the worker substrate,
    # sustained QPS across a mid-run versioned hot-swap.
    measure_fleet_family(model, data, rows, record)
    # Distributed-training family (ROADMAP item 2's measurement half):
    # only runs when YDF_TPU_BENCH_DIST_WORKERS is set.
    measure_distributed_family(rows, trees, depth, features, record)
    # Cache-build family (distributed-ingest round's measurement half):
    # only runs when YDF_TPU_BENCH_CACHE_WORKERS is set.
    measure_cache_build_family(rows, features, record)
    # Core-scaling family (many-core round): per-family speedup /
    # efficiency curves over thread counts {1,2,4,...,nproc}, each count
    # a fresh subprocess so the pool re-resolves its lane count.
    measure_core_scaling(rows, features, record)
    if platform != "cpu":
        hardware_extras(model, data, record)
    return record, model


def north_star(rows, trees, depth, features, workdir=None):
    """The north-star benchmark as ONE command (VERDICT r4 #4):
    Higgs-shaped data streamed to an on-disk binned cache
    (dataset/cache.py, out-of-core), GBT trained FROM the cache with
    periodic checkpoints (crash-safe greatest-snapshot protocol), over a
    device mesh when more than one device exists, AUC on a held-out
    slice. Defaults match the Higgs-11M config (BASELINE.json config 3 /
    ref distributed_gradient_boosted_trees.cc:233); --rows/--trees give
    the CPU-scale validation. Emits one JSON line."""
    import shutil
    import tempfile

    t_all = time.time()
    base = workdir or tempfile.mkdtemp(prefix="ydf_north_star_")
    try:
        return _north_star_inner(
            rows, trees, depth, features, base, t_all
        )
    finally:
        # The CSV shards + cache are multi-GB at full scale — never leak
        # them, even when a signal/exception cuts the run short.
        if workdir is None:
            shutil.rmtree(base, ignore_errors=True)


def _north_star_inner(rows, trees, depth, features, base, t_all):
    import jax
    import numpy as np

    import ydf_tpu as ydf
    from ydf_tpu.dataset.cache import create_dataset_cache
    from ydf_tpu.metrics import roc_auc

    csv_dir = os.path.join(base, "csv")
    cache_dir = os.path.join(base, "cache")
    ckpt_dir = os.path.join(base, "ckpt")
    for d in (csv_dir, ckpt_dir):
        os.makedirs(d, exist_ok=True)

    # --- stream the Higgs-shaped table to CSV shards (the cache's
    # supported ingestion format), chunked so peak memory stays ~100 MB
    # no matter how many rows. Same label model as the bench rows
    # (synth_higgs_chunk) so AUCs are comparable.
    def gen_chunk(rng, m):
        return synth_higgs_chunk(rng, m, features)

    import pandas as pd

    rng = np.random.RandomState(0)
    chunk = 1_000_000
    shard = 0
    t0 = time.time()
    for start in range(0, rows, chunk):
        m = min(chunk, rows - start)
        x, y = gen_chunk(rng, m)
        df = pd.DataFrame(
            {f"f{i}": x[:, i] for i in range(features)} | {"label": y}
        )
        df.to_csv(
            os.path.join(csv_dir, f"shard-{shard:05d}.csv"),
            index=False, float_format="%.6g",
        )
        shard += 1
    x_te, y_te = gen_chunk(rng, min(100_000, max(rows // 10, 1000)))
    t_gen = time.time() - t0

    t0 = time.time()
    cache = create_dataset_cache(
        f"csv:{csv_dir}/shard-*.csv", cache_dir, label="label",
        chunk_rows=500_000,
    )
    t_cache = time.time() - t0

    devices = jax.devices()
    mesh = None
    if len(devices) > 1:
        from ydf_tpu.parallel import make_mesh

        mesh = make_mesh(
            devices, feature_parallelism=2 if len(devices) % 2 == 0 else 1
        )

    t0 = time.time()
    model = ydf.GradientBoostedTreesLearner(
        label="label", num_trees=trees, max_depth=depth,
        validation_ratio=0.0, early_stopping="NONE", mesh=mesh,
        working_dir=ckpt_dir, resume_training_snapshot_interval_trees=50,
    ).train(cache)
    t_train = time.time() - t0

    test = {f"f{i}": x_te[:, i] for i in range(features)}
    # predict() scores classes[1]; the cache's label dictionary is
    # frequency-sorted, so orient the held-out labels to it explicitly.
    pos = str(model.classes[1])
    auc = float(
        roc_auc(
            (y_te.astype(str) == pos).astype(np.int32),
            np.asarray(model.predict(test)),
        )
    )
    rec = {
        "metric": "north_star_gbt_rows_x_trees_per_sec",
        "value": round(rows * trees / t_train, 1),
        "unit": "rows*trees/s",
        "backend": jax.default_backend(),
        "rows": rows,
        "trees": trees,
        "depth": depth,
        "auc": round(auc, 4),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "gen_wall_s": round(t_gen, 1),
        "cache_build_wall_s": round(t_cache, 1),
        "train_wall_s": round(t_train, 1),
        "total_wall_s": round(time.time() - t_all, 1),
        "checkpoints": "every 50 trees (greatest-snapshot protocol)",
    }
    emit(rec)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--cpu", action="store_true",
        help="run on the CPU backend; without it the default backend is "
        "used and a machine with no accelerator is an error",
    )
    ap.add_argument("--small", action="store_true", help="tiny smoke config")
    ap.add_argument(
        "--north-star", action="store_true",
        help="one-command Higgs-11M flow: out-of-core cache + checkpointed "
        "(+mesh when multi-device) training + AUC; --rows/--trees scale "
        "it down for CPU validation",
    )
    ap.add_argument("--workdir", default=None,
                    help="north-star scratch dir (kept when given)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--trees", type=int, default=None)
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the sklearn same-shape baseline measurement")
    args = ap.parse_args()

    if args.cpu:
        use_cpu()
    from ydf_tpu.config import enable_compile_cache

    enable_compile_cache()
    platform = device_fields()["platform"]
    if platform == "cpu" and not args.cpu:
        sys.exit(
            "bench.py: JAX found no accelerator (platform 'cpu'). Pass "
            "--cpu to measure the CPU backend explicitly."
        )

    if args.north_star:
        north_star(
            rows=args.rows or 11_000_000,
            trees=args.trees or 500,
            depth=args.depth,
            features=args.features,
            workdir=args.workdir,
        )
        return

    rows = args.rows or (
        20_000 if args.small
        else (500_000 if platform == "cpu" else 2_000_000)
    )
    trees = args.trees or (5 if args.small else 20)
    record, _ = run_bench(
        rows, trees, args.depth, args.features,
        with_baseline=not args.no_baseline and not args.small,
    )
    emit(record)


if __name__ == "__main__":
    main()
