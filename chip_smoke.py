#!/usr/bin/env python3
"""The quickest proof that ydf_tpu still starts on the chip.

One process, no children, no network, all data from a seed. Drives the
GBT train -> predict path through the public API at the full width of
the flagship model (BASELINE.json config 3: 28 numerical features, 256
bins, depth 6; rows cut to 1,000,000) with every hyperparameter but
num_trees at its default, then checks what came out by the repo's own
means:

  device     JAX must report a TPU; otherwise exit non-zero, run nothing
  train      twice (cold with compile, then warm); implementations resolved
             to matmul / xla / f32; dispatches counted; data on the device
  histogram  matmul vs segment on the same device, inside the f32 contract
  predict    served by a compiled QuickScorerEngine; equals the routed scan;
             AUC in the band of the CPU run; save -> load -> same predictions
  deep       a depth-10 forest served by a compiled PallasBankEngine
  mesh       (--mesh 4) the same train sharded over four chips

Any failed check raises, so the run ends with a traceback, a non-zero
exit code and no result line. A passing run ends with two lines: a
`[result]` line holding every figure measured, then, last, exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
with the device as JAX reports it (nothing else belongs on that line).

    python3 chip_smoke.py                 # on the chip, through the chip tool
    python3 chip_smoke.py --mesh 4        # on a four-chip host
    python3 chip_smoke.py --allow-cpu     # rehearsal on the CPU; never a result
"""

import argparse
import importlib.metadata
import json
import os
import sys
import tempfile
import time

SEED = 21
FEATURES = 28
ROWS = 1_000_000
HELDOUT = 100_000
DEEP_ROWS = 100_000
NUM_TREES = 20
# Held-out AUC of this script's own CPU run at the same seed and size
# (`python chip_smoke.py --allow-cpu --rows 1000000`, PR 21 sandbox,
# jax 0.9.0 on the CPU backend, native histogram). The chip's AUC must
# land within AUC_BAND of it.
CPU_AUC_AT_FULL_SIZE = 0.76609
AUC_BAND = 0.003


class SmokeFailure(AssertionError):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def say(stage, **fields):
    print(f"[{stage}] " + json.dumps(fields, default=str), flush=True)


def make_data(rows, seed):
    """Higgs-shaped table: `rows` x 28 float32, binary label from a
    fixed logit, then ~1% NaN in one informative and one noise column
    (injected after the label is computed, so no NaN leaks into it)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, FEATURES), dtype=np.float32)
    logit = x[:, 0] - 0.5 * x[:, 1] + np.sin(2 * x[:, 2]) + x[:, 3] * x[:, 4]
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int64)
    for col in (1, 9):
        x[rng.random(rows) < 0.01, col] = np.nan
    data = {f"f{i}": x[:, i] for i in range(FEATURES)}
    data["label"] = y
    return data


def head(data, n):
    return {k: v[:n] for k, v in data.items()}


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def encode_and_route(model, data):
    """(x_num, x_cat, raw scores of the routed scan) for `data`: the
    inputs an engine takes and the reference it must reproduce, computed
    on the default device."""
    import jax.numpy as jnp
    import numpy as np

    import ydf_tpu as ydf
    from ydf_tpu.ops.routing import forest_predict_values

    ds = ydf.Dataset.from_data(data, dataspec=model.dataspec)
    x_num, x_cat, _ = model._encode_inputs(ds)
    routed = np.asarray(
        forest_predict_values(
            model.forest, jnp.asarray(x_num), jnp.asarray(x_cat),
            num_numerical=model.binner.num_numerical,
            max_depth=model.max_depth, combine="sum",
        )
    )[:, 0]
    return x_num, x_cat, routed


def stage_train(ydf, train, on_tpu, mesh=None):
    import jax
    import numpy as np

    from ydf_tpu.ops import device_loop

    def fit():
        learner = ydf.GradientBoostedTreesLearner(
            label="label", num_trees=NUM_TREES, mesh=mesh
        )
        device_loop.reset_stats()
        t0 = time.perf_counter()
        model = learner.train(train)
        return model, time.perf_counter() - t0, device_loop.stats_snapshot()

    _, cold_s, _ = fit()
    model, warm_s, stats = fit()
    impl = model.training_logs["implementations"]
    num_bins = int(model.binner.num_bins)
    mem = jax.devices()[0].memory_stats() or {}
    say(
        "train", cold_wall_s=round(cold_s, 2), warm_wall_s=round(warm_s, 2),
        device_loop=stats, implementations=impl, num_bins=num_bins,
        max_depth=model.max_depth, trees=model.num_trees(),
        peak_bytes_in_use=mem.get("peak_bytes_in_use"),
        warm_profile_s={k: round(v, 2) for k, v in model.training_profile.items()},
    )
    if len(train["label"]) >= ROWS:
        check(num_bins == 256,
              f'num_bins="auto" resolved to {num_bins}, not 256')
    check(model.max_depth == 6, f"default max_depth is {model.max_depth}")
    check(model.training_logs["valid_loss"] is not None,
          "the default validation split did not run")
    check(stats["dispatches"] >= 1 and stats["trees"] >= model.num_trees(),
          f"device loop dispatched nothing: {stats}")
    check(np.isfinite(model.training_logs["train_loss"]).all(),
          "non-finite training loss")
    if on_tpu:
        check(impl == {"hist_impl": "matmul", "hist_quant": "f32",
                       "route_impl": "xla"},
              f"TPU backend resolved {impl}")
        # The bin matrix alone is rows x 28 bytes: a peak below it means
        # the loop did not hold its data on this device.
        check(mem.get("peak_bytes_in_use", 0) >= len(train["label"]) * FEATURES,
              f"TPU device memory never held the training data: {mem}")
    return model, cold_s, warm_s, stats, impl


def stage_histogram(rows):
    """One layer-shaped call: matmul against segment on the same device.
    f32 mode is documented exact (ops/histogram.py): counts must be
    equal, gradient sums within 1e-5 of the cell's magnitude (the sum of
    |stat| over the cell's rows)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ydf_tpu.ops.histogram import histogram

    rng = np.random.default_rng(SEED + 1)
    L, B, S = 32, 256, 3
    bins = jnp.asarray(rng.integers(0, B, (rows, FEATURES), dtype=np.uint8))
    slot = jnp.asarray(rng.integers(0, L, rows, dtype=np.int32))
    stats_np = np.stack(
        [
            rng.standard_normal(rows, dtype=np.float32),
            rng.random(rows, dtype=np.float32) * 0.25,
            np.ones(rows, np.float32),
        ],
        axis=1,
    )
    stats = jnp.asarray(stats_np)
    kw = dict(num_slots=L, num_bins=B, quant="f32")
    out = {}
    for impl in ("matmul", "segment"):
        out[impl] = np.asarray(
            jax.block_until_ready(histogram(bins, slot, stats, impl=impl, **kw))
        )
        check(out[impl].shape == (L, FEATURES, B, S),
              f"{impl} histogram has shape {out[impl].shape}")
    mag = np.asarray(
        histogram(bins, slot, jnp.abs(stats), impl="segment", **kw)
    )
    diff = np.abs(out["matmul"] - out["segment"])
    counts_equal = bool(np.array_equal(out["matmul"][..., 2], out["segment"][..., 2]))
    rel = float(np.max(diff[..., :2] / np.maximum(mag[..., :2], 1e-30)))
    say("histogram", rows=rows, counts_equal=counts_equal,
        max_abs_diff=float(diff.max()), max_diff_over_cell_magnitude=rel,
        device=str(bins.devices()))
    check(float(out["segment"][..., 2].sum()) == rows * FEATURES,
          "segment histogram lost rows")
    check(counts_equal, "matmul and segment disagree on the count column")
    check(rel <= 1e-5,
          f"matmul gradient sums are {rel:.3g} of the cell magnitude away "
          "from segment (f32 contract: 1e-5)")
    return {"counts_equal": counts_equal, "max_abs_diff": float(diff.max()),
            "max_diff_over_cell_magnitude": rel}


def stage_predict(ydf, model, heldout, on_tpu, full_size):
    import numpy as np

    pred = np.asarray(model.predict(heldout))
    check(pred.shape == (len(heldout["label"]),), f"predict shape {pred.shape}")
    check(np.isfinite(pred).all() and pred.min() >= 0 and pred.max() <= 1,
          "predictions are not finite probabilities")
    eng = model._fast_engine()
    served_by = type(eng).__name__
    if on_tpu:
        check(served_by == "QuickScorerEngine" and eng.interpret is False,
              f"predict() was served by {served_by} "
              f"(interpret={getattr(eng, 'interpret', None)})")
    else:
        # Rehearsal: the CPU serves predict() natively; run the kernel
        # the chip would select through the Pallas interpreter instead.
        from ydf_tpu.serving.quickscorer import build_quickscorer

        eng = build_quickscorer(model)
        check(eng is not None, "model fell outside the QuickScorer envelope")
    x_num, x_cat, routed = encode_and_route(model, heldout)
    diff = float(np.max(np.abs(np.asarray(eng(x_num, x_cat)) - routed)))
    check(diff <= 1e-6, f"QuickScorer differs from the routed scan by {diff}")

    auc = float(model.evaluate(heldout).auc)
    check(0.5 < auc <= 1.0, f"AUC {auc}")
    auc_checked = full_size and CPU_AUC_AT_FULL_SIZE is not None
    if auc_checked:
        check(abs(auc - CPU_AUC_AT_FULL_SIZE) <= AUC_BAND,
              f"AUC {auc:.5f} is outside {CPU_AUC_AT_FULL_SIZE} +- {AUC_BAND}")

    with tempfile.TemporaryDirectory() as d:
        model.save(d)
        again = np.asarray(ydf.load_model(d).predict(heldout))
    check(np.array_equal(pred, again), "save -> load changed the predictions")
    out = {"served_by": served_by, "checked_engine": type(eng).__name__,
           "interpret": eng.interpret, "engine_vs_routed": diff,
           "auc": round(auc, 5), "auc_band_checked": auc_checked,
           "save_load_identical": True}
    say("predict", **out)
    return out


def stage_deep(ydf, train, on_tpu):
    """Outside QuickScorer's 64-leaf envelope: the other engine a TPU
    backend selects by itself."""
    import numpy as np

    model = ydf.GradientBoostedTreesLearner(
        label="label", num_trees=5, max_depth=10
    ).train(train)
    probe = head(train, 20_000)
    pred = np.asarray(model.predict(probe))
    check(np.isfinite(pred).all(), "deep forest predictions are not finite")
    eng = model._fast_engine()
    served_by = type(eng).__name__
    if on_tpu:
        check(served_by == "PallasBankEngine" and eng.interpret is False,
              f"deep predict() was served by {served_by} "
              f"(interpret={getattr(eng, 'interpret', None)})")
    else:
        from ydf_tpu.serving.pallas_scorer import build_pallas_scorer

        eng = build_pallas_scorer(model)
        check(eng is not None, "deep model fell outside the PallasBank envelope")
        probe = head(train, 1024)  # the interpreter is slow
    x_num, x_cat, routed = encode_and_route(model, probe)
    diff = float(np.max(np.abs(np.asarray(eng(x_num, x_cat)) - routed)))
    max_leaves = int((np.asarray(model.forest.feature) >= 0).sum(axis=1).max()) + 1
    out = {"served_by": served_by, "checked_engine": type(eng).__name__,
           "interpret": eng.interpret, "max_leaves": max_leaves,
           "engine_vs_routed": diff}
    say("deep", **out)
    check(max_leaves > 64, "the deep forest fits QuickScorer's 64-leaf envelope")
    check(diff <= 1e-6, f"PallasBank differs from the routed scan by {diff}")
    return out


def stage_mesh(ydf, train, heldout, one_chip, n, on_tpu):
    """The same train sharded over `n` chips in this one process."""
    import jax

    devices = jax.devices()[:n]
    mesh = ydf.make_mesh(devices, feature_parallelism=2)
    before = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
    model, cold_s, warm_s, _, _ = stage_train(ydf, train, on_tpu, mesh=mesh)
    placed = model.training_logs["mesh"]["input_devices"]
    for name, ids in placed.items():
        check(len(ids) == n, f"sharded input {name!r} spans devices {ids}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
    if on_tpu:
        # Device 0 also ran the one-chip stages, so its lifetime peak is
        # no evidence; the other three peaked during this train only.
        check(min(peaks[1:]) > 0 and max(peaks[1:]) <= 10 * min(peaks[1:]),
              f"per-device peak memory is not of one order: {peaks}")
    auc = float(model.evaluate(heldout).auc)
    check(abs(auc - one_chip["auc"]) <= AUC_BAND,
          f"mesh AUC {auc:.5f} vs one-chip {one_chip['auc']:.5f}")
    out = {
        "mesh": dict(mesh.shape), "input_devices": placed,
        "peak_bytes_in_use": peaks, "bytes_in_use_before": before,
        "bytes_in_use_after": in_use,
        "auc": round(auc, 5), "cold_wall_s": round(cold_s, 2),
        "warm_wall_s": round(warm_s, 2),
        "one_chip_warm_wall_s": round(one_chip["warm_wall_s"], 2),
    }
    say("mesh", **out)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU at a tiny size (interpret-mode "
                    "kernels, device checks skipped); never a result")
    ap.add_argument("--rows", type=int, default=None,
                    help="training rows (rehearsal only; default 20000)")
    ap.add_argument("--mesh", type=int, default=0, choices=(0, 4),
                    help="also train sharded over this many chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not args.allow_cpu:
        sys.exit(
            f"chip_smoke: JAX reports platform {platform!r} "
            f"({devices[0].device_kind}), not 'tpu'. Nothing was run. "
            "Rehearse on the CPU with --allow-cpu."
        )
    if args.rows is not None and not args.allow_cpu:
        sys.exit("chip_smoke: --rows is for the --allow-cpu rehearsal only")
    if args.mesh and len(devices) < args.mesh:
        sys.exit(f"chip_smoke: --mesh {args.mesh} needs {args.mesh} devices, "
                 f"JAX reports {len(devices)}")
    rehearsal = not on_tpu
    rows = (args.rows or 20_000) if rehearsal else ROWS

    import jaxlib

    import ydf_tpu as ydf
    from ydf_tpu.config import enable_compile_cache
    from ydf_tpu.dataset import native_csv
    from ydf_tpu.dataset.binning import resolve_bin_impl

    cache_dir = enable_compile_cache()
    cache_at_start = cache_entries(cache_dir)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        versions["libtpu"] = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        versions["libtpu"] = None
    host_impls = {
        "bin_impl": resolve_bin_impl("auto"),
        "csv_loader": "native" if native_csv.available() else "python",
    }
    say("device", **device, versions=versions, rehearsal=rehearsal,
        compile_cache_dir=cache_dir, cache_entries_at_start=cache_at_start,
        host_implementations=host_impls)

    t_all = time.perf_counter()
    data = make_data(rows + max(rows // 10, 1000), SEED)
    train = head(data, rows)
    heldout = {k: v[rows:] for k, v in data.items()}
    if not rehearsal:
        check(len(heldout["label"]) == HELDOUT, "held-out size")

    model, cold_s, warm_s, loop_stats, impl = stage_train(ydf, train, on_tpu)
    hist = stage_histogram(rows)
    pred = stage_predict(ydf, model, heldout, on_tpu, rows == ROWS)
    deep = stage_deep(ydf, head(train, min(rows, DEEP_ROWS)), on_tpu)
    result = {
        "rehearsal": rehearsal,
        "versions": versions,
        "rows": rows,
        "train_cold_wall_s": round(cold_s, 2),
        "train_warm_wall_s": round(warm_s, 2),
        "device_loop": loop_stats,
        "implementations": impl,
        "host_implementations": host_impls,
        "histogram_parity": hist,
        "predict": pred,
        "deep": deep,
        "device_checks": "checked" if on_tpu else "not checked",
    }
    if args.mesh:
        result["mesh"] = stage_mesh(
            ydf, train, heldout,
            {"auc": pred["auc"], "warm_wall_s": warm_s}, args.mesh, on_tpu,
        )
    result["compile_cache"] = {
        "dir": cache_dir, "entries_at_start": cache_at_start,
        "entries_at_end": cache_entries(cache_dir),
    }
    result["total_wall_s"] = round(time.perf_counter() - t_all, 1)
    say("result", **result)
    # The contract line: these keys and no others, last on stdout.
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
