#!/usr/bin/env python3
"""`tools/limits.py` for a cell whose reference is a named module
(`benchmark/references/<module>.py`, the configuration's
`reference.module`): reads, on the chip and at the cell's own size, the
numbers the limits of `benchmark/limits/<cell>.json` are set from
(PERF.md section 2). Sound runs over many seeds give the lower readings;
on the first `--control-seeds` seeds the control (the program's own
`int8` or `bf16x2` histogram) and the planted faults give the upper
ones:

  split_altered     tree 1's root cut one bin further up
  half_batch        the first half of the rows trained on
  groups_truncated  a ranking cell's own: the documents past a query's
                    512th get no gradient (the program's
                    `ranking_max_group_size`, which the configuration
                    leaves off), what a lazy bucketing would do

One process, one table a seed, every model of a seed read against one
reference where the module gives `reference(x, y, hp, devices=...)`. No
window is measured.

    python3 benchmark/tools/named_limits.py --cell mslr30k_rank.sweep \\
        --seeds 500,501 --kinds sound,int8,split_altered,half_batch,groups_truncated
"""

import argparse
import json
import os
import sys
import time
import warnings

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

TRUNCATED_AT = 512


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default="sound")
    ap.add_argument("--control-seeds", type=int, default=10 ** 6,
                    help="only the first so many seeds read more than sound")
    ap.add_argument("--rows", type=int, default=0, help="rehearsal only")
    ap.add_argument("--block-rows", type=int, default=1 << 19)
    ap.add_argument("--out", default="chiprun_out/limits.jsonl")
    args = ap.parse_args()

    import jax
    import numpy as np

    import ydf_tpu as ydf
    from ydf_tpu.config import enable_compile_cache
    from ydf_tpu.learners import gbt

    from harness import compare, manifest
    from harness.datagen import as_columns, make_table
    from harness.runner import learner_of

    if jax.devices()[0].platform != "tpu" and not args.rows:
        sys.exit("named_limits: no TPU (use --rows for a CPU rehearsal)")
    enable_compile_cache()
    cell, _, config, _, limits = manifest.cell_files(manifest.load(),
                                                     args.cell)
    config = dict(config, rows=args.rows or config["rows"])
    module = compare.of_config(config)
    hp = config["reference"]
    follow = min(3, config["num_trees"])
    devices = jax.devices()[:cell["chips"]]
    os.makedirs(os.path.dirname(os.path.join(manifest.ROOT, args.out)),
                exist_ok=True)
    out = open(os.path.join(manifest.ROOT, args.out), "a")

    def emit(**rec):
        line = json.dumps(rec, default=float)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def train(ds, quant=None, **changed):
        if quant:
            os.environ["YDF_TPU_HIST_QUANT"] = quant
        gbt._make_boost_fn.cache_clear()  # the switch is read when tracing
        try:
            cfg = dict(config, hyperparameters=dict(
                config["hyperparameters"], **changed))
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # the cap's own warning
                model = learner_of(ydf, cfg, devices)().train(ds)
            return (module.forest_arrays(model),
                    model.training_logs["implementations"],
                    dict(model.training_profile))
        finally:
            os.environ.pop("YDF_TPU_HIST_QUANT", None)
            gbt._make_boost_fn.cache_clear()

    kinds = args.kinds.split(",")
    for nth, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        x, y = make_table(config["rows"], config["features"], seed,
                          config["table"])
        ds = ydf.Dataset.from_data(as_columns(x, y), label="label")
        ref = None
        if callable(getattr(module, "reference", None)):
            ref = module.reference(x, y, hp, block_rows=args.block_rows,
                                   devices=devices)

        def read(arrays, kind, impl=None, profile=None):
            t0 = time.perf_counter()
            kw = {"ref": ref} if ref is not None else {}
            numbers = module.readings(
                x, y, hp, [arrays], follow_trees=follow, devices=devices,
                block_rows=args.block_rows, **kw)
            numbers.setdefault("programs_built_in_window", 0)
            correct, _ = compare.judge(numbers, limits)
            emit(cell=args.cell, seed=seed, kind=kind, correct=correct,
                 numbers=numbers, impl=impl, read_s=time.perf_counter() - t0,
                 profile=profile)

        sound = None
        for kind in kinds if nth < args.control_seeds else ["sound"]:
            t0 = time.perf_counter()
            if kind == "sound":
                sound, impl, profile = train(ds)
                read(sound, kind, impl, profile)
            elif kind in ("bf16x2", "int8"):
                arrays, impl, _ = train(ds, quant=kind)
                read(arrays, kind, impl)
            elif kind == "groups_truncated":
                read(train(ds, ranking_max_group_size=TRUNCATED_AT)[0], kind)
            elif kind == "half_batch":
                half = ydf.Dataset.from_data(
                    as_columns(x[:, :config["rows"] // 2],
                               y[:config["rows"] // 2]), label="label")
                read(train(half)[0], kind)
                del half
            elif kind == "split_altered":
                # the root of tree 1 cut one bin further up
                arrays = {k: (np.array(v) if isinstance(v, np.ndarray)
                              else v) for k, v in sound.items()}
                f = arrays["feature"][0, 0]
                edges = sound["bin_edges"][f]
                b = int(np.flatnonzero(
                    edges == arrays["threshold"][0, 0])[0])
                arrays["threshold"][0, 0] = edges[b + 1]
                read(arrays, kind)
            else:
                sys.exit(f"named_limits: unknown kind {kind!r}")
            print(f"# {args.cell} seed {seed} {kind}: "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
        print(f"# seed {seed}: {time.perf_counter() - t:.1f} s", flush=True)
        del ds, ref


if __name__ == "__main__":
    main()
