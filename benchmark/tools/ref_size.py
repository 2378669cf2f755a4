#!/usr/bin/env python3
"""How large a table the plain reference holds on so many chips (chip
only). Makes a table from a seed, trains a configuration's
hyperparameters on its first `--sub` rows on one chip, so that a forest
exists, releases the program's arrays, and runs `compare.readings` over
the WHOLE table with the reference's row blocks divided over `--chips`
devices. Prints each chip's `peak_bytes_in_use` and the seconds by phase
(host bins, upload, trees), or how the reference died.

It shows capacity, not agreement: the forest's leaves are a subset's, so
the gaps it prints mean nothing unless `--sub` is `--rows`. Then the
forest is a cell's own, and a list of chip counts (`--chips 1,4`) shows
whether the reference reads it alike on each. A count given twice
(`--chips 4,4`) reads the second time with every program built: what a
run pays once the compile cache is warm.

    python3 benchmark/tools/ref_size.py --rows 224000000 --features 28 --chips 4
"""

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


CONFIG = "higgs_gbt"  # whose hyperparameters the forest is trained with
BLOCK_ROWS = 1 << 19  # the cells' own


def say(tag, **fields):
    print(f"[{tag}] " + json.dumps(fields, default=float), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--features", type=int, required=True)
    ap.add_argument("--table", default="binary_logit")
    ap.add_argument("--chips", default="1", help="one count, or a list: 1,4")
    ap.add_argument("--sub", type=int, default=16_000_000)
    ap.add_argument("--seed", type=int, default=3000001101)
    ap.add_argument("--allow-cpu", action="store_true", help="rehearsal only")
    args = ap.parse_args()

    import jax

    import ydf_tpu as ydf
    from ydf_tpu.config import enable_compile_cache
    from ydf_tpu.dataset.dataset import release_device_inputs

    from harness import compare, manifest
    from harness.compiles import CompileCounter
    from harness.datagen import as_columns, make_table
    from harness.reference import GbtReference

    devices = jax.devices()
    chip_counts = [int(c) for c in args.chips.split(",")]
    if devices[0].platform != "tpu" and not args.allow_cpu:
        sys.exit("ref_size: no TPU (--allow-cpu rehearses)")
    if max(chip_counts) > len(devices):
        sys.exit(f"ref_size: {max(chip_counts)} chips asked for, "
                 f"JAX reports {len(devices)}")
    enable_compile_cache()
    m = manifest.load()
    config = manifest.load_json(
        next(c["file"] for c in m["configs"] if c["name"] == CONFIG))

    t = time.perf_counter()
    x, y = make_table(args.rows, args.features, args.seed, args.table)
    say("table", rows=args.rows, features=args.features, seed=args.seed,
        seconds=time.perf_counter() - t)
    t = time.perf_counter()
    sub = min(args.sub, args.rows)
    hp = dict(config["hyperparameters"])
    hp["task"] = ydf.Task[hp["task"]]
    model = ydf.GradientBoostedTreesLearner(label="label", **hp).train(
        as_columns(x[:, :sub], y[:sub]))
    arrays = compare.forest_arrays(model)
    del model
    release_device_inputs()
    gc.collect()
    say("forest", sub_rows=sub, seconds=time.perf_counter() - t)

    def memory(chips):
        stats = [d.memory_stats() or {} for d in devices[:chips]]
        return {k: [s.get(k) for s in stats]
                for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}

    counter = CompileCounter()
    read = {}
    for nth, chips in enumerate(chip_counts):
        builds, build_s = counter.builds, counter.build_s
        say("before", chips=chips, **memory(max(chip_counts)))
        t = time.perf_counter()
        ref = None
        try:
            ref = GbtReference(x, y, config["reference"],
                               block_rows=BLOCK_ROWS,
                               devices=devices[:chips])
            numbers = compare.readings(
                x, y, config["reference"], [arrays],
                follow_trees=min(3, config["num_trees"]), ref=ref)
            read[nth] = {k: float(v) for k, v in numbers.items()}
            say("reference", chips=chips, ran=True, blocks=ref.blocks,
                blocks_by_chip=[p.hi - p.lo for p in ref.parts],
                **ref.seconds, seconds=time.perf_counter() - t,
                programs_built=counter.builds - builds,
                build_s=counter.build_s - build_s,
                numbers=read[nth])
        except Exception as err:  # the tool's job is to say how it died
            say("reference", chips=chips, ran=False,
                seconds=time.perf_counter() - t,
                error=f"{type(err).__name__}: {str(err)[:600]}")
        say("after", chips=chips, **memory(max(chip_counts)))
        del ref
        gc.collect()
    for nth in sorted(read)[1:] if 0 in read else []:
        say("equal", chips=[chip_counts[0], chip_counts[nth]],
            differ={k: [read[0][k], v] for k, v in read[nth].items()
                    if v != read[0][k]})
    sys.exit(0 if len(read) == len(chip_counts) else 1)


if __name__ == "__main__":
    main()
