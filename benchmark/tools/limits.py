#!/usr/bin/env python3
"""Reads, on the chip and at the cells' own size, the numbers the limits
of `benchmark/limits/<cell>.json` are set from (PERF.md section 2): sound
runs of the program over many seeds (the lower readings), and on a few
seeds the control (the program's own `bf16x2` and `int8` histogram) and
the planted faults (the upper readings). One process, one table a seed,
every cell of the table read against one reference. No window is
measured: a training cell's readings need none.

    python3 benchmark/tools/limits.py --cells synth100_gbt.sweep \
        --seeds 500,501 --kinds sound,bf16x2,int8,half_batch,split_altered
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", required=True)
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
    from harness.reference import GbtReference
    from harness.runner import learner_of

    if jax.devices()[0].platform != "tpu" and not args.rows:
        sys.exit("limits: no TPU (use --rows for a CPU rehearsal)")
    enable_compile_cache()
    m = manifest.load()
    cells = {c: manifest.cell_files(m, c) for c in args.cells.split(",")}
    kinds = args.kinds.split(",")
    os.makedirs(os.path.dirname(os.path.join(manifest.ROOT, args.out)),
                exist_ok=True)
    out = open(os.path.join(manifest.ROOT, args.out), "a")

    def emit(**rec):
        line = json.dumps(rec, default=float)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def train(cell, config, ds, quant=None):
        if quant:
            os.environ["YDF_TPU_HIST_QUANT"] = quant
        gbt._make_boost_fn.cache_clear()  # the switch is read when tracing
        try:
            model = learner_of(
                ydf, config, jax.devices()[:cell["chips"]])().train(ds)
            impl = model.training_logs["implementations"]
            return compare.forest_arrays(model), impl
        finally:
            os.environ.pop("YDF_TPU_HIST_QUANT", None)
            gbt._make_boost_fn.cache_clear()

    for nth, seed in enumerate(int(s) for s in args.seeds.split(",")):
        first = next(iter(cells.values()))[2]
        rows = args.rows or first["rows"]
        t = time.perf_counter()
        x, y = make_table(rows, first["features"], seed, first["table"])
        ds = ydf.Dataset.from_data(as_columns(x, y), label="label")
        ref = None
        for name, (cell, _, config, _, _) in cells.items():
            hp = config["reference"]
            follow = min(3, config["num_trees"])
            if (ref is None or ref.hp["max_depth"] != hp["max_depth"]
                    or len(ref.parts) != cell["chips"]):
                ref = GbtReference(x, y, hp, block_rows=args.block_rows,
                                   devices=jax.devices()[:cell["chips"]])
            ref.hp = hp

            def read(arrays, kind, impl=None):
                t0 = time.perf_counter()
                numbers = compare.readings(x, y, hp, [arrays],
                                           follow_trees=follow, ref=ref)
                emit(cell=name, seed=seed, kind=kind, numbers=numbers,
                     impl=impl, read_s=time.perf_counter() - t0)

            sound = None
            for kind in kinds if nth < args.control_seeds else ["sound"]:
                t0 = time.perf_counter()
                if kind == "sound":
                    sound, impl = train(cell, config, ds)
                    read(sound, kind, impl)
                elif kind in ("bf16x2", "int8"):
                    arrays, impl = train(cell, config, ds, quant=kind)
                    read(arrays, kind, impl)
                elif kind == "half_batch":
                    half = ydf.Dataset.from_data(
                        as_columns(x[:, : rows // 2], y[: rows // 2]),
                        label="label")
                    read(train(cell, config, half)[0], kind)
                    del half
                elif kind == "split_altered":
                    # the root of tree 1 cut one bin further up
                    arrays = {k: (np.array(v) if isinstance(v, np.ndarray)
                                  else v) for k, v in sound.items()}
                    f = arrays["feature"][0, 0]
                    b = int(np.flatnonzero(
                        ref.edges[f] == arrays["threshold"][0, 0])[0])
                    arrays["threshold"][0, 0] = ref.edges[f, b + 1]
                    read(arrays, kind)
                print(f"# {name} seed {seed} {kind}: "
                      f"{time.perf_counter() - t0:.1f} s", flush=True)
        print(f"# seed {seed}: {time.perf_counter() - t:.1f} s", flush=True)
        del ds, ref


if __name__ == "__main__":
    main()
