#!/usr/bin/env python3
"""Where ops/lookup.py's dense forms stop paying: times `pick_column`
and `lookup_small` on the chip, dense and as the gather, at a routing
level's shapes, and prints one JSON line a reading (seconds a call,
nanoseconds a row, seconds to compile). How `DENSE_COLUMNS_MAX` and
`DENSE_TABLE_MAX` were set (PERF.md section 6, PR 29).

    chiprun -- python3 scripts/lookup_crossover.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ydf_tpu.ops.lookup import lookup_small, pick_column

COLUMNS = (28, 100, 512, 2048)
TABLES = (32, 128, 256, 1024, 2048)
REPS = 5


def reading(name, size, rows, dense, fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    seconds = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        seconds.append(time.perf_counter() - t0)
    best = min(seconds)
    print(json.dumps({
        "helper": name, "size": size, "rows": rows,
        "form": "dense" if dense else "gather", "call_s": best,
        "ns_per_row": best / rows * 1e9, "compile_s": compile_s,
    }), flush=True)


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("lookup_crossover: JAX reports no TPU. Nothing was run.")
    print(json.dumps({"device": device.device_kind}), flush=True)
    rng = np.random.default_rng(0)
    for F in COLUMNS:
        rows = (1 << 31) // max(F, 512)  # 2 GiB of bins at most
        bins = jnp.asarray(rng.integers(0, 256, (rows, F), dtype=np.uint8))
        f = jnp.asarray(rng.integers(0, F, rows, dtype=np.int32))
        for dense in (True, False):
            # The consumer a routing level has: the row's bin against a cut.
            reading("pick_column", F, rows, dense,
                    lambda b, f, d=dense: pick_column(b, f, d).astype(
                        jnp.int32) <= 127, bins, f)
        del bins, f
    rows = 1 << 24
    for size in TABLES:
        table = jnp.asarray(rng.integers(0, 1 << 20, size, dtype=np.int32))
        idx = jnp.asarray(rng.integers(0, size + 1, rows, dtype=np.int32))
        for dense in (True, False):
            reading("lookup_small", size, rows, dense,
                    lambda t, i, d=dense, s=size: lookup_small(t, i, s, 0, d),
                    table, idx)


if __name__ == "__main__":
    main()
