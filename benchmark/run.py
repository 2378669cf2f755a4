#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and
prints, as the last line of standard output, one JSON object: correct,
attempted, failed, metrics, device (and, traced, breakdown), then the
numbers compared, each beside its limit. Exits non-zero and prints no
result where JAX finds no TPU or fewer chips than the cell asks for, or
where the program is not beside it.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from harness import manifest

    try:
        m = manifest.load()
        cell = manifest.cell_files(m, args.workload)[0]
    except (manifest.ManifestError, OSError, json.JSONDecodeError) as err:
        sys.exit(f"benchmark: {err}")
    try:
        import ydf_tpu  # noqa: F401
    except ImportError as err:
        sys.exit(f"benchmark: the program is not beside the benchmark: {err}")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: JAX reports platform {devices[0].platform!r}, "
                 "not 'tpu'. Nothing was run.")
    if len(devices) < cell["chips"]:
        sys.exit(f"benchmark: cell {args.workload} needs {cell['chips']} "
                 f"chips, JAX reports {len(devices)}")

    from harness.runner import run_cell

    code, result = run_cell(m, args.workload, args.seed, args.seconds,
                            args.trace, T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    sys.exit(code)


if __name__ == "__main__":
    main()
