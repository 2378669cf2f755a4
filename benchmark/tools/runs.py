#!/usr/bin/env python3
"""Runs the benchmark's command several times, one process after the
other (this parent never touches JAX, so each run has the chip), and
keeps every result line in `chiprun_out/runs.jsonl`. How the spreads of
PERF.md section 2 were measured:

    python3 benchmark/tools/runs.py --workload synth100_gbt.sweep --label set1 \
        --seeds 2147483747,3000000019 --trace-seeds 2147483929
"""

import argparse
import json
import os
import statistics
import subprocess
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--label", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--trace-seeds", default="")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    plan = [(int(s), 0) for s in args.seeds.split(",") if s]
    plan += [(int(s), 1) for s in args.trace_seeds.split(",") if s]
    values = {}
    for seed, trace in plan:
        t0 = time.time()
        run = subprocess.run(
            m["command"] + ["--workload", args.workload, "--seed", str(seed),
                            "--seconds", str(m["run_seconds"]),
                            "--trace", str(trace)],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        lines = run.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if run.returncode == 0 and lines else None
        rec = {"label": args.label, "seed": seed, "trace": trace,
               "rc": run.returncode, "wall_s": wall, "result": result,
               "stderr_tail": run.stderr[-3000:]}
        with open(os.path.join(ROOT, "chiprun_out", "runs.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        short = result and {k: v["value"] for k, v in result["metrics"].items()}
        print(f"seed {seed} trace {trace} rc {run.returncode} wall {wall:.0f} s "
              f"correct {result and result['correct']} {short}", flush=True)
        if not result or not result["correct"]:
            print(run.stderr[-3000:], flush=True)
        if result and not trace:
            for k, v in result["metrics"].items():
                values.setdefault(k, []).append(v["value"])
    for k, v in values.items():
        if len(v) >= 4:
            q = statistics.quantiles(v, n=4)
            med = statistics.median(v)
            print(f"{k}: median {med} IQR/median {(q[2] - q[0]) / med:.5f} "
                  f"runs {v}", flush=True)


if __name__ == "__main__":
    main()
