#!/usr/bin/env python3
"""What can be checked with no chip, in one command: the manifest and
every file it names against the driver's rules (each configuration's
table file, reference module and learner name among them), the look-ups
by name on stand-in files, the trace reduction on the recorded trace and
on two device planes, `train_mfu_pct`'s arithmetic on hand-worked shapes
and on four chips. It prints counts and verdicts, never a device metric.

    JAX_PLATFORMS=cpu python3 benchmark/tools/selfcheck.py
"""

import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def main():
    from harness import manifest

    m = manifest.load()
    print(f"manifest: {len(m['workloads'])} cells, {len(m['per_layer'])} "
          "per-layer metrics, every name, unit and file in order")
    for c in m["configs"]:
        cfg = manifest.load_json(c["file"])
        print(f"config {c['name']}: table {cfg['table']!r}, reference "
              f"{cfg['reference'].get('module', 'harness/reference.py')!r}, "
              f"learner {cfg.get('learner', 'GradientBoostedTreesLearner')!r}")
    tests = [os.path.join(BENCH, "tests", t) for t in
             ("test_manifest.py", "test_xplane.py", "test_work.py")]
    sys.exit(subprocess.call(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", *tests],
        cwd=os.path.dirname(BENCH)))


if __name__ == "__main__":
    main()
