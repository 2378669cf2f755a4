#!/usr/bin/env python3
"""What can be checked with no chip, in one command: the manifest and
every file it names against the driver's rules, the trace reduction on
the recorded trace, `train_mfu_pct`'s arithmetic on hand-worked shapes.
It prints counts and verdicts, never a device metric.

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
    tests = [os.path.join(BENCH, "tests", t) for t in
             ("test_manifest.py", "test_xplane.py", "test_work.py")]
    sys.exit(subprocess.call(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", *tests],
        cwd=os.path.dirname(BENCH)))


if __name__ == "__main__":
    main()
