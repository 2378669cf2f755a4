"""The control: the program's own lower-precision histogram, switched on
through YDF_TPU_HIST_QUANT in a fresh process (the program reads it once,
when it first traces its boosting loop), has to come out not correct.
On the chip, at the cell's size, both `bf16x2` and `int8` fail
`leaf_gap_median` (PERF.md section 2). At the size a CPU test run can
hold only `int8` does: `bf16x2` reads 7e-8 at 40,000 rows (`platform:
cpu`), under what sound runs read on the chip. `f32`, the same path with
the switch at what the configuration states, has to come out correct."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {bench!r}]
from tests.small import run_small
r = run_small(seed=17, chips={chips})
print(json.dumps({{"correct": r["correct"], "compared": r["compared"]}}))
"""


def run_with(quant, chips=1):
    bench = os.path.dirname(HERE)
    env = dict(os.environ, YDF_TPU_HIST_QUANT=quant, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         SCRIPT.format(root=os.path.dirname(bench), bench=bench,
                       chips=chips)],
        env=env, capture_output=True, text=True, timeout=600, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("quant,correct", [("f32", True), ("int8", False)])
def test_control(quant, correct):
    got = run_with(quant)
    assert got["correct"] is correct, got["compared"]


def test_control_is_not_correct_with_the_reference_over_four_devices():
    got = run_with("int8", chips=4)
    assert got["correct"] is False, got["compared"]
