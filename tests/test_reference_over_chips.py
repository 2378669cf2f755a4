"""The plain reference over a cell's chips (PR 35), in tier-1: the fast
cases of `benchmark/tests/test_reference_over_chips.py` themselves (the
reference's row blocks over 2, 3 and 4 devices read what one device
reads, to the last digit; a cell's chips reach `train()` as a mesh over
them; the planted faults stay not `correct` on four devices), imported
as `tests/test_higgs_cell.py` imports the harness, so that the driver's
count guards them (PERF.md section 7 (a) of PR 35, ROADMAP D11). The
suite's conftest gives the CPU eight virtual devices."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):  # benchmark/ first: its `tests` package is meant
    if _p in sys.path:
        sys.path.remove(_p)
    sys.path.insert(0, _p)

from tests.test_reference_over_chips import (  # noqa: E402,F401
    one_device,
    test_a_cells_chips_reach_train_as_data,
    test_fault_is_not_correct_on_four_devices,
    test_more_devices_than_blocks,
    test_readings_equal_one_devices_to_the_last_digit,
    test_sound_run_is_correct_on_four_devices,
    test_uneven_parts_are_consecutive_and_differ_by_one,
)
