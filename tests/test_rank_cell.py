"""The ranking cell `mslr30k_rank.sweep` (PR 36) at a size a CPU run can
hold, in tier-1: the cases of `benchmark/tests/test_rank_cell.py`
themselves (a sound run reads `correct`; the `int8` control and the
planted faults `split_altered`, `half_batch` and `groups_truncated` read
not `correct`), imported as `tests/test_higgs_cell.py` imports the
harness, so that the driver's count guards them."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):  # benchmark/ first: its `tests` package is meant
    if _p in sys.path:
        sys.path.remove(_p)
    sys.path.insert(0, _p)

from tests.test_rank_cell import (  # noqa: E402,F401
    test_fault_is_not_correct,
    test_int8_control_is_not_correct,
    test_sound_run_is_correct,
)
