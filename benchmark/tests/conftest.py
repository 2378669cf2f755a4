import os
import sys

# Four virtual CPU devices, set before JAX is imported: the reference's
# row blocks are divided over the devices of a cell (test_reference_over_
# chips.py), and the processes these tests start inherit the flag.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
