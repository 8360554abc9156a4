"""Tests of the benchmark's own code, run on the CPU:

    python3 -m pytest benchmarks/tests -q

They are no part of the repository's tier-1 suite (``tests/``).  Four
virtual CPU devices stand in for the four-chip cell.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
