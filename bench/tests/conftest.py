"""Tests of the benchmark itself, on the CPU: ``python -m pytest bench/tests``.

Four virtual CPU devices stand in for the four-chip host; the variables
are set before anything imports JAX.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
