"""Tests of the benchmark itself; run by hand on the CPU: ``pytest benchmark/tests``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SKYPLANE_TPU_FORCE_ACCEL_PATH", "1")  # walk the device-path code on the CPU backend
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent)]
