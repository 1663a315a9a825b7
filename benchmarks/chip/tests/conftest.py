"""Tests of the benchmark's own code, run on the CPU:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]
