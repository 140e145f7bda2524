"""Run by hand and in the CPU rehearsal (``python -m pytest benchmark/tests``);
not part of the repository's tier-1 tests."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]
