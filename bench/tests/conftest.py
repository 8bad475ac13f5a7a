"""The benchmark's CPU tests import it as the package `bench` and the
program from <checkout>/src, with the Pallas kernels interpreted."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import repro  # noqa: E402,F401  (turns on jax_enable_x64)
