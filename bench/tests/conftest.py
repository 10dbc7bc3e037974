"""pytest bench/tests: by hand, not part of tier-1. Only
test_broken_run.py needs JAX and the program; the rest need NumPy."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
