"""The benchmark's own checks run on the CPU: `python -m pytest
benchmark/selftest -q` from the repo root.  Outside tier-1's tests/."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
