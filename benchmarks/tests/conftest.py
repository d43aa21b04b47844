"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests``.  Not collected by the repo's tier-1 command."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
