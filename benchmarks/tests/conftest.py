"""benchmarks/tests: CPU checks of the benchmark's own yardstick. Not
tier-1; run by hand with `python3 -m pytest benchmarks/tests -q`."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
