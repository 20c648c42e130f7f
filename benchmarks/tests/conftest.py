"""CPU tests of the benchmark's own code. Run from the repo root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Nothing here reads a device time; a number from these runs is never
written under a device metric's name."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
