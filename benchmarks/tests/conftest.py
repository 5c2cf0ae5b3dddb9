"""Tests of the benchmark's own code.  Run from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Nothing here touches a device or describes a TPU topology.
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH, os.path.dirname(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)
