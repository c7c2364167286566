"""Tests of the benchmark's own files.  Run by hand from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not under ``tests/`` (the tier-1 suite) on purpose: the
yardstick is checked with the yardstick.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
