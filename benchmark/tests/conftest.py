"""Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` from the
repository's root. These tests are the benchmark's own and are not part of
tier-1 (``tests/``)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
