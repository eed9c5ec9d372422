"""Tests of the benchmark itself.  Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of the repository's tier-1 suite (``tests/``)."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)
