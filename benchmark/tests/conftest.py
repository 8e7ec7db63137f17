"""The benchmark's own tests run on the CPU: ``python -m pytest benchmark/tests -q``.
They are not part of the repo's tier-1 suite (``tests/``)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
