"""The benchmark's own tests; run by hand: ``python -m pytest benchmark/tests -q``
(tier-1 does not collect them). JAX is held to the CPU before anything
imports it."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
