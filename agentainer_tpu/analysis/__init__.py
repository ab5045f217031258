"""Invariant analysis plane.

Three legs, one entry point (``make analyze``):

1. **AST rules** (:mod:`.rules`): repo-specific invariants — exception
   discipline, hot-path host-sync bans, lock-hold discipline, failpoint
   catalog parity, jit dispatch via warmed ladders — checked as visitor
   rules with per-rule IDs (ATP001..ATP005) and a
   checked-in ``baseline.json`` ratchet: pre-existing violations are
   frozen with per-site justifications, new ones fail the run.
2. **HLO contracts** (:mod:`.hlo_contracts`): declarative assertions over
   compiled HLO text — never-all-gather sharding, donation aliasing,
   recompile budgets — consumed by the ``tests/test_*_hlo.py`` files so
   the sharding invariants live in one place.
3. **Sanitizer builds** (``native/Makefile`` asan/tsan/ubsan +
   ``native/stress_store.cc``): the C++ store under multi-threaded
   stress with the race/heap/UB checkers on.

Run the lint leg: ``python -m agentainer_tpu.analysis`` (add
``--update-baseline`` to re-freeze; see docs/ANALYSIS.md).
"""

from .framework import (  # noqa: F401
    AnalysisError,
    Baseline,
    Rule,
    Violation,
    load_baseline,
    run_rules,
)
from .rules import ALL_RULES  # noqa: F401
