"""Helpers for the readers of the engine's phase spans: every engine's
``/metrics`` carries ``phases`` (``{name: {"n", "self_s", "total_s"}}``,
cumulative; ``agentainer_tpu/utils/spans.py``) and ``loop_s``, taken at the
window's start and end like the counters. A program without spans has no
``phases`` key: ``names`` is then empty and the readers return ``None``."""

from __future__ import annotations


def names(docs: list[dict]) -> list[str]:
    return sorted({name for m in docs for name in (m.get("phases") or {})})


def delta(before: list[dict], after: list[dict], name: str, key: str) -> float:
    """Window difference of one number of one phase, summed over engines."""

    def total(docs: list[dict]) -> float:
        return sum(((m.get("phases") or {}).get(name) or {}).get(key) or 0 for m in docs)

    return float(total(after) - total(before))
