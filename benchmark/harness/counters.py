"""Helpers for the per-layer readers: the engines' ``/metrics`` documents are
taken at the window's start and end (one per engine; a fleet has several),
and a reader works on their difference."""

from __future__ import annotations

from . import stats


def total(docs: list[dict], key: str) -> float:
    return float(sum((m.get(key) or 0) for m in docs))


def delta(before: list[dict], after: list[dict], key: str) -> float:
    return total(after, key) - total(before, key)


def hist_delta(before: list[dict], after: list[dict], key: str) -> dict[int, float]:
    out: dict[int, float] = {}
    for sign, docs in ((1, after), (-1, before)):
        for m in docs:
            for k, v in (m.get(key) or {}).items():
                out[int(k)] = out.get(int(k), 0) + sign * v
    return out


def recent_median(after: list[dict], samples_key: str) -> float | None:
    """Median of the engines' newest samples (bounded deques the engine
    keeps; at the window's end they hold the window's last requests)."""
    xs = [float(x) for m in after for x in (m.get(samples_key) or [])]
    return stats.median(xs)
