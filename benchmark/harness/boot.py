"""Helpers for the readers of an engine's boot: every engine's ``/metrics``
carries ``boot`` (``agentainer_tpu/utils/boot.py``), frozen when the engine
became ready: the spawn stamp and ``main``'s entry, ``ready_s``, the
``boot.*`` spans as ``phases`` (the shape ``harness/phases.py`` reads) and the
compile listener's totals at ready. A fleet's engines load side by side, so a
reader takes the largest over the documents; a program without the block has
none, and the readers return ``None``."""

from __future__ import annotations

from typing import Callable


def largest(docs: list[dict], value: Callable[[dict], float | None]) -> float | None:
    """``value`` of each engine's ``boot`` block (``None``: it cannot say),
    the largest of them."""
    values = [v for m in docs if isinstance(b := m.get("boot"), dict) and (v := value(b)) is not None]
    return float(max(values)) if values else None


def total_s(block: dict, *names: str) -> float | None:
    """Σ ``total_s`` of the named stages; ``None`` if the engine recorded
    none of them (a stage it skipped counts 0 beside one it ran)."""
    phases = block.get("phases") or {}
    if not any(n in phases for n in names):
        return None
    return sum((phases.get(n) or {}).get("total_s") or 0.0 for n in names)


def at_ready(block: dict, *keys: str) -> float | None:
    """Σ of the named totals of the compile listener at ready."""
    cache = block.get("compile_cache_at_ready")
    if not isinstance(cache, dict) or not any(k in cache for k in keys):
        return None
    return sum(cache.get(k) or 0 for k in keys)
