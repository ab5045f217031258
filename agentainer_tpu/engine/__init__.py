"""Serving engines — the programs agents run.

Replaces the reference's user-supplied Docker images (Flask apps calling
external LLM APIs, examples/gpt-agent/app.py). Engines here are in-process
serving programs placed on TPU chips:

- ``echo``       mock-LLM parity agent (engine/echo.py): same HTTP contract
  as examples/gpt-agent (/chat /health /history /clear /metrics),
  conversation memory in the store — BASELINE.json config #1.
- ``llm``        JAX prefill+decode engine with continuous batching
  (engine/llm.py) — BASELINE.json configs #2-#5.
- ``assistant``  persona flavor of the llm engine: system-prompted, with
  recent store-backed history FLATTENED into each turn's prompt — the
  reference's second example personality
  (examples/gemini-agent/app.py:87-113 builds one prompt string from
  history instead of threading structured messages).

The registry is OPEN — the reference accepted any Docker image, so this
framework accepts user engines the same way: ``register_engine`` in
process, or ``ATPU_EXTRA_ENGINES=name:module.path,...`` in the daemon's
environment (each module must expose ``serve()``; engine subprocesses
import it by that path).
"""

from __future__ import annotations

import os

_BUILTIN: dict[str, str] = {
    "echo": "agentainer_tpu.engine.echo",
    "llm": "agentainer_tpu.engine.llm_serve",
    "assistant": "agentainer_tpu.engine.llm_serve",  # persona preset of llm
}

# engines backed by the JAX model runtime: they validate a model config at
# deploy time, share weight HBM by config name, and keep their JAX_PLATFORMS
# (everything else is pinned to CPU so it can't touch the chips). Keyed at
# the registry so flavors can't silently miss a per-call-site name check.
_TPU_BACKED: set[str] = {"llm", "assistant"}

_EXTRA: dict[str, str] = {}


def register_engine(name: str, module: str, tpu: bool = False) -> None:
    """Register a user engine: ``module`` must expose ``serve()`` (run in
    the engine subprocess with the AGENTAINER_* env contract). ``tpu``
    marks it JAX-backed (model-config validation + chip placement); its
    ``serve(boot=)`` is handed the process's boot timeline
    (``utils/boot.BootTimeline``, ``boot.import`` open: ``imported()``
    closes it), which ``runtime/engine_main`` started at its entry."""
    if not name or ":" in name or "," in name:
        raise ValueError(f"bad engine name {name!r}")
    _EXTRA[name] = module
    if tpu:
        _TPU_BACKED.add(name)


def is_tpu_engine(name: str) -> bool:
    return name in _TPU_BACKED


def _env_engines() -> dict[str, str]:
    out: dict[str, str] = {}
    raw = os.environ.get("ATPU_EXTRA_ENGINES", "")
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, module = part.partition(":")
        if name and module:
            out[name] = module
    return out


def engine_registry() -> dict[str, str]:
    """name → serve-module for every known engine (builtin + registered +
    environment-injected)."""
    reg = dict(_BUILTIN)
    reg.update(_env_engines())
    reg.update(_EXTRA)
    return reg


def known_engines() -> set[str]:
    return set(engine_registry())
