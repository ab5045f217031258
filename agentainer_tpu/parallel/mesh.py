"""Device mesh construction and the layout rule.

The TPU-native replacement for the reference's "distribution" layer (Docker
bridge + replicas, SURVEY.md §2.3): an engine that spans more than one chip
computes on a ``jax.sharding.Mesh`` over the chips the slice scheduler
assigned it, with two named axes

    tp  — tensor parallel (attention heads / FFN width over ICI)
    ep  — expert parallel (a MoE model's experts, whole, over ICI)

Replica fan-out (the reference's ``replicas: N``) is separate engine
processes behind the proxy, not a mesh axis. ``plan_layout`` is the one
place that decides how many chips an engine spans and how; XLA/GSPMD
inserts the collectives implied by the sharding annotations
(parallel/sharding.py) so they ride ICI.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from ..models.configs import ModelConfig


def make_mesh(tp: int = 1, ep: int = 1, devices: list | None = None) -> Mesh:
    """A ``tp × ep`` mesh over the first ``tp * ep`` of ``devices`` (default:
    this process's devices)."""
    devs = devices if devices is not None else jax.devices()
    n = tp * ep
    if len(devs) < n:
        raise ValueError(f"tp*ep={n} needs {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]).reshape(tp, ep), axis_names=("tp", "ep"))


def pick_tp(cfg: ModelConfig, n_devices: int) -> int:
    """Largest tp that divides both the device count and the model's KV-head
    count (GQA shards KV heads; tp beyond n_kv_heads would split a head)."""
    tp = 1
    for cand in range(1, n_devices + 1):
        if n_devices % cand == 0 and cfg.n_kv_heads % cand == 0 and cfg.n_heads % cand == 0:
            tp = cand
    return tp


def pick_ep(cfg: ModelConfig, n_devices: int) -> int:
    """Largest ep ≤ n_devices that evenly shards the expert set — each
    device owns E/ep experts' weights whole (the expert axis never splits
    one expert's matrices)."""
    if not cfg.is_moe:
        return 1
    ep = 1
    for cand in range(1, max(1, n_devices) + 1):
        if cfg.n_experts % cand == 0:
            ep = cand
    return ep


def plan_layout(
    cfg: ModelConfig,
    n_assigned: int,
    n_visible: int,
    tp_asked: int = 0,
    ep_asked: int = 0,
) -> tuple[int, int]:
    """The ``(tp, ep)`` an engine for ``cfg`` is built with.

    ``n_assigned`` is how many chips the scheduler assigned the agent (0 for
    a standalone engine), ``n_visible`` how many devices the process sees,
    ``tp_asked``/``ep_asked`` the deployment's options (0 = not given). An
    assignment is the placement authority: the options may only narrow the
    span, never spill onto chips other agents own. A standalone engine spans
    exactly what its options ask for, at most what it sees. Both axes are
    clamped to divisors of the model's head and expert counts, and a span
    narrower than was asked for or assigned says so on stdout (the chips
    left over stay idle).
    """
    budget = n_assigned or min(n_visible, max(1, tp_asked) * max(1, ep_asked))
    if (cfg.is_hybrid or cfg.n_window) and not (tp_asked or ep_asked):
        # the hybrid block, and a model whose window layers keep a ring, are
        # served on one chip (its share of a stated expert-parallel
        # deployment is ``cfg.experts_held``); an explicit tp/ep passes
        # through and the engine refuses it by name
        # (engine/llm.cache_features)
        if n_assigned > 1:
            print(
                f"[llm-engine] parallelism narrowed to tp=1 ep=1: {cfg.name} is served on one "
                f"chip (assigned chips={n_assigned}); the others stay idle",
                flush=True,
            )
        return 1, 1
    if cfg.is_moe:
        # EP-first: experts dominate a MoE model's HBM footprint. Explicit
        # tp/ep options override the split.
        if ep_asked:
            ep = pick_ep(cfg, min(ep_asked, budget))
            tp = pick_tp(cfg, min(max(1, tp_asked), budget // ep))
        elif tp_asked:
            tp = pick_tp(cfg, min(tp_asked, budget))
            ep = pick_ep(cfg, budget // tp)
        else:
            ep = pick_ep(cfg, budget)
            tp = pick_tp(cfg, budget // ep)
    else:
        ep = 1
        # dense + assigned chips + no explicit tp: span the whole
        # assignment (the scheduler sized it; idle chips help nobody)
        dense_tp = tp_asked or (budget if n_assigned else 1)
        tp = pick_tp(cfg, min(dense_tp, budget))
    asked = max(1, tp_asked) * max(1, ep_asked)
    if tp * ep < min(asked, budget) or tp * ep < n_assigned:
        print(
            f"[llm-engine] parallelism narrowed to tp={tp} ep={ep} "
            f"(asked tp={tp_asked or 'auto'} ep={ep_asked or 'auto'}, "
            f"assigned chips={n_assigned or 'none'}, visible devices="
            f"{n_visible}, model kv_heads={cfg.n_kv_heads}, "
            f"heads={cfg.n_heads}, experts={cfg.n_experts}); "
            "extra chips idle",
            flush=True,
        )
    return tp, ep
