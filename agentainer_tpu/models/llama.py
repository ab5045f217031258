"""Llama-3-family transformer — functional JAX, TPU-first.

Green-field (the reference proxies to external LLM APIs and has no model
code — SURVEY.md §2.3); this is the in-process engine's model, designed for
XLA from the start:

- **pytree params with stacked layers**: every per-layer weight carries a
  leading ``[n_layers, ...]`` axis and the forward pass is one
  ``lax.scan`` over layers — one traced block regardless of depth (fast
  compiles);
- **static shapes everywhere**: the KV cache is a fixed ``[L, B, S, KV, hd]``
  arena written by scatter at per-sequence positions, so the same compiled
  function serves prefill and continuous-batching decode (ragged batches);
- **bf16 weights/activations, f32 softmax/norms** — MXU-friendly;
- GQA grouping instead of repeated K/V (HBM bandwidth);
- sharding-agnostic: parallel/sharding.py maps these pytree paths to mesh
  axes; nothing here names a device.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.attention import (
    attention_reference,
    causal_mask,
    flash_attention,
    plan_cache_attention,
    scatter_paged_kv,
)
from ..ops.moe import EXPERT_WEIGHTS, gate_act, sorted_from_rows, sorted_moe_ffn, stacked_experts
from ..ops.norms import rms_norm
from ..ops.quant import QTensor, dequant, embed_lookup
from ..ops.rope import apply_rope
from .configs import ModelConfig


class KVCache(NamedTuple):
    """Static-shape KV arena: k/v ``[L, B, S, KV, hd]``."""

    k: jnp.ndarray
    v: jnp.ndarray

    @staticmethod
    def create(
        cfg: ModelConfig, batch: int, max_seq: int, dtype: jnp.dtype = jnp.bfloat16
    ) -> "KVCache":
        shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


class WindowKVCache(NamedTuple):
    """The K/V arena of a model with sliding-window layers beside global
    ones (``cfg.window_layers``), two leaves a side: ``k``/``v`` ``[Lg, B,
    S, KV, hd]`` hold the global layers' rows as :class:`KVCache` does, and
    ``wk``/``wv`` ``[Lw, B, R, KV, hd]`` the window layers' as a RING: the
    row of position p is ``p mod R``, so a lane holds R rows of such a layer
    whatever its context. ``R`` is :func:`ring_rows`."""

    k: jnp.ndarray
    v: jnp.ndarray
    wk: jnp.ndarray
    wv: jnp.ndarray

    @staticmethod
    def create(
        cfg: ModelConfig, batch: int, max_seq: int, dtype: jnp.dtype = jnp.bfloat16,
        launch_rows: int | None = None, block: int = 1,
    ) -> "WindowKVCache":
        if not cfg.n_global:
            # the arena's length, and with it the row a parked lane sits at,
            # is read off the global leaf
            raise ValueError("a model whose every layer is windowed is not served: no global leaf")
        r = ring_rows(cfg.window, max_seq, launch_rows, block)
        g = (cfg.n_global, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        w = (cfg.n_window, batch, r, cfg.n_kv_heads, cfg.head_dim)
        return WindowKVCache(jnp.zeros(g, dtype), jnp.zeros(g, dtype), jnp.zeros(w, dtype), jnp.zeros(w, dtype))


def ring_rows(window: int, max_seq: int, launch_rows: int | None = None, block: int = 1) -> int:
    """Rows ``R`` a lane keeps of a window layer: ``window + launch_rows``,
    rounded up to the K/V block the kernels read, and never more than the
    arena itself rounded the same way.

    Why the launch's rows on top of the window: every launch writes its new
    rows BEFORE it reads (``_attention_block``). A launch whose rows of one
    lane sit at positions ``s .. s + T - 1`` overwrites the ring rows of
    positions ``s - R .. s + T - 1 - R``, and its first query still sees
    position ``s - window + 1``: nothing a query of the launch sees is lost
    iff ``s + T - 1 - R < s - window + 1``, i.e. ``R >= window + T - 1``.
    ``launch_rows`` is the longest run of rows of ONE lane a launch carries
    (an engine's prefill chunk; a bucket's padding rows count, they are
    written too). ``None``: the arena's length, so any launch is safe and
    the ring never wraps (direct callers, tests of the plain path). The
    same bound makes a ring row's position a function of the launch's last
    position alone, which is what the kernels' masks compute."""
    up = lambda n: -(-n // block) * block  # noqa: E731
    if launch_rows is None:
        return up(max_seq)
    return min(up(window + launch_rows), up(max_seq))


def check_ring_launch(ring_len: int, arena_len: int, window: int, run: int) -> None:
    """Refuse a launch that carries ``run`` rows of one lane into a ring of
    ``ring_len`` rows sized for fewer (:func:`ring_rows`'s bound; a ring as
    long as the arena never wraps and takes any launch)."""
    if ring_len < arena_len and run > ring_len - window + 1:
        raise ValueError(
            f"a launch of {run} rows a lane would write over rows its own queries see: "
            f"the ring holds {ring_len} rows for a window of {window} (ring_rows)"
        )


def ring_plan(cfg: ModelConfig, dtype, launch_rows: int) -> dict:
    """``init_cache``'s keywords for a caller whose launches carry at most
    ``launch_rows`` rows of one lane (an engine's prefill chunk, bucket padding
    included; the decode lanes add one row each, to their own lanes): the ring
    is whole K/V blocks where the flash kernels read it. Empty for a model
    without window layers."""
    if not cfg.n_window:
        return {}
    from ..ops.attention import pallas_available
    from ..ops.pallas_attention import ring_block

    kernels = pallas_available(cfg.window_heads, cfg.n_kv_heads, cfg.head_dim)[0]
    return {
        "launch_rows": launch_rows,
        "block": ring_block(cfg.n_kv_heads, cfg.head_dim, dtype) if kernels else 1,
    }


class PagedKVCache(NamedTuple):
    """Block-table KV arena: a global pool of fixed-size pages
    ``[L, n_pages, KV, page_size, hd]`` (KV heads outside the page, so a
    (page, head) block is one tile-aligned ``[page_size, hd]`` slab — see
    ops/attention.py). A sequence owns a LIST of pages (its block table
    row) instead of a dense arena row, so resident sessions are bounded by
    the pool, not the compiled batch width, and shared prefixes are
    refcounted page mappings instead of copies. Same pytree shape
    discipline as :class:`KVCache` (two leaves, leading layer axis) so the
    engine's scan/donation/sharding machinery applies unchanged — under tp
    the KV-head axis (2) shards like the dense arena's."""

    k: jnp.ndarray
    v: jnp.ndarray

    @staticmethod
    def create(
        cfg: ModelConfig,
        n_pages: int,
        page_size: int,
        dtype: jnp.dtype = jnp.bfloat16,
    ) -> "PagedKVCache":
        shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
        return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def init_cache(
    cfg: ModelConfig, lanes: int, max_seq: int, dtype: jnp.dtype = jnp.bfloat16, live: bool = True,
    launch_rows: int | None = None, block: int = 1,
):
    """The cache a model's ``forward`` reads and writes, built by the model:
    a :class:`KVCache`, a :class:`WindowKVCache` where some layers are
    windowed (``launch_rows``, ``block``: :func:`ring_rows`), or the hybrid
    block's pytree (models/hybrid.py: positional latent rows beside per-lane
    recurrent state). ``live=False`` starts a hybrid cache's lanes closed, as
    an engine wants them."""
    if cfg.is_hybrid:
        from . import hybrid

        return hybrid.init_cache(cfg, lanes, max_seq, dtype, live=live, launch_rows=launch_rows, block=block)
    if cfg.n_window:
        return WindowKVCache.create(cfg, lanes, max_seq, dtype, launch_rows, block)
    return KVCache.create(cfg, lanes, max_seq, dtype=dtype)


def snapshot_lane(cache: WindowKVCache, lane, bucket: int) -> dict:
    """Lane ``lane``'s leaves as a snapshot ships them: the global layers'
    rows up to ``bucket`` positions, the window layers' ring whole (its R
    rows are the last R positions wherever the lane stands)."""
    def row(a):
        return lax.dynamic_index_in_dim(a, lane, 1, keepdims=False)

    return {"k": row(cache.k)[:, :bucket], "v": row(cache.v)[:, :bucket], "wk": row(cache.wk), "wv": row(cache.wv)}


def restore_lane(cache: WindowKVCache, lane, leaves: dict) -> WindowKVCache:
    """Write a snapshot's leaves back into lane ``lane`` (the global rows
    from position 0, the ring whole)."""
    def put(arena, value):
        return lax.dynamic_update_slice(arena, value[:, None].astype(arena.dtype), (0, lane, 0, 0, 0))

    return WindowKVCache(*(put(getattr(cache, n), leaves[n]) for n in WindowKVCache._fields))


def init_params(cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16) -> dict:
    """Random init (truncated-normal-ish 0.02 scale). Checkpoint loading maps
    onto the same pytree (engine/checkpoint.py)."""
    if cfg.is_hybrid:
        from . import hybrid

        return hybrid.init_params(cfg, key, dtype)
    keys = iter(jax.random.split(key, 16))
    d, hd = cfg.dim, cfg.head_dim

    def w(k, *shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    layers = {
        "attn_norm": jnp.ones((cfg.n_layers, d), dtype),
        "wq": w(next(keys), cfg.n_layers, d, cfg.n_heads * hd),
        "wk": w(next(keys), cfg.n_layers, d, cfg.n_kv_heads * hd),
        "wv": w(next(keys), cfg.n_layers, d, cfg.n_kv_heads * hd),
        "wo": w(next(keys), cfg.n_layers, cfg.n_heads * hd, d),
        "mlp_norm": jnp.ones((cfg.n_layers, d), dtype),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((cfg.n_layers, cfg.n_heads * hd), dtype)
        layers["k_norm"] = jnp.ones((cfg.n_layers, cfg.n_kv_heads * hd), dtype)
    if cfg.is_moe:
        layers.update(
            {
                "router": w(next(keys), cfg.n_layers, d, cfg.n_experts),
                "w_gate": w(next(keys), cfg.n_layers, cfg.n_held, d, cfg.ffn_dim),
                "w_up": w(next(keys), cfg.n_layers, cfg.n_held, d, cfg.ffn_dim),
                "w_down": w(next(keys), cfg.n_layers, cfg.n_held, cfg.ffn_dim, d),
            }
        )
    else:
        layers.update(
            {
                "w_gate": w(next(keys), cfg.n_layers, d, cfg.ffn_dim),
                "w_up": w(next(keys), cfg.n_layers, d, cfg.ffn_dim),
                "w_down": w(next(keys), cfg.n_layers, cfg.ffn_dim, d),
            }
        )
    return {
        "embed": w(next(keys), cfg.vocab_size, d),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w(next(keys), d, cfg.vocab_size),
    }


def _mlp(x: jnp.ndarray, lp: dict, act: str = "silu") -> jnp.ndarray:
    """SwiGLU (ReGLU under ``act="relu"``)."""
    gate = gate_act(act)(x @ lp["w_gate"])
    return (gate * (x @ lp["w_up"])) @ lp["w_down"]


def moe_gates(
    logits: jnp.ndarray, cfg: ModelConfig, dtype, bias: jnp.ndarray | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The router rule, once for every MoE path: ``(gates, chosen)``, both
    ``[..., k]``, from router logits ``[..., E]``. ``moe_renormalize``
    (Mixtral): top-k of the logits, float32 softmax over the chosen k, so a
    token's gates sum to 1 (times ``moe_scale`` where the model has one: the
    softmax over all E, its top k renormalised, is the same numbers).
    Otherwise (OLMoE, ``norm_topk_prob: false``):
    float32 softmax over all E experts, the top k kept as they are.
    ``moe_router == "sigmoid"`` (Kimi-Linear): float32 sigmoid scores; the
    top k of score + ``bias`` (the selection bias chooses and never weighs);
    the chosen scores, divided by their sum under ``moe_renormalize``, times
    ``moe_scale``."""
    k = cfg.experts_per_token
    if cfg.moe_router == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, chosen = lax.top_k(scores if bias is None else scores + bias.astype(jnp.float32), k)
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.moe_renormalize:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return (gates * cfg.moe_scale).astype(dtype), chosen
    if cfg.moe_renormalize:
        top, chosen = lax.top_k(logits, k)
        gates = jax.nn.softmax(top.astype(jnp.float32), axis=-1)
        if cfg.moe_scale != 1.0:  # Laguna: the renormalised gates x 2.5
            gates = gates * cfg.moe_scale
        return gates.astype(dtype), chosen
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, chosen = lax.top_k(probs, k)
    return gates.astype(dtype), chosen


def _moe_mlp(x: jnp.ndarray, lp: dict, cfg: ModelConfig, logits: jnp.ndarray | None = None) -> jnp.ndarray:
    """All-experts einsum MoE (top-k routing, every expert computed, masked
    combine): exact, dropless, branch-free, and E/k× the routed FLOPs.

    Serves every call that is weight-bound anyway — decode, speculation's
    verify, the short prefill buckets: fewer rows than
    ``ops/moe.sorted_from_rows`` — on one chip, and every call of a ``tp``
    mesh. Calls with more rows on one chip take ``_moe_mlp_sorted``; an
    ``ep > 1`` mesh (or the ``routed`` option) takes ``_moe_mlp_routed``.

    A chip that holds a share of the experts (``cfg.experts_held``) routes
    over all of them and sums the terms of the experts in its stack: a choice
    of an absent expert one-hots to a zero row. ``logits [B, T, E]``: the
    router's logits where the caller computes them itself (the hybrid block,
    in float32: models/hybrid.router_logits).
    """
    b, t, d = x.shape
    if logits is None:
        logits = x @ lp["router"]
    weights, chosen = moe_gates(logits, cfg, x.dtype, lp.get("router_bias"))  # [B,T,K]
    if cfg.expert_offset:
        chosen = chosen - cfg.expert_offset
    onehot = jax.nn.one_hot(chosen, cfg.n_held, dtype=x.dtype)  # [B,T,K,E held]
    combine = jnp.einsum("btk,btke->bte", weights, onehot)  # [B,T,E]
    gate = gate_act(cfg.ffn_act)(jnp.einsum("btd,edf->btef", x, lp["w_gate"]))
    up = jnp.einsum("btd,edf->btef", x, lp["w_up"])
    expert_out = jnp.einsum("btef,efd->bted", gate * up, lp["w_down"])
    return jnp.einsum("bted,bte->btd", expert_out, combine)


def _moe_mlp_sorted(
    x: jnp.ndarray,
    lp: dict,
    cfg: ModelConfig,
    experts: dict,
    layer,
    logits: jnp.ndarray | None = None,
    routed: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The MoE FFN of a call with many rows (a prefill chunk): the same
    router and the same sum as ``_moe_mlp``, computing only the (token,
    chosen expert) pairs — sorted by expert, one grouped FFN over the
    stacked ``experts`` (``ops/moe.stacked_experts``: int8 as stored, layer
    ``layer`` of them read in place). Exact top-k and dropless at any skew;
    a bucket's padding rows are routed like any other row. ``routed [B·T]``
    bool: rows whose choices count; the others choose no expert, take no
    buffer row and get 0 (the mixed step's parked lanes: a row that is
    nobody's, alone at an expert, would stream that expert for one tile)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    logits = xf @ lp["router"] if logits is None else logits.reshape(b * t, -1)
    gates, chosen = moe_gates(logits, cfg, x.dtype, lp.get("router_bias"))
    if routed is not None:
        # expert -1 is nobody's: dropped before the sort, like an absent one
        chosen = jnp.where(routed[:, None], chosen, -1)
    held = (cfg.expert_offset, cfg.n_experts) if cfg.experts_held or routed is not None else None
    return sorted_moe_ffn(xf, gates, chosen, experts, layer, held=held, act=cfg.ffn_act).reshape(b, t, d)


def moe_sorted_from(cfg: ModelConfig, layers: dict) -> int | None:
    """The row count ``B·T`` from which a call sorts its MoE FFN: the rule
    (``ops/moe.sorted_from_rows``) applied to this model's experts as
    ``layers`` stores them (a hybrid model's whole pytree: its experts are
    the ``moe`` stack, and the rule is asked with what this chip holds);
    ``None`` where no call does."""
    if not cfg.is_moe:
        return None
    w = (layers["moe"] if "moe" in layers else layers)["w_gate"]
    dtype = w.q.dtype if isinstance(w, QTensor) else w.dtype
    return sorted_from_rows(cfg.n_held, cfg.experts_per_token, dtype)


def moe_sorts(cfg: ModelConfig, layers: dict, n_rows: int) -> bool:
    """Whether a call of ``n_rows`` rows sorts its MoE FFN. Static: decided
    when a step is traced."""
    cut = moe_sorted_from(cfg, layers)
    return cut is not None and n_rows >= cut


# token counts at or below this run routed MoE with cap = n (dropless) even
# for prefill-shaped (t > 1) calls, where dropless is free anyway. Decode
# calls (t == 1) are ALWAYS dropless via the shape gate in _moe_mlp_routed,
# whatever max_batch is.
_DROPLESS_MAX_N = 64


def routed_capacity(n_tokens: int, n_experts: int, k: int, capacity_factor: float) -> int:
    """Static per-expert dispatch-buffer size: ``capacity_factor`` × the
    perfectly-balanced share (n·k/E), clamped to n — top-k indices are
    distinct, so a token contributes at most ONE slot per expert and C = n
    is dropless no matter how skewed the router. Callers force
    droplessness with a large factor."""
    import math

    return max(1, min(n_tokens, math.ceil(n_tokens * k / n_experts * capacity_factor)))


def _moe_mlp_routed(
    x: jnp.ndarray,
    lp: dict,
    cfg: ModelConfig,
    *,
    capacity_factor: float = 2.0,
    base: int = 0,
) -> jnp.ndarray:
    """Top-k token-dispatch MoE — GShard-style one-hot dispatch/combine
    einsums (static shapes, MXU matmuls, no gather/scatter). The ``routed``
    option's path and, through parallel/expert.py, the ``ep > 1`` mesh's; no
    default one-chip engine runs it (prefill there is ``_moe_mlp_sorted``,
    which drops nothing).

    Computes ONLY routed (token, expert) work: per-token MLP FLOPs are
    ∝ k·capacity_factor, not E — the dense ``_moe_mlp`` computes every
    expert for every token and masks at combine, ~E/k× wasted FLOPs
    (VERDICT r3 missing #5). A token overflowing an expert's capacity
    loses that expert's contribution (GShard drop semantics); capacity
    clamps at N so droplessness is one large factor away.

    ``base`` supports the EP shard_map wrapper (parallel/expert.py): the
    router is replicated so routing runs over the FULL expert set on every
    device, while ``lp`` carries only the E/ep local experts starting at
    ``base`` — out-of-range choices one-hot to zero rows, and a psum over
    ep combines the per-device partial outputs.
    """
    b, t, d = x.shape
    w_gate = lp["w_gate"]
    e_loc = w_gate.shape[0]
    n, k = b * t, cfg.experts_per_token
    # Decode-sized calls (t==1, n = max_batch) go DROPLESS: the engine's
    # pipelined decode feeds every lane — including parked/idle ones —
    # through this path, and cumsum slot assignment would let a parked
    # lane's garbage token steal a real token's expert capacity (ADVICE
    # r4). Gate on the CALL SHAPE, not a fixed token count: the old
    # n <= _DROPLESS_MAX_N gate silently reverted engines configured with
    # max_batch > 64 to cf-capped routing — exactly the stealing bug again
    # (ADVICE r5). cap = n makes stealing impossible and costs almost
    # nothing at decode batch sizes; prefill (t = bucket, all real tokens
    # from ONE sequence) keeps the cf-bounded buffers unless it is small
    # enough that dropless is free anyway.
    if t == 1 or n <= _DROPLESS_MAX_N:
        cap = n
    else:
        cap = routed_capacity(n, cfg.n_experts, k, capacity_factor)
    xf = x.reshape(n, d)
    # [N, E] logits over the full expert set
    weights, chosen = moe_gates(xf @ lp["router"], cfg, x.dtype)
    # one-hot over LOCAL experts; choices outside [base, base+e_loc) fall
    # out of range and one-hot to all-zero rows
    local = (chosen - base).reshape(n * k)
    oh = jax.nn.one_hot(local, e_loc, dtype=jnp.float32)  # [S, E_loc]
    # each assignment's slot in its expert's buffer = how many earlier
    # assignments picked that expert (f32 cumsum is exact well past any
    # realistic S); slots ≥ cap one-hot to zero → the token drops
    slot = ((jnp.cumsum(oh, axis=0) - 1.0) * oh).astype(jnp.int32)
    disp = oh[:, :, None] * jax.nn.one_hot(slot, cap, dtype=jnp.float32)
    disp = disp.reshape(n, k, e_loc, cap)
    # a token's k choices are distinct experts, so summing over k leaves at
    # most one nonzero per (token, expert) — dispatch/combine stay one-hot
    disp_tok = disp.sum(1).astype(x.dtype)  # [N, E_loc, C]
    combine_tok = (disp * weights[..., None, None]).sum(1).astype(x.dtype)
    xe = jnp.einsum("nd,nec->ecd", xf, disp_tok)  # gather into [E_loc, C, D]
    gate = gate_act(cfg.ffn_act)(jnp.einsum("ecd,edf->ecf", xe, w_gate))
    up = jnp.einsum("ecd,edf->ecf", xe, lp["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", gate * up, lp["w_down"])
    out = jnp.einsum("ecd,nec->nd", out_buf, combine_tok)  # weighted scatter
    return out.reshape(b, t, d)


def _parked(positions: jnp.ndarray, arena_len: int) -> jnp.ndarray:
    """One-token rows of the dense arena that sit at its last row: where the
    engine parks idle, finished and still-prefilling lanes (its ``scratch``;
    admission keeps real lanes below it). Their output is nobody's."""
    return positions >= arena_len - 1


def _seen(positions: jnp.ndarray, arena_len: int) -> jnp.ndarray:
    """What a one-token row of the dense arena attends up to: its own
    position, or row 0 alone where it is parked, so such a lane reads one K/V
    block and not all S positions. Its rows are still WRITTEN at the true
    position: only the read's bound changes."""
    return jnp.where(_parked(positions, arena_len), 0, positions)


def _ring_index(positions: jnp.ndarray, ring: int, arena_len: int) -> jnp.ndarray:
    """Where a window layer's ring keeps the rows of ``positions``: ``p mod
    R``. A row at or past the arena's last row is nobody's (a parked lane, a
    bucket's padding run past the arena) and goes nowhere: index ``R`` is out
    of range and the scatter drops it, where the global leaf has its own last
    row for such writes; in a ring that row would be a live position's."""
    return jnp.where(positions >= arena_len - 1, ring, positions % ring)


def _cache_attention(
    q, k, v, ck, cv, positions, cache_attn_impl, block_table, layer, slot,
    chunk_positions=None, lane_positions=None, window: int = 0, arena_len: int | None = None,
):
    """Write a layer's new rows into its leaf of the stacked arena and attend
    over it: ``(attn [B, T, H, hd], ck, cv)``. ``window``: the leaf is a ring
    of ``ck.shape[2]`` rows (``WindowKVCache``) and a row sees the last
    ``window`` positions; ``arena_len`` is then the GLOBAL leaf's length (where
    parked lanes sit). Without ``window`` this is the dense arena's path,
    traced as it always was."""
    b, t = q.shape[:2]
    kw = {"window": window} if window else {}
    if arena_len is None:
        arena_len = ck.shape[2]
    if window:
        ring = ck.shape[2]

        def at(p):
            return _ring_index(p, ring, arena_len)
    else:
        def at(p):
            return p
    if lane_positions is not None:
        n_lanes = lane_positions.shape[0]
        tc = t - n_lanes

        def groups(a):  # the chunk's rows [1, T, ...], the lanes' [B, 1, ...]
            return a[:, :tc], a[0, tc:, None]

        (qc, ql), (kc, kl), (vc, vl) = groups(q), groups(k), groups(v)
        lanes = jnp.arange(n_lanes)[:, None]
        ck = ck.at[layer, slot, at(chunk_positions)].set(kc).at[layer, lanes, at(lane_positions)].set(kl)
        cv = cv.at[layer, slot, at(chunk_positions)].set(vc).at[layer, lanes, at(lane_positions)].set(vl)
        attn_c = cache_attn_impl(qc, ck, cv, chunk_positions, None, layer, slot, **kw)
        attn_l = cache_attn_impl(ql, ck, cv, _seen(lane_positions, arena_len), None, layer, None, **kw)
        attn = jnp.concatenate([attn_c, attn_l.reshape(1, n_lanes, *q.shape[2:])], axis=1)
    else:
        if layer is None:
            raise ValueError("a cache is the stacked arena: say which layer of it")
        if block_table is not None:
            # paged arena: write through the block table into pool pages —
            # same masking rule, same numbers as the dense scatter below
            ck, cv = scatter_paged_kv(ck, cv, k, v, block_table, positions, layer)
        else:
            # scatter this step's K/V rows, and only them, into the stack at
            # per-sequence positions (rows past S — bucket padding — drop)
            rows = jnp.arange(b)[:, None] + (0 if slot is None else slot)
            ck = ck.at[layer, rows, at(positions)].set(k)
            cv = cv.at[layer, rows, at(positions)].set(v)
            if t == 1:
                positions = _seen(positions, arena_len)
        attn = cache_attn_impl(q, ck, cv, positions, block_table, layer, slot, **kw)
    return attn, ck, cv


def _attention_block(
    x: jnp.ndarray,
    lp: dict,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    mask: jnp.ndarray,
    ck: jnp.ndarray | None,
    cv: jnp.ndarray | None,
    use_flash: bool,
    cache_attn_impl=None,
    block_table=None,
    layer=None,
    slot=None,
    lane_positions=None,
    ring=None,
    kind=None,
):
    """One layer's attention. With a cache, ``ck``/``cv`` are the STACKED
    arena ``[L, B, S, KV, hd]`` (or page pool) and ``layer`` this layer's
    index in it: the layer's new rows are written into the stack and the
    attention reads layer ``layer`` of it, so nothing the size of a layer
    is sliced out, copied or written back. ``slot`` offsets the batch's
    rows in the arena (one lane's prefill in the whole arena).

    ``lane_positions [B, 1]``: ``x`` is ``[1, T + B, d]``, a chunk's T rows
    for arena row ``slot`` and then one row for each of the arena's B lanes
    (``forward``'s second group). The projections and the rotary embedding
    run over all of them at once; both groups' new rows are written before
    either is read, and each group's attention reads its own arena rows.

    A model with window layers (``cfg.window_layers``): ``kind = (windowed,
    rotary)``, this layer's two flags as the layer scan hands them (traced
    scalars), ``ring = (wk, wv)`` the window layers' leaf beside the global
    layers' ``ck``/``cv``, and ``layer`` the layer's index in ITS leaf. Returns
    ``ring`` as a fourth value then."""
    b, t, d = x.shape
    if lane_positions is not None:
        chunk_positions = positions
        positions = jnp.concatenate([positions, lane_positions.reshape(1, -1)], axis=1)
    else:
        chunk_positions = None
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k = h @ lp["wq"], h @ lp["wk"]
    if cfg.qk_norm:
        # over all heads' values at once, before the split and the rotary
        # embedding; under tp the columns are sharded and GSPMD reduces
        # across the shards (tests/test_olmoe.py pins it)
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    if kind is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        # a layer without the flag has no positional embedding at all
        windowed, rotary = kind[:2]
        q = jnp.where(rotary, apply_rope(q, positions, cfg.rope_theta), q)
        k = jnp.where(rotary, apply_rope(k, positions, cfg.rope_theta), k)

    if ck is not None and cache_attn_impl is None:
        # engines choose once at build and pass their choice in (it is
        # what they report); direct callers get the same choice here
        cache_attn_impl = plan_cache_attention(
            cfg.n_heads,
            cfg.n_kv_heads,
            cfg.head_dim,
            page_size=ck.shape[3] if block_table is not None else 0,
            use_pallas=use_flash,
        ).fn
    if ck is not None and kind is not None:
        arena_len = ck.shape[2]

        def attend(leaf_k, leaf_v, window):
            return _cache_attention(
                q, k, v, leaf_k, leaf_v, positions if chunk_positions is None else None,
                cache_attn_impl, None, layer, slot, chunk_positions, lane_positions,
                window=window, arena_len=arena_len,
            )

        def on_global(c):
            with jax.named_scope("attn_global"):
                return attend(c[1], c[2], 0)

        def on_window(c):
            with jax.named_scope("attn_window"):
                return attend(c[1], c[2], cfg.window)

        if ring is None:  # per-layer rotary flags alone: one leaf, no window
            attn, ck, cv = attend(ck, cv, 0)
        else:
            # Either leaf as a loop of 0 or 1 trips over the stack it updates:
            # a ``lax.cond`` would copy the leaf its branch passes through
            # untouched (models/hybrid.py found that on the chip); a while
            # loop's carry stays one buffer whether it trips or not
            trips = windowed.astype(jnp.int32)
            attn, ck, cv = lax.fori_loop(0, 1 - trips, lambda _, c: on_global(c), (jnp.zeros_like(q), ck, cv))
            attn, *ring = lax.fori_loop(0, trips, lambda _, c: on_window(c), (attn, *ring))
    elif ck is not None:
        attn, ck, cv = _cache_attention(
            q, k, v, ck, cv, positions if chunk_positions is None else None,
            cache_attn_impl, block_table, layer, slot, chunk_positions, lane_positions,
        )
    elif kind is not None:
        # no cache: the window is a mask over the tokens given
        i = jnp.arange(t)
        near = (i[:, None] - i[None, :]) < jnp.where(windowed, cfg.window, t)
        attn = attention_reference(q, k, v, mask=mask & near[None])
    elif use_flash:
        attn = flash_attention(q, k, v, causal=True)
    else:
        attn = attention_reference(q, k, v, mask=mask)
    out = attn.reshape(b, t, cfg.n_heads * cfg.head_dim) @ lp["wo"]
    if kind is not None:
        return x + out, ck, cv, None if ring is None else tuple(ring)
    return x + out, ck, cv


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32
    positions: jnp.ndarray,  # [B, T] int32
    cache: KVCache | None = None,
    use_flash: bool = True,
    cache_attn_impl=None,
    moe_impl=None,
    block_table: jnp.ndarray | None = None,
    slot: jnp.ndarray | None = None,
    valid: jnp.ndarray | None = None,
    lanes: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    last: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache | None]:
    """Returns (logits [B, T, V], updated cache).

    A config with ``layer_kinds`` is the hybrid block (models/hybrid.py: its
    own cache pytree, ``cache_attn_impl`` its plan, ``valid [B, T]`` the rows
    of a bucket that are real — a recurrent state, unlike K/V rows, must not
    see padding).

    With a cache: serves prefill (T = prompt chunk) and decode (T = 1) with
    per-sequence positions — the continuous-batching engine relies on this.
    The stacked arena rides in the layer scan's carry: a layer writes its
    B × T new rows into it and the attention reads that layer of it where
    it lies, so a donated arena stays one buffer through the whole step.
    With ``slot`` the batch's rows are arena rows ``slot .. slot + B`` (a
    lane's prefill against the whole arena, no row sliced out).
    With ``block_table`` the cache is a :class:`PagedKVCache` pool and
    every KV read/write goes through the table (paged serving); the cache
    returned is the updated pool.
    With ``lanes = (tokens [B, 1], positions [B, 1])`` the launch carries a
    second group of rows: ``tokens [1, T]`` is a lane's prefill chunk at arena
    row ``slot`` and ``lanes`` one decode step of each of the arena's B lanes.
    The layer scan's body runs the ``T + B`` rows together through everything
    that reads weights, so a launch streams them once for both; each group's
    attention reads its own arena rows. The head runs on ``1 + B`` rows and
    the logits returned are ``[1 + B, V]``: the chunk's row ``last``, then
    the lanes' (the dense arena, or the hybrid block's cache whatever its
    layers' kinds; no page pool).
    Without: pure causal self-attention over the tokens given (what tests
    compare the cached path with).
    A config with ``window_layers`` / ``rope_layers`` hands each layer its
    two flags as scan inputs; its cache is a :class:`WindowKVCache` (the
    window layers' leaf a ring), and a launch may carry at most
    ``R - window + 1`` rows of one lane (:func:`ring_rows`).
    ``moe_impl`` overrides the MoE MLP (routed token-dispatch, meshed EP,
    the einsum pinned under a ``tp`` mesh). Without one the call's static
    row count decides (``moe_sorts``): few rows take the all-experts einsum,
    many the sorted grouped FFN, which reads the expert stack in place — so
    the experts then stay out of the layer scan's slices altogether.
    """
    if cfg.is_hybrid:
        from . import hybrid

        if block_table is not None:
            raise ValueError("the hybrid block has no paged cache")
        # ``lanes``: for every ``layer_kinds`` (hybrid.forward's docstring says how)
        return hybrid.forward(
            params, cfg, tokens, positions, cache,
            plan=cache_attn_impl, moe_impl=moe_impl, slot=slot, valid=valid, lanes=lanes, last=last,
        )
    x = embed_lookup(params["embed"], tokens)
    lane_positions = None
    if lanes is not None:
        if cache is None or slot is None or block_table is not None or tokens.shape[0] != 1:
            raise ValueError("lanes ride one lane's chunk at ``slot`` of a dense arena")
        lane_tokens, lane_positions = lanes
        x = jnp.concatenate([x, embed_lookup(params["embed"], lane_tokens[:, 0])[None]], axis=1)
    lp_stack = params["layers"]
    experts = routed = None
    if moe_impl is None and moe_sorts(cfg, lp_stack, x.shape[0] * x.shape[1]):
        experts = stacked_experts(lp_stack)
        lp_stack = {k: v for k, v in lp_stack.items() if k not in EXPERT_WEIGHTS}
        if lanes is not None:
            # the chunk's rows, and the lanes that step: a parked lane's row
            # is routed nowhere (``_moe_mlp_sorted``)
            live = ~_parked(lane_positions[:, 0], cache.k.shape[2])
            routed = jnp.concatenate([jnp.ones(tokens.shape[1], bool), live])
    if cache is not None:
        mask = None  # arena attention masks from positions (in-kernel on TPU)
    else:
        t = tokens.shape[1]
        mask = jnp.broadcast_to(causal_mask(t), (tokens.shape[0], t, t))
    # the per-layer switches of a model that has them, as scan inputs beside
    # the weights (static absences everywhere else: no operand, the programs
    # of every other model are what they were)
    switched = bool(cfg.n_window or cfg.rope_layers)
    if (cfg.early_router or cfg.ffn_act != "silu") and moe_impl is not None:
        raise ValueError("a pinned MoE path (routed, or a mesh) computes its own router and SwiGLU")
    if switched and block_table is not None:
        raise ValueError("window and no-rope layers are served from the dense arena, not the page pool")
    if cfg.n_window and cache is not None:
        check_ring_launch(cache.wk.shape[2], cache.k.shape[2], cfg.window, tokens.shape[1])

    def block(x, ck, cv, lp, layer, ring=None, kind=None):
        # int8-quantized weights (engine/quant.py) dequantize per layer
        # slice here: HBM holds the int8 stack, only the current layer is
        # dense, and XLA fuses the convert into the consuming matmuls
        lp = {k: dequant(v) for k, v in lp.items()}
        logits = None
        if cfg.early_router:
            # the router reads the stream as it enters the layer
            # (float32 logits: a near-tie among 64 decided by a bfloat16
            # rounding sends a token to another expert)
            with jax.named_scope("moe_early_router"):
                logits = jnp.dot(x, lp["router"], preferred_element_type=jnp.float32)
        x, ck, cv, *ring = _attention_block(
            x, lp, cfg, positions, mask, ck, cv, use_flash,
            cache_attn_impl=cache_attn_impl,
            block_table=block_table,
            layer=layer,
            slot=slot,
            lane_positions=lane_positions,
            **({"ring": ring, "kind": kind} if kind is not None else {}),
        )
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        early = {"logits": logits} if logits is not None else {}
        if experts is not None:
            x = x + _moe_mlp_sorted(h, lp, cfg, experts, moe_layer(layer, kind), routed=routed, **early)
        elif cfg.is_moe:
            x = x + (moe_impl(h, lp) if moe_impl is not None else _moe_mlp(h, lp, cfg, **early))
        else:
            x = x + _mlp(h, lp, cfg.ffn_act)
        return (x, ck, cv, *ring)

    def moe_layer(layer, kind):
        # the expert stack is indexed by the model's layer; ``layer`` is the
        # index in the layer's own cache leaf where the leaves are two
        return layer if kind is None else kind[2]

    if switched:
        n = cfg.n_layers
        win = np.asarray(cfg.window_layers or (0,) * n, bool)
        rot = np.asarray(cfg.rope_layers or (1,) * n, bool)
        leaf_idx = np.where(win, np.cumsum(win) - 1, np.cumsum(~win) - 1).astype(np.int32)
        kinds = (jnp.asarray(win), jnp.asarray(rot), jnp.arange(n, dtype=jnp.int32))
        if cache is not None and cfg.n_window:
            def layer_step(carry, inputs):
                lp, layer, kind = inputs
                x, ck, cv, wk, wv = carry
                x, ck, cv, ring = block(x, ck, cv, lp, layer, (wk, wv), kind)
                return (x, ck, cv, *ring), None

            (x, *leaves), _ = lax.scan(
                layer_step, (x, *cache), (lp_stack, jnp.asarray(leaf_idx), kinds)
            )
            new_cache = type(cache)(*leaves)
        elif cache is not None:
            def layer_step(carry, inputs):
                lp, layer, kind = inputs
                return block(*carry, lp, layer, None, kind)[:3], None

            (x, new_k, new_v), _ = lax.scan(
                layer_step, (x, cache.k, cache.v), (lp_stack, kinds[2], kinds)
            )
            new_cache = type(cache)(new_k, new_v)
        else:
            x, _ = lax.scan(
                lambda x, inp: (block(x, None, None, inp[0], inp[1][2], None, inp[1])[0], None),
                x, (lp_stack, kinds),
            )
            new_cache = None
    elif cache is not None:
        def layer_step(carry, inputs):
            lp, layer = inputs
            return block(*carry, lp, layer), None

        layers = jnp.arange(cache.k.shape[0], dtype=jnp.int32)
        (x, new_k, new_v), _ = lax.scan(
            layer_step, (x, cache.k, cache.v), (lp_stack, layers)
        )
        new_cache = type(cache)(new_k, new_v)
    else:
        layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        x, _ = lax.scan(
            lambda x, inp: (block(x, None, None, *inp)[0], None), x, (lp_stack, layers)
        )
        new_cache = None

    if lanes is not None:
        # the head's rows: the chunk's ``last`` and the lanes', not T + B
        t = tokens.shape[1]
        x = jnp.concatenate([lax.dynamic_slice_in_dim(x[0], last, 1, 0), x[0, t:]], axis=0)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ dequant(params["lm_head"])).astype(jnp.float32)
    return logits, new_cache


def greedy_decode(
    params: dict,
    cfg: ModelConfig,
    prompt: jnp.ndarray,  # [B, Tp]
    max_new_tokens: int,
    cache_len: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> jnp.ndarray:
    """Reference generation loop: prefill then a ``lax.scan`` decode.
    Engine-grade batching lives in engine/llm.py; this is the simple path
    used by tests and the graft entry."""
    b, tp = prompt.shape
    cache = init_cache(cfg, b, cache_len, dtype=dtype)
    positions = jnp.broadcast_to(jnp.arange(tp), (b, tp))
    logits, cache = forward(params, cfg, prompt, positions, cache)
    last = jnp.argmax(logits[:, -1], axis=-1)  # [B]

    def step(carry, i):
        cache, tok, pos = carry
        logits, cache = forward(
            params, cfg, tok[:, None], pos[:, None], cache
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        return (cache, nxt, pos + 1), nxt

    (_, _, _), toks = lax.scan(
        step, (cache, last, jnp.full((b,), tp)), jnp.arange(max_new_tokens - 1)
    )
    return jnp.concatenate([last[:, None], toks.T], axis=1)  # [B, max_new_tokens]
