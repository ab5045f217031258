"""Plain reference forward of the ``laguna`` family (``families/laguna.py``).

Laguna-XS.2's decoder as ISSUE 50 writes it down from the catalog row's
``config`` with the sibling row ``Laguna-S-2.1`` beside it (the sandbox has no
network; what ``config.json`` does not state is listed under ``assumed`` in the
configuration file, with the same words, and marked ASSUMED here). For layer
``l`` of kind ``t`` (``layer_types[l]``: full or sliding; ``H_t`` query heads,
``num_attention_heads_per_layer[l]``: 48 or 64) with input ``x``:

    h  = rmsnorm(x; w_attn_norm)
    q  = h @ Wq_t -> H_t x 128, k = h @ Wk -> 8 x 128, v = h @ Wv -> 8 x 128
                                        no bias, no QK-norm (ASSUMED: no key names one)
    g  = sigmoid(h @ Wg_t) -> H_t       one gate a head from the normed input (ASSUMED:
                                        ``gating: true``; the sibling says ``per-head``)
    full:    the FIRST 64 dims of every q and k head (partial_rotary_factor 0.5) are
             rotated, rotate-half pairs (i, i + 32) inside those 64 (ASSUMED), by YaRN's
             frequencies for a 64-wide head (theta 5e5, factor 64 over an original 4,096,
             beta_fast 64, beta_slow 1), cos and sin both x attention_factor 1.4158883
             (ASSUMED: as the public YaRN code does); the last 64 dims carry no position
    sliding: all 128 dims, plain theta 1e4
    s  = q k^T * 128^-0.5, causal; sliding: query i sees keys j with 0 <= i - j < 512
    x  = x + concat_head(g_head * (softmax(s) v)_head) @ Wo_t
    h2 = rmsnorm(x; w_mlp_norm)
    l == 0 (mlp_layer_types dense):  x = x + swiglu_8192(h2)
    else: p = softmax(h2 @ Wr) over 256 in float32, e = top_8(p),
          w = 2.5 * p_e / sum(p_e)      (ASSUMED: Qwen-MoE's order, norm_topk_prob)
          x = x + sum_i w_i * swiglu_512[e_i](h2) + swiglu_512_shared(h2)
                                        the shared expert added whole, ungated (ASSUMED)
    logits = rmsnorm(x_L; w_final) @ W_head   (untied)

A chip that holds a share of the experts (``experts_held`` of them from
``expert_offset``) routes over all 256 and sums the terms of the experts it
holds and the shared expert whole: ``w_gate``/``w_up``/``w_down`` then carry
that share only (a departure from the published model that the configuration
lists under ``reduced``; the mathematics of what is held is unchanged).

float32 throughout at ``highest`` matmul precision, the full causal forward
over the whole sequence with an explicit window mask: no kernels, no cache, no
batching tricks, and nothing imported from ``agentainer_tpu`` or from another
family's reference. Query rows go through the attention in blocks of
``Q_BLOCK`` (a ``[64, T, T]`` score tensor of a 4k-token forward would be 4.9
GB beside the program): the same sums, fewer of them alive at once. The
comparison rule and its tolerance are not here: ``harness/compare.py``,
applied by ``harness/numerics_child.py`` to every family alike.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # query rows scored at once


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int, beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies of a ``dim``-wide rotation (arXiv
    2309.00071): pair i keeps ``theta^(-2i/dim)`` where it turns ``beta_fast``
    times or more over the original context, takes it over ``factor`` where
    it turns ``beta_slow`` times or fewer, a linear ramp over the pair index
    between (the bounds truncated to whole pairs, as the public code does).
    A table of ``dim / 2`` constants, computed on the host and rounded once."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns: float) -> float:
        return dim * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(pair_of(beta_fast)), 0), min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray((f / factor) * ramp + f * (1.0 - ramp), jnp.float32)


def rope(x, positions, inv_freq, factor: float = 1.0):
    """``x [T, H, hd]``: the first ``2 · len(inv_freq)`` dims of every head
    rotated, rotate-half pairs (i, i + r/2) inside them, cos and sin times
    ``factor``; the dims after them pass as they are."""
    r = 2 * inv_freq.shape[0]
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]  # [T, r/2]
    cos, sin = factor * jnp.cos(ang)[:, None, :], factor * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2 : r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)


def attention(h, lp, n_kv, head_dim, inv_freq, rope_factor, window: int, act, gated: bool = True):
    """``window`` 0: the whole causal context; else the last ``window`` keys.
    The query heads are the kind's own: read off ``wq``'s width."""
    t = h.shape[0]
    h = act(h)
    pos = jnp.arange(t)
    n_heads = lp["wq"].shape[1] // head_dim
    q = rope((h @ lp["wq"]).reshape(t, n_heads, head_dim), pos, inv_freq, rope_factor)
    k = rope((h @ lp["wk"]).reshape(t, n_kv, head_dim), pos, inv_freq, rope_factor)
    v = (h @ lp["wv"]).reshape(t, n_kv, head_dim)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for start in range(0, t, Q_BLOCK):  # a block of query rows against every key
        rows = pos[start : start + Q_BLOCK]
        scores = jnp.einsum("thd,shd->hts", q[start : start + Q_BLOCK], k) * head_dim**-0.5
        back = rows[:, None] - pos[None, :]  # i - j
        seen = (back >= 0) & ((back < window) if window else True)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", probs, v))
    o = jnp.concatenate(outs, axis=0)  # [T, H, hd]
    if gated:
        o = o * jax.nn.sigmoid(h @ lp["wg"])[:, :, None]  # one gate a head
    return act(o.reshape(t, n_heads * head_dim)) @ lp["wo"]


def swiglu(x, w_gate, w_up, w_down, act):
    x = act(x)
    return act(jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def gates(logits, top_k: int, scale: float):
    """Router logits ``[T, E]`` -> (gates, experts) ``[T, k]``: float32
    softmax over ALL experts, the largest k, divided by their sum, times
    ``scale``."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, chosen = jax.lax.top_k(p, top_k)
    return scale * top / jnp.sum(top, axis=-1, keepdims=True), chosen


def moe(h2, lp, top_k, scale, expert_offset, act):
    """The held experts' FFN for every token (one mapped ``swiglu``), weighted
    by the token's gate for that expert (0 where it was not chosen), plus the
    shared expert whole. The router chooses among ALL experts;
    ``lp["w_gate"]`` holds experts ``expert_offset ..`` and only their terms
    are summed."""
    g, chosen = gates(act(h2) @ lp["router"], top_k, scale)
    held = expert_offset + jnp.arange(lp["w_gate"].shape[0])
    weight = jnp.sum(jnp.where(chosen[:, :, None] == held, g[:, :, None], 0.0), axis=1)  # [T, E held]
    outs = jax.lax.map(lambda w: swiglu(h2, *w, act), (lp["w_gate"], lp["w_up"], lp["w_down"]))
    routed = jnp.einsum("te,etd->td", weight, outs)  # outs [E held, T, D]
    return routed + swiglu(h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"], act)


def forward(weights: dict, tokens, *, n_kv_heads: int, head_dim: int, norm_eps: float, top_k: int,
            layer_types, window: int, full_rope: dict, sliding_theta: float, routed_scale: float,
            expert_offset: int = 0, act=lambda x: x):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``,
    ``wq [D, H_t*hd]``, ``wk``/``wv [D, KV*hd]``, ``wo [H_t*hd, D]``, ``wg
    [D, H_t]`` and either a dense ``w_gate``/``w_up [D, F]``, ``w_down [F, D]``
    or ``router [D, E]``, ``w_gate``/``w_up [E held, D, F]``, ``w_down [E
    held, F, D]`` and the shared expert's ``ws_gate``/``ws_up``/``ws_down``.
    ``layer_types``: ``"full"`` or ``"sliding"`` a layer, as published;
    ``full_rope``: ``theta``, ``factor``, ``original_max``, ``beta_fast``,
    ``beta_slow``, ``attention_factor``, ``rotary_dim`` of the full layers.
    """
    with jax.default_matmul_precision("highest"):
        full_freq = yarn_inv_freq(
            full_rope["rotary_dim"], full_rope["theta"], full_rope["factor"], full_rope["original_max"],
            full_rope["beta_fast"], full_rope["beta_slow"],
        )
        sliding_freq = 1.0 / (sliding_theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
        x = weights["embed"][tokens]
        for kind, lp in zip(layer_types, weights["layers"]):
            h = rms_norm(x, lp["attn_norm"], norm_eps)
            if kind == "full":
                x = x + attention(h, lp, n_kv_heads, head_dim, full_freq, full_rope["attention_factor"], 0, act)
            else:
                x = x + attention(h, lp, n_kv_heads, head_dim, sliding_freq, 1.0, window, act)
            h2 = rms_norm(x, lp["mlp_norm"], norm_eps)
            if "router" in lp:
                x = x + moe(h2, lp, top_k, routed_scale, expert_offset, act)
            else:
                x = x + swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"], act)
        x = rms_norm(x, weights["final_norm"], norm_eps)
        return act(x) @ weights["lm_head"]
