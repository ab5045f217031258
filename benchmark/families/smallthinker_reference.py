"""Plain reference forward of the ``smallthinker`` family (``families/smallthinker.py``).

SmallThinker-21BA3B's decoder block as ISSUE 37 reads it from the catalog
row's ``config`` and ``described_as`` (the sandbox has no network and the
upstream ``modeling_smallthinker.py`` is quoted from memory of its llama.cpp
graph; every line the published ``config.json`` does not state is listed
under ``assumed`` in the configuration file, with the same words). For layer
``l`` with input ``x`` (hidden 2560):

    r  = x @ Wr                         router logits [64] from the layer's INPUT, the
                                        residual stream before any norm (ASSUMED: which
                                        side of input_layernorm the upstream code reads)
    h  = rmsnorm(x; w_attn_norm)
    q  = h @ Wq -> 28 x 128, k = h @ Wk -> 4 x 128, v = h @ Wv -> 4 x 128
                                        head_dim is its own key; no bias, no QK-norm (ASSUMED)
    rope_layout[l] == 1: q, k = rope(q), rope(k)   rotate-half pairs (ASSUMED), theta 1.5e6
    rope_layout[l] == 0: no positional embedding at all
    s  = q k^T * 128^-0.5, causal; sliding_window_layout[l] == 1: query i sees keys j
         with 0 <= i - j < sliding_window_size
    x  = x + softmax(s) v @ Wo
    h2 = rmsnorm(x; w_mlp_norm)
    g, e = softmax(top_6(r))            float32 softmax over the chosen six (norm_topk_prob)
    x  = x + sum_i g_i * (relu(h2 @ Wg[e_i]) * (h2 @ Wu[e_i])) @ Wd[e_i]     ReGLU (ASSUMED)
    logits = rmsnorm(x_L; w_final) @ W_head   (untied)

A chip that holds a share of the experts (``experts_held`` of them from
``expert_offset``) routes over all 64 and sums the terms of the experts it
holds: ``w_gate``/``w_up``/``w_down`` then carry that share only.

float32 throughout at ``highest`` matmul precision, the full causal forward
over the whole sequence with an explicit window mask: no kernels, no cache,
and nothing imported from ``agentainer_tpu`` or from another family's
reference. Query rows go through the attention in blocks of ``Q_BLOCK`` (a
``[28, T, T]`` score tensor of a 5k-token forward would be 3.6 GB beside the
program): the same sums, fewer of them alive at once. The comparison rule and
its tolerance are not here: ``harness/compare.py``, applied by
``harness/numerics_child.py`` to every family alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512  # query rows scored at once


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, H, hd]; rotate-half layout: the pair of (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, lp, n_heads, n_kv, head_dim, theta, rotary: bool, window: int, act):
    """``window`` 0: the whole causal context; else the last ``window`` keys."""
    t = h.shape[0]
    h = act(h)
    pos = jnp.arange(t)
    q = (h @ lp["wq"]).reshape(t, n_heads, head_dim)
    k = (h @ lp["wk"]).reshape(t, n_kv, head_dim)
    v = (h @ lp["wv"]).reshape(t, n_kv, head_dim)
    if rotary:
        q, k = rope(q, pos, theta), rope(k, pos, theta)
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
        outs.append(jnp.einsum("hts,shd->thd", probs, v).reshape(rows.shape[0], n_heads * head_dim))
    return act(jnp.concatenate(outs, axis=0)) @ lp["wo"]


def gates(logits, top_k):
    """Router logits ``[T, E]`` -> (gates, experts) ``[T, k]``: the largest k
    logits, a float32 softmax over those k (a token's gates sum to 1)."""
    top, chosen = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top.astype(jnp.float32), axis=-1), chosen


def reglu(x, w_gate, w_up, w_down, act, gate_act=jax.nn.relu):
    x = act(x)
    return act(gate_act(x @ w_gate) * (x @ w_up)) @ w_down


def moe(h2, logits, lp, top_k, expert_offset, act, gate_act=jax.nn.relu):
    """The held experts' FFN for every token (one mapped ``reglu``), weighted
    by the token's gate for that expert: 0 where the expert was not chosen.
    The router chooses among ALL experts; ``lp["w_gate"]`` holds experts
    ``expert_offset ..`` and only their terms are summed."""
    g, chosen = gates(logits, top_k)  # [T, k] over every expert
    held = expert_offset + jnp.arange(lp["w_gate"].shape[0])
    weight = jnp.sum(jnp.where(chosen[:, :, None] == held, g[:, :, None], 0.0), axis=1)  # [T, E held]
    outs = jax.lax.map(lambda w: reglu(h2, *w, act, gate_act), (lp["w_gate"], lp["w_up"], lp["w_down"]))
    return jnp.einsum("te,etd->td", weight, outs)  # outs [E held, T, D]


def layer(x, lp, *, n_heads, n_kv_heads, head_dim, rope_theta, norm_eps, top_k, rotary, window,
          expert_offset=0, act=lambda x: x, early_router=True, gate_act=jax.nn.relu):
    """One decoder layer ``[T, D] -> [T, D]``. ``early_router=False`` and
    ``gate_act=jax.nn.silu`` are the two blocks this one is NOT (a router on
    the normed post-attention stream; SwiGLU), kept for the tests that show
    the program tells them apart."""
    logits = act(x) @ lp["router"]  # the stream as it enters the layer
    x = x + attention(rms_norm(x, lp["attn_norm"], norm_eps), lp, n_heads, n_kv_heads, head_dim,
                      rope_theta, rotary, window, act)
    h2 = rms_norm(x, lp["mlp_norm"], norm_eps)
    if not early_router:
        logits = act(h2) @ lp["router"]
    return x + moe(h2, logits, lp, top_k, expert_offset, act, gate_act)


def forward(weights: dict, tokens, *, n_heads: int, n_kv_heads: int, head_dim: int, rope_theta: float,
            norm_eps: float, top_k: int, rope_layout, window_layout, window: int, expert_offset: int = 0,
            act=lambda x: x):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``,
    ``wq [D, H*hd]``, ``wk``/``wv [D, KV*hd]``, ``wo [H*hd, D]``, ``router
    [D, E]``, ``w_gate``/``w_up [E held, D, F]`` and ``w_down [E held, F, D]``.
    ``rope_layout``/``window_layout``: one 0/1 flag a layer, as published.
    """
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for i, lp in enumerate(weights["layers"]):
            x = layer(
                x, lp, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim, rope_theta=rope_theta,
                norm_eps=norm_eps, top_k=top_k, rotary=bool(rope_layout[i]),
                window=window if window_layout[i] else 0, expert_offset=expert_offset, act=act,
            )
        x = rms_norm(x, weights["final_norm"], norm_eps)
        return act(x) @ weights["lm_head"]
