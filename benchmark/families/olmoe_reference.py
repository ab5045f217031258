"""Plain reference forward of the ``olmoe`` family (``families/olmoe.py``).

OLMoE-1B-7B's decoder block, written from the public ``transformers``
``models/olmoe/modeling_olmoe.py`` and the paper (OLMoE: Open
Mixture-of-Experts Language Models, arXiv:2409.02060), from memory (the
sandbox has no network; what ``config.json`` does not state is listed under
``assumed`` in the configuration file):

    h  = rmsnorm(x; w_attn_norm)
    q  = rmsnorm(h @ Wq; w_q_norm)      over all heads' values, then split into heads
    k  = rmsnorm(h @ Wk; w_k_norm)
    v  = h @ Wv                         no bias, no clipping
    q, k = rope(q), rope(k)             rotate-half pairs, after the norm
    x  = x + softmax(q k^T / sqrt(hd), causal) v @ Wo
    h  = rmsnorm(x; w_mlp_norm)
    p  = softmax(h @ Wr)                over ALL experts
    g, e = top_k(p, k)                  NOT renormalised
    x  = x + sum_i g_i * (silu(h @ Wg[e_i]) * (h @ Wu[e_i])) @ Wd[e_i]
    logits = rmsnorm(x_L; w_final) @ W_head   (untied)

float32 throughout at ``highest`` matmul precision, the full causal forward
over the whole sequence: no kernels, no cache, no batching tricks, and
nothing imported from ``agentainer_tpu`` or from the ``llama`` family's
reference. The comparison rule and its tolerance are not here:
``harness/compare.py``, applied by ``harness/numerics_child.py`` to every
family alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def qk_norm(x, w, eps):
    """x ``[T, heads * hd]``: one norm over the whole projection."""
    return rms_norm(x, w, eps)


def gates(logits, top_k):
    """Router logits ``[T, E]`` -> (gates, experts) ``[T, k]``: a softmax over
    every expert, the largest k kept as they are (they sum to under 1)."""
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)


def rope(x, positions, theta):
    """x [T, H, hd]; rotate-half layout: the pair of (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, lp, n_heads, n_kv, theta, eps, act):
    t, d = x.shape
    x = act(x)
    hd = d // n_heads
    pos = jnp.arange(t)
    q = qk_norm(x @ lp["wq"], lp["q_norm"], eps).reshape(t, n_heads, hd)
    k = qk_norm(x @ lp["wk"], lp["k_norm"], eps).reshape(t, n_kv, hd)
    v = (x @ lp["wv"]).reshape(t, n_kv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, n_heads * hd)
    return act(out) @ lp["wo"]


def swiglu(x, w_gate, w_up, w_down, act):
    x = act(x)
    return act(jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(x, lp, top_k, act):
    """Every expert's FFN for every token (one mapped ``swiglu``, so the
    program is one expert's and not 64 copies of it), weighted by the token's
    gate for that expert: 0 where the expert was not chosen."""
    g, chosen = gates(act(x) @ lp["router"], top_k)  # [T, k]
    experts = jnp.arange(lp["router"].shape[-1])
    weight = jnp.sum(jnp.where(chosen[:, :, None] == experts, g[:, :, None], 0.0), axis=1)  # [T, E]
    outs = jax.vmap(lambda wg, wu, wd: swiglu(x, wg, wu, wd, act))(lp["w_gate"], lp["w_up"], lp["w_down"])
    return jnp.einsum("te,etd->td", weight, outs)  # outs [E, T, D]


def forward(weights: dict, tokens, *, n_heads: int, n_kv_heads: int, rope_theta: float, norm_eps: float, top_k: int, act=lambda x: x):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``,
    ``wq [D, H*hd]``, ``wk``/``wv [D, KV*hd]``, ``wo [H*hd, D]``, ``q_norm
    [H*hd]``, ``k_norm [KV*hd]``, ``router [D, E]``, ``w_gate``/``w_up
    [E, D, F]`` and ``w_down [E, F, D]``.
    """
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for lp in weights["layers"]:
            h = rms_norm(x, lp["attn_norm"], norm_eps)
            x = x + attention(h, lp, n_heads, n_kv_heads, rope_theta, norm_eps, act)
            x = x + moe(rms_norm(x, lp["mlp_norm"], norm_eps), lp, top_k, act)
        x = rms_norm(x, weights["final_norm"], norm_eps)
        return act(x) @ weights["lm_head"]
