"""Plain reference forward of the ``llama`` family (``families/llama.py``).

A decoder-only transformer written from the published descriptions
(Mistral 7B, arXiv:2310.06825; Mixtral of Experts, arXiv:2401.04088; the
blocks are Llama's, arXiv:2302.13971): token embedding, then per layer
pre-RMSNorm -> grouped-query attention with rotary embeddings (rotate-half
layout, as the HF implementations) -> residual, pre-RMSNorm -> SwiGLU MLP or
a sparse mixture of SwiGLU experts (router logits, top-k, softmax over the
chosen k, weighted sum) -> residual; final RMSNorm and an untied output head.

float32 throughout at ``highest`` matmul precision, the full causal forward
over the whole sequence: no kernels, no cache, no batching tricks, and
nothing imported from ``agentainer_tpu``. Weights arrive as plain float32
arrays in the layout documented at ``forward``. The comparison rule and its
tolerance are not here: ``harness/compare.py``, applied by
``harness/numerics_child.py`` to every family alike.

Departure from the published models: none in the mathematics. Neither served
configuration uses a sliding window (Mistral-7B-v0.3 and Mixtral-8x7B-v0.1
publish ``sliding_window: null``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def attention(x, lp, n_heads, n_kv, theta, act):
    t, d = x.shape
    x = act(x)
    hd = d // n_heads
    pos = jnp.arange(t)
    q = rope((x @ lp["wq"]).reshape(t, n_heads, hd), pos, theta)
    k = rope((x @ lp["wk"]).reshape(t, n_kv, hd), pos, theta)
    v = (x @ lp["wv"]).reshape(t, n_kv, hd)
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
    logits = act(x) @ lp["router"]  # [T, E]
    top, chosen = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top, axis=-1)  # softmax over the chosen k
    out = jnp.zeros_like(x)
    for e in range(lp["router"].shape[-1]):
        w = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)  # [T]
        out = out + w[:, None] * swiglu(x, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], act)
    return out


def forward(weights: dict, tokens, *, n_heads: int, n_kv_heads: int, rope_theta: float, norm_eps: float, top_k: int = 0, act=lambda x: x):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``,
    ``wq [D, H*hd]``, ``wk``/``wv [D, KV*hd]``, ``wo [H*hd, D]`` and either
    ``w_gate``/``w_up [D, F]``, ``w_down [F, D]`` or, for a mixture,
    ``router [D, E]`` and the same three with a leading expert axis.
    """
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for lp in weights["layers"]:
            x = x + attention(rms_norm(x, lp["attn_norm"], norm_eps), lp, n_heads, n_kv_heads, rope_theta, act)
            h = rms_norm(x, lp["mlp_norm"], norm_eps)
            if "router" in lp:
                x = x + moe(h, lp, top_k, act)
            else:
                x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], act)
        x = rms_norm(x, weights["final_norm"], norm_eps)
        return act(x) @ weights["lm_head"]
