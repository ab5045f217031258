"""Plain reference forward of the ``mistral4`` family (``families/mistral4.py``).

Mistral-Small-4-119B-2603's text decoder, written from its public
``config.json`` (``model_type: mistral4``; its keys repeat DeepSeek-V3's letter
for letter) and, for what the keys do not state, from memory of the public
DeepSeek-V3 modelling code and of Mistral's own (the sandbox has no network;
those points are listed under ``assumed`` in the configuration file). Pre-norm
residual layers, ``x += mla(rmsnorm(x)); x += moe(rmsnorm(x))``, every layer
alike. With ``h`` the normed stream, ``p`` a token's position, H heads::

    c_q = rmsnorm(h W_qa; w_q_norm);   q = c_q W_qb  -> per head [q_n (nope), q_r (r)]
    [c_kv (R), k_r (r)] = h W_kva;     c = rmsnorm(c_kv; w_kv_norm)
    [k_n,h (nope), v_h (dv)] = c W_kvb,h
    q_r <- rope(q_r, p),  k_r <- rope(k_r, p)            one k_r for all heads
    q <- q * (1 + beta * ln(1 + floor(p / original_max)))    Llama-4's query scale
    s = (q_n . k_n + q_r . k_r) * (nope + r)^-1/2 * m^2,   m = 0.1 * mscale_all_dim * ln(factor) + 1
    causal softmax over s;  o_h = sum p v_h;  x += [o_1 .. o_H] W_o

``rope`` rotates ADJACENT pairs ``(2i, 2i + 1)`` (``rope_interleave``) by
``p * inv_freq_i`` with YaRN's frequencies (:func:`yarn_inv_freq`); cos and sin
are not scaled (``mscale == mscale_all_dim``: the ratio YaRN scales them by is
1). Then::

    g = softmax(h' W_r) over all E in float32;  the top k, divided by their sum
    x += sum_{e chosen and held here} g_e SwiGLU_e(h') + SwiGLU_shared(h')

**Held experts.** ``w_gate``/``w_up``/``w_down`` hold experts ``[offset,
offset + E_held)`` of the router's ``E``: the layer routes over all E, adds the
terms of the experts it is given, leaves the others out, and adds the shared
expert whole. With every expert held that is the published layer.

float32 throughout at ``highest`` matmul precision; every head's keys and
values expanded from the latent (no absorption), the attention a block of
queries at a time so that ``[H, T, T]`` is never whole (the mathematics is the
full causal softmax), a Python loop over the experts; no kernels, no cache, no
batching, and nothing imported from ``agentainer_tpu``. The constants (the
frequencies, ``m``) are computed once on the host in float64 and rounded to
float32. The comparison rule and its tolerance are not here:
``harness/compare.py``, applied by ``harness/numerics_child.py`` to every
family alike.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512  # queries scored at once: [H, 512, T] float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(d: int, theta: float, factor: float, original_max: int, beta_fast: float, beta_slow: float):
    """``[d / 2]`` float32. ``f_i = theta^(-2i/d)``; ``dim(n) = d ln(original_max
    / (2 pi n)) / (2 ln theta)`` is the pair that turns n times over the
    original context; ``low = floor(dim(beta_fast))``, ``high =
    ceil(dim(beta_slow))``, clipped to ``[0, d - 1]``; ``ramp_i = clip((i - low)
    / (high - low), 0, 1)``; ``inv_freq_i = (f_i / factor) ramp_i + f_i (1 -
    ramp_i)``. ``factor`` 1: plain RoPE."""
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    if factor == 1.0:
        return jnp.asarray(f, jnp.float32)

    def dim(n):
        return d * math.log(original_max / (2 * math.pi * n)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001  # the public code's guard against a ramp of no width
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(f / factor * ramp + f * (1.0 - ramp), jnp.float32)


def rotate(x, positions, inv_freq):
    """``x [T, n, d]``: the pair ``(x[2i], x[2i + 1])`` turned by ``p * inv_freq_i``."""
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]  # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def softmax_mscale(factor: float, mscale_all_dim: float) -> float:
    """YaRN's ``m``; the softmax scale takes its square."""
    if factor <= 1.0 or not mscale_all_dim:
        return 1.0
    return 0.1 * mscale_all_dim * math.log(factor) + 1.0


def query_scale(positions, beta: float, original_max: int):
    """``[T]``: 1 inside the original context, ``1 + beta ln(1 + n)`` in its n-th repeat."""
    return 1.0 + beta * jnp.log1p(jnp.floor(positions.astype(jnp.float32) / original_max))


def query_latent(x, lp, eps, act):
    """The query's low-rank pair: the NORMED latent goes into ``W_qb``."""
    return rms_norm(act(x) @ lp["wq_a"], lp["q_norm"], eps)


def mla(x, lp, *, heads, rank, nope, v_dim, eps, rope, act):
    """``rope``: ``inv_freq``, ``mscale`` (m), ``beta``, ``original_max``."""
    t = x.shape[0]
    pos = jnp.arange(t)
    q = (act(query_latent(x, lp, eps, act)) @ lp["wq_b"]).reshape(t, heads, -1)
    ckv = act(x) @ lp["wkva"]
    c, k_r = rms_norm(ckv[:, :rank], lp["kv_norm"], eps), ckv[:, rank:]
    kv = (act(c) @ lp["wkvb"]).reshape(t, heads, nope + v_dim)
    q_r = rotate(q[:, :, nope:], pos, rope["inv_freq"])
    k_r = rotate(k_r[:, None, :], pos, rope["inv_freq"])
    q = jnp.concatenate([q[:, :, :nope], q_r], axis=-1) * query_scale(pos, rope["beta"], rope["original_max"])[:, None, None]
    k = jnp.concatenate([kv[:, :, :nope], jnp.broadcast_to(k_r, (t, heads, k_r.shape[-1]))], axis=-1)
    v = kv[:, :, nope:]
    scale = rope["mscale"] ** 2 / math.sqrt(q.shape[-1])
    out = []
    for start in range(0, t, QUERY_BLOCK):  # a block of queries against every key up to its last
        end = min(t, start + QUERY_BLOCK)
        scores = jnp.einsum("thd,shd->hts", q[start:end], k[:end]) * scale
        seen = pos[start:end, None] >= pos[None, :end]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs, v[:end]))
    return act(jnp.concatenate(out, axis=0).reshape(t, heads * v_dim)) @ lp["wo"]


def swiglu(x, w_gate, w_up, w_down, act):
    x = act(x)
    return act(jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def gates(logits, top_k):
    """Router logits ``[T, E]`` -> (weights, experts) ``[T, k]``: softmax over
    all E, the top k, divided by their sum (``norm_topk_prob``)."""
    w, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return w / jnp.sum(w, axis=-1, keepdims=True), chosen


def shared_expert(x, lp, act):
    return swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], act)


def moe(x, lp, top_k, offset, act):
    w, chosen = gates(act(x) @ lp["router"], top_k)
    out = jnp.zeros_like(x)
    for i in range(lp["w_gate"].shape[0]):  # the experts held here: offset + i
        w_i = jnp.sum(jnp.where(chosen == offset + i, w, 0.0), axis=-1)  # [T]
        out = out + w_i[:, None] * swiglu(x, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i], act)
    if "ws_gate" in lp:
        out = out + shared_expert(x, lp, act)
    return out


def forward(
    weights: dict, tokens, *, n_heads: int, kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
    norm_eps: float, top_k: int, rope_theta: float, rope_factor: float, original_max: int,
    beta_fast: float, beta_slow: float, mscale_all_dim: float, query_beta: float,
    expert_offset: int = 0, act=lambda x: x,
):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``,
    ``wq_a [D, Rq]``, ``q_norm [Rq]``, ``wq_b [Rq, H (nope + r)]``, ``wkva [D,
    R + r]``, ``kv_norm [R]``, ``wkvb [R, H (nope + dv)]``, ``wo [H dv, D]``,
    ``router [D, E]``, ``w_gate``/``w_up [E_held, D, f]``, ``w_down [E_held, f,
    D]`` and the shared expert's ``ws_gate``/``ws_up [D, f]``, ``ws_down [f, D]``.
    """
    rope = {
        "inv_freq": yarn_inv_freq(rope_dim, rope_theta, rope_factor, original_max, beta_fast, beta_slow),
        "mscale": softmax_mscale(rope_factor, mscale_all_dim),
        "beta": query_beta,
        "original_max": original_max,
    }
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for lp in weights["layers"]:
            h = rms_norm(x, lp["attn_norm"], norm_eps)
            x = x + mla(h, lp, heads=n_heads, rank=kv_rank, nope=nope_dim, v_dim=v_dim, eps=norm_eps, rope=rope, act=act)
            x = x + moe(rms_norm(x, lp["mlp_norm"], norm_eps), lp, top_k, expert_offset, act)
        x = rms_norm(x, weights["final_norm"], norm_eps)
        return act(x) @ weights["lm_head"]
