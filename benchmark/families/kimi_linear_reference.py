"""Plain reference forward of the ``kimi_linear`` family (``families/kimi_linear.py``).

Kimi-Linear-48B-A3B's decoder, written from the model card and the Kimi
Linear report (arXiv:2510.26692) and from memory of the public modelling code
(the sandbox has no network; what ``config.json`` does not state is listed
under ``assumed`` in the configuration file). Pre-norm residual layers,
``x += mixer(rmsnorm(x)); x += ffn(rmsnorm(x))``; the mixer of a layer is

KDA (gated delta-rule linear attention), per token t and head h::

    q̃, k̃, ṽ = silu(conv4(W_q x)), silu(conv4(W_k x)), silu(conv4(W_v x))
                                        depthwise causal conv over the channels
    q = l2norm(q̃) / sqrt(dk),  k = l2norm(k̃),  v = ṽ
    g_t = −exp(A_log_h) · softplus(W_fb W_fa x_t + dt_bias)     ∈ R^dk, per channel
    β_t = sigmoid(w_β,h · x_t)
    S_t = (I − β_t k_t k_tᵀ) · diag(exp g_t) · S_{t−1} + β_t k_t v_tᵀ      S ∈ R^{dk×dv}
    o_t = S_tᵀ q_t
    y_t = W_o ( rmsnorm_head(o_t; w_o_norm) ⊙ sigmoid(W_gb W_ga x_t) )

or MLA (latent attention, ``mla_use_nope``: no rotary embedding anywhere)::

    q = W_q x → [H, nope + rope];   [c, k_s] = W_kva x;   c̄ = rmsnorm(c)
    [k_nope, v] = W_kvb c̄ → [H, nope + dv];   k = [k_nope, k_s]  (k_s shared by all heads)
    causal softmax attention at scale (nope + rope)^-½, then W_o

and the FFN a dense SwiGLU (the leading dense layers) or::

    s = sigmoid(x W_r);  chosen = top-k of (s + b);  w = s[chosen] / Σ s[chosen] · scale
    y = Σ_{e chosen and held here} w_e · expert_e(x) + shared_expert(x)

**Held experts.** ``experts`` holds experts ``[offset, offset + E_held)`` of
the router's ``E``: the layer routes over all E, adds the terms of the
experts it is given, leaves the others out, and adds the shared expert whole.
With every expert held that is the published layer.

float32 throughout at ``highest`` matmul precision; KDA one token after
another, MLA with every head's keys and values expanded, a Python loop over
the experts; no kernels, no cache, no chunking, and nothing imported from
``agentainer_tpu``. The comparison rule and its tolerance are not here:
``harness/compare.py``, applied by ``harness/numerics_child.py`` to every
family alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def short_conv(x, w):
    """Depthwise causal convolution: ``x [T, C]``, ``w [W, C]``;
    ``y_t = Σ_j w[j] · x_{t − (W − 1) + j}`` with zeros before the sequence."""
    width = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(padded[j : j + x.shape[0]] * w[j] for j in range(width))


def log_decay(x, lp, heads, dk, act):
    """``g [T, H, dk]``, all negative."""
    z = act(act(x) @ lp["w_fa"]) @ lp["w_fb"] + lp["dt_bias"]
    return -jnp.exp(lp["a_log"])[None, :, None] * jax.nn.softplus(z).reshape(-1, heads, dk)


def beta_of(x, lp, act):
    return jax.nn.sigmoid(act(x) @ lp["w_beta"])  # [T, H]


def output_gate(x, lp, heads, dk, act):
    return jax.nn.sigmoid(act(act(x) @ lp["w_ga"]) @ lp["w_gb"]).reshape(-1, heads, dk)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token after another: ``q, k, v, g [T, H, dk]``,
    ``beta [T, H]`` → ``o [T, H, dv]``."""
    heads, dk = q.shape[1], q.shape[2]

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, :, None]  # diag(exp g) S
        kept = jnp.einsum("hk,hkv->hv", k_t, state)  # kᵀ S
        state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - kept)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, v.shape[2]), v.dtype), (q, k, v, g, beta))
    return o


def kda(x, lp, heads, dk, eps, act):
    t = x.shape[0]
    q = jax.nn.silu(short_conv(act(x) @ lp["wq"], lp["conv_q"])).reshape(t, heads, dk)
    k = jax.nn.silu(short_conv(act(x) @ lp["wk"], lp["conv_k"])).reshape(t, heads, dk)
    v = jax.nn.silu(short_conv(act(x) @ lp["wv"], lp["conv_v"])).reshape(t, heads, dk)
    q, k = l2norm(q) / jnp.sqrt(jnp.float32(dk)), l2norm(k)
    o = delta_rule(q, k, v, log_decay(x, lp, heads, dk, act), beta_of(x, lp, act))
    o = rms_norm(o, lp["o_norm"], eps) * output_gate(x, lp, heads, dk, act)
    return act(o.reshape(t, heads * dk)) @ lp["wo"]


def position_embed(q_rope, k_shared, positions):
    """``mla_use_nope``: the 64 "rope" dimensions carry no rotary embedding;
    they are 64 more key dimensions shared by all heads."""
    return q_rope, k_shared


def mla(x, lp, heads, rank, nope, v_dim, eps, act):
    t = x.shape[0]
    pos = jnp.arange(t)
    q = (act(x) @ lp["wq"]).reshape(t, heads, -1)
    ckv = act(x) @ lp["wkva"]
    c, k_s = rms_norm(ckv[:, :rank], lp["kv_norm"], eps), ckv[:, rank:]
    kv = (act(c) @ lp["wkvb"]).reshape(t, heads, nope + v_dim)
    q_rope, k_s = position_embed(q[:, :, nope:], k_s, pos)
    q = jnp.concatenate([q[:, :, :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[:, :, :nope], jnp.broadcast_to(k_s[:, None, :], (t, heads, k_s.shape[-1]))], axis=-1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), kv[:, :, nope:])
    return act(out.reshape(t, heads * v_dim)) @ lp["wo"]


def swiglu(x, w_gate, w_up, w_down, act):
    x = act(x)
    return act(jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def gates(logits, bias, top_k, scale, renormalize):
    """Router logits ``[T, E]`` → (weights, experts) ``[T, k]``: sigmoid
    scores; the selection bias chooses and never weighs."""
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * scale, chosen


def shared_expert(x, lp, act):
    return swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], act)


def moe(x, lp, top_k, scale, renormalize, offset, act):
    w, chosen = gates(act(x) @ lp["router"], lp["router_bias"], top_k, scale, renormalize)
    out = jnp.zeros_like(x)
    for i in range(lp["w_gate"].shape[0]):  # the experts held here: offset + i
        w_i = jnp.sum(jnp.where(chosen == offset + i, w, 0.0), axis=-1)  # [T]
        out = out + w_i[:, None] * swiglu(x, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i], act)
    if "ws_gate" in lp:
        out = out + shared_expert(x, lp, act)
    return out


def forward(
    weights: dict, tokens, *, n_heads: int, kda_heads: int, kda_head_dim: int, kv_rank: int,
    nope_dim: int, v_dim: int, norm_eps: float, top_k: int, routed_scale: float,
    renormalize: bool, expert_offset: int = 0, act=lambda x: x,
):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``;
    a KDA layer's ``wq``/``wk``/``wv [D, H·dk]``, ``conv_q``/``conv_k``/
    ``conv_v [W, H·dk]``, ``w_fa [D, dk]``, ``w_fb [dk, H·dk]``, ``dt_bias
    [H·dk]``, ``a_log [H]``, ``w_beta [D, H]``, ``w_ga [D, dk]``, ``w_gb
    [dk, H·dk]``, ``o_norm [dk]``, ``wo [H·dk, D]``, or an MLA layer's ``wq
    [D, H·(nope + rope)]``, ``wkva [D, R + rope]``, ``kv_norm [R]``, ``wkvb
    [R, H·(nope + dv)]``, ``wo [H·dv, D]``; and a dense layer's ``w_gate``/
    ``w_up [D, F]``, ``w_down [F, D]`` or an expert layer's ``router [D, E]``,
    ``router_bias [E]``, ``w_gate``/``w_up [E_held, D, f]``, ``w_down
    [E_held, f, D]`` and the shared expert's ``ws_gate``/``ws_up``/``ws_down``.
    """
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for lp in weights["layers"]:
            h = rms_norm(x, lp["attn_norm"], norm_eps)
            if "wkva" in lp:
                x = x + mla(h, lp, n_heads, kv_rank, nope_dim, v_dim, norm_eps, act)
            else:
                x = x + kda(h, lp, kda_heads, kda_head_dim, norm_eps, act)
            h = rms_norm(x, lp["mlp_norm"], norm_eps)
            if "router" in lp:
                x = x + moe(h, lp, top_k, routed_scale, renormalize, expert_offset, act)
            else:
                x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], act)
        x = rms_norm(x, weights["final_norm"], norm_eps)
        return act(x) @ weights["lm_head"]
