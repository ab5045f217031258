"""Plain reference forward of the ``solar_open2`` family (``families/solar_open2.py``).

Solar-Open2-250B's decoder, written from its public ``config.json`` (the
catalog's row), the catalog's summary ("gated delta-rule linear (neg.
eigenvalues, conv4); softmax NoPE GQA 64Q/8KV — 48L 3:1; 320 experts, top-8,
1 shared") and, for what the config names and does not spell out, the Kimi
Linear report (arXiv:2510.26692: KDA's projections, norms and gates) and the
gated-attention paper (arXiv:2505.06708: the elementwise sigmoid gate); what
``config.json`` does not state is listed under ``assumed`` in the
configuration file. Pre-norm residual layers, ``x += mixer(rmsnorm(x)); x +=
moe(rmsnorm(x))``; layer ``l`` (0-indexed) is GQA where ``l`` is in
``gqa_layers`` and KDA elsewhere.

KDA (gated delta-rule linear attention), per token t and head h::

    q̃, k̃, ṽ = silu(conv4(W_q x)), silu(conv4(W_k x)), silu(conv4(W_v x))
                                        depthwise causal conv over the channels
    q = l2norm(q̃) / sqrt(dk),  k = l2norm(k̃),  v = ṽ
    g_t = −exp(A_log_h) · softplus(W_fb W_fa x_t + dt_bias)     ∈ R^dk, per channel
    β_t = 2 · sigmoid(w_β,h · x_t)      ``kda_allow_neg_eigval``: β ∈ (0, 2)
    S_t = (I − β_t k_t k_tᵀ) · diag(exp g_t) · S_{t−1} + β_t k_t v_tᵀ      S ∈ R^{dk×dv}
    o_t = S_tᵀ q_t
    y_t = W_o ( rmsnorm_head(o_t; w_o_norm) ⊙ sigmoid(W_gb W_ga x_t) )

GQA (``use_rope: false``: no rotary embedding; no QK-norm, no bias)::

    q = W_q x → [H, hd];   k, v = W_k x, W_v x → [KV, hd];   H / KV query heads a K/V head
    o = causal softmax(q kᵀ / sqrt(hd)) v
    y = W_o ( o ⊙ sigmoid(W_g x) )      ``use_gqa_gate``: W_g as wide as o

and the FFN of every layer::

    s = sigmoid(x W_r);  chosen = top-k of (s + b);  w = s[chosen] / Σ s[chosen] · scale
    y = Σ_{e chosen and held here} w_e · expert_e(x) + shared_expert(x)

**Held experts.** ``experts`` holds experts ``[offset, offset + E_held)`` of
the router's ``E``: the layer routes over all E, adds the terms of the
experts it is given, leaves the others out, and adds the shared expert whole.
With every expert held that is the published layer.

float32 throughout at ``highest`` matmul precision; KDA one token after
another, plain causal softmax with every query head's keys and values
repeated, a Python loop over the held experts; no kernels, no cache, no
chunking, no batching, and nothing imported from ``agentainer_tpu``.
:func:`forward` is :func:`embed`, then :func:`layer` for each layer, then
:func:`head`: the family calls the three itself where the float32 copy of all
the layers would not fit a device (a layer at a time, the head in blocks of the
vocabulary: the same functions on the same numbers). The comparison rule and
its tolerance are not here: ``harness/compare.py``, applied by
``harness/numerics_child.py`` to every family alike.

Departures from the published model: none in the mathematics as described
above; the points the config leaves open (the gate's width, the router's
scoring function, no QK-norm, no biases, KDA's low-rank pairs) follow the
conventions named under ``assumed``.
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


def beta_of(x, lp, act, neg_eigval=True):
    """``β [T, H]``: in (0, 2) where the model allows negative eigenvalues."""
    return jax.nn.sigmoid(act(x) @ lp["w_beta"]) * (2.0 if neg_eigval else 1.0)


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


def kda(x, lp, heads, dk, eps, neg_eigval, act):
    t = x.shape[0]
    q = jax.nn.silu(short_conv(act(x) @ lp["wq"], lp["conv_q"])).reshape(t, heads, dk)
    k = jax.nn.silu(short_conv(act(x) @ lp["wk"], lp["conv_k"])).reshape(t, heads, dk)
    v = jax.nn.silu(short_conv(act(x) @ lp["wv"], lp["conv_v"])).reshape(t, heads, dk)
    q, k = l2norm(q) / jnp.sqrt(jnp.float32(dk)), l2norm(k)
    o = delta_rule(q, k, v, log_decay(x, lp, heads, dk, act), beta_of(x, lp, act, neg_eigval))
    o = rms_norm(o, lp["o_norm"], eps) * output_gate(x, lp, heads, dk, act)
    return act(o.reshape(t, heads * dk)) @ lp["wo"]


def position_embed(q, k, positions):
    """``use_rope: false``: queries and keys carry no position."""
    return q, k


def attn_gate(x, lp, act):
    """``use_gqa_gate``: a sigmoid as wide as the attention's output."""
    return jax.nn.sigmoid(act(x) @ lp["wg"])


def gqa(x, lp, heads, kv_heads, hd, act):
    t = x.shape[0]
    pos = jnp.arange(t)
    q = (act(x) @ lp["wq"]).reshape(t, heads, hd)
    k = (act(x) @ lp["wk"]).reshape(t, kv_heads, hd)
    v = (act(x) @ lp["wv"]).reshape(t, kv_heads, hd)
    q, k = position_embed(q, k, pos)
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v).reshape(t, heads * hd)
    return act(out * attn_gate(x, lp, act)) @ lp["wo"]


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


def embed(table, tokens):
    return table[tokens]


def layer(
    x, lp, *, n_heads: int, n_kv_heads: int, head_dim: int, kda_heads: int, kda_head_dim: int, norm_eps: float,
    top_k: int, routed_scale: float, renormalize: bool, neg_eigval: bool = True, expert_offset: int = 0,
    act=lambda x: x,
):
    """One layer on ``x [T, D]``: a KDA layer where ``lp`` holds ``w_beta``, a GQA layer otherwise."""
    h = rms_norm(x, lp["attn_norm"], norm_eps)
    if "w_beta" in lp:
        x = x + kda(h, lp, kda_heads, kda_head_dim, norm_eps, neg_eigval, act)
    else:
        x = x + gqa(h, lp, n_heads, n_kv_heads, head_dim, act)
    h = rms_norm(x, lp["mlp_norm"], norm_eps)
    return x + moe(h, lp, top_k, routed_scale, renormalize, expert_offset, act)


def head(x, final_norm, lm_head, norm_eps, act=lambda x: x):
    """Logits over the columns of ``lm_head`` it is given (all of them, or a block of the vocabulary)."""
    return act(rms_norm(x, final_norm, norm_eps)) @ lm_head


def forward(weights: dict, tokens, *, norm_eps: float, act=lambda x: x, **kw):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``;
    a KDA layer's ``wq``/``wk``/``wv [D, H·dk]``, ``conv_q``/``conv_k``/
    ``conv_v [W, H·dk]``, ``w_fa [D, dk]``, ``w_fb [dk, H·dk]``, ``dt_bias
    [H·dk]``, ``a_log [H]``, ``w_beta [D, H]``, ``w_ga [D, dk]``, ``w_gb
    [dk, H·dk]``, ``o_norm [dk]``, ``wo [H·dk, D]``, or a GQA layer's ``wq
    [D, H·hd]``, ``wk``/``wv [D, KV·hd]``, ``wg [D, H·hd]``, ``wo [H·hd, D]``;
    and every layer's ``router [D, E]``, ``router_bias [E]``, ``w_gate``/
    ``w_up [E_held, D, f]``, ``w_down [E_held, f, D]`` and the shared expert's
    ``ws_gate``/``ws_up``/``ws_down``. ``kw``: the sizes :func:`layer` takes.
    """
    with jax.default_matmul_precision("highest"):
        x = embed(weights["embed"], tokens)
        for lp in weights["layers"]:
            x = layer(x, lp, norm_eps=norm_eps, act=act, **kw)
        return head(x, weights["final_norm"], weights["lm_head"], norm_eps, act)
