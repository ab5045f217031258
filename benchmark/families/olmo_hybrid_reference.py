"""Plain reference forward of the ``olmo_hybrid`` family (``families/olmo_hybrid.py``).

Olmo-Hybrid-7B's decoder, written from its published ``config.json`` keys,
the Gated DeltaNet paper (arXiv:2412.06464) and the OLMo-2/3 family's
conventions, from memory (the sandbox has no network; what ``config.json``
does not state is listed under ``assumed`` in the configuration file).
Residual layers in the OLMo-2 placement — no pre-norm, the residual adds each
sublayer's NORMED output::

    x += rmsnorm(mixer(x); w_attn_norm);   x += rmsnorm(ffn(x); w_mlp_norm)

The mixer of a ``linear_attention`` layer is the gated delta rule with one
decay a head, per token t and head h (keys ``dk``, values ``dv``)::

    q̃, k̃, ṽ = silu(conv4(W_q x)), silu(conv4(W_k x)), silu(conv4(W_v x))
                                        depthwise causal conv over the channels
    q = l2norm(q̃) / sqrt(dk),  k = l2norm(k̃),  v = ṽ
    α_t = exp(−exp(A_log_h) · softplus(w_a,h · x_t + dt_bias_h))      one scalar, in (0, 1]
    β_t = 2 · sigmoid(w_β,h · x_t)            (``linear_allow_neg_eigval``: in (0, 2))
    S_t = α_t (I − β_t k_t k_tᵀ) S_{t−1} + β_t k_t v_tᵀ               S ∈ R^{dk×dv}
    o_t = S_tᵀ q_t
    y_t = W_o ( rmsnorm_head(o_t; w_o_norm) ⊙ silu(W_g x_t) )

and of a ``full_attention`` layer causal softmax attention over ``H`` heads of
``hd`` at scale ``hd^-½``, with an RMSNorm over the whole projected q and the
whole projected k before the heads are split, and no rotary embedding
(``rope_theta`` null). The FFN is a SwiGLU in every layer.

float32 throughout at ``highest`` matmul precision; the delta rule one token
after another; no kernels, no cache, no chunking, no batching, and nothing
imported from ``agentainer_tpu``. The comparison rule and its tolerance are
not here: ``harness/compare.py``, applied by ``harness/numerics_child.py`` to
every family alike.
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


def log_decay(x, lp, act):
    """``log α [T, H]``, all negative: one scalar a head."""
    return -jnp.exp(lp["a_log"])[None, :] * jax.nn.softplus(act(x) @ lp["w_a"] + lp["dt_bias"])


def beta_of(x, lp, act, neg_eigval: bool):
    return (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(act(x) @ lp["w_beta"])  # [T, H]


def output_gate(x, lp, heads, dv, act):
    return jax.nn.silu(act(x) @ lp["w_g"]).reshape(-1, heads, dv)


def delta_rule(q, k, v, log_alpha, beta):
    """The recurrence, one token after another: ``q, k [T, H, dk]``,
    ``v [T, H, dv]``, ``log_alpha, beta [T, H]`` → ``o [T, H, dv]``."""
    heads, dk = q.shape[1], q.shape[2]

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, None, None]  # α S
        kept = jnp.einsum("hk,hkv->hv", k_t, state)  # kᵀ α S
        state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - kept)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, v.shape[2]), v.dtype), (q, k, v, log_alpha, beta))
    return o


def gated_delta_net(x, lp, heads, dk, dv, eps, neg_eigval, act):
    t = x.shape[0]
    q = jax.nn.silu(short_conv(act(x) @ lp["wq"], lp["conv_q"])).reshape(t, heads, dk)
    k = jax.nn.silu(short_conv(act(x) @ lp["wk"], lp["conv_k"])).reshape(t, heads, dk)
    v = jax.nn.silu(short_conv(act(x) @ lp["wv"], lp["conv_v"])).reshape(t, heads, dv)
    q, k = l2norm(q) / jnp.sqrt(jnp.float32(dk)), l2norm(k)
    o = delta_rule(q, k, v, log_decay(x, lp, act), beta_of(x, lp, act, neg_eigval))
    o = rms_norm(o, lp["o_norm"], eps) * output_gate(x, lp, heads, dv, act)
    return act(o.reshape(t, heads * dv)) @ lp["wo"]


def qk_norm(q, k, lp, eps):
    """Over the whole projection (all heads' values at once), before the split."""
    return rms_norm(q, lp["q_norm"], eps), rms_norm(k, lp["k_norm"], eps)


def position_embed(q, k, positions):
    """``rope_theta`` null: the full layers rotate nothing; position reaches
    them through the delta-rule layers and their convolutions."""
    return q, k


def full_attention(x, lp, heads, kv_heads, eps, act):
    t = x.shape[0]
    pos = jnp.arange(t)
    q, k = qk_norm(act(x) @ lp["wq"], act(x) @ lp["wk"], lp, eps)
    q, k = q.reshape(t, heads, -1), k.reshape(t, kv_heads, -1)
    v = (act(x) @ lp["wv"]).reshape(t, kv_heads, -1)
    q, k = position_embed(q, k, pos)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return act(out.reshape(t, -1)) @ lp["wo"]


def swiglu(x, w_gate, w_up, w_down, act):
    x = act(x)
    return act(jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def sublayer(x, f, norm_w, eps):
    """The family's placement: the residual adds the normed OUTPUT."""
    return x + rms_norm(f(x), norm_w, eps)


def forward(
    weights: dict, tokens, *, n_heads: int, n_kv_heads: int, lin_heads: int, lin_key_dim: int,
    lin_value_dim: int, norm_eps: float, neg_eigval: bool = True, act=lambda x: x,
):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``, the
    FFN's ``w_gate``/``w_up [D, F]``, ``w_down [F, D]``, and a linear layer's
    ``wq``/``wk [D, H·dk]``, ``wv [D, H·dv]``, ``conv_q``/``conv_k [W, H·dk]``,
    ``conv_v [W, H·dv]``, ``w_a [D, H]``, ``dt_bias``, ``a_log [H]``, ``w_beta
    [D, H]``, ``w_g [D, H·dv]``, ``o_norm [dv]``, ``wo [H·dv, D]``, or a full
    layer's ``wq [D, H·hd]``, ``wk``/``wv [D, KV·hd]``, ``q_norm [H·hd]``,
    ``k_norm [KV·hd]``, ``wo [H·hd, D]``.
    """
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for lp in weights["layers"]:
            if "w_a" in lp:
                mixer = lambda h, lp=lp: gated_delta_net(  # noqa: E731
                    h, lp, lin_heads, lin_key_dim, lin_value_dim, norm_eps, neg_eigval, act)
            else:
                mixer = lambda h, lp=lp: full_attention(h, lp, n_heads, n_kv_heads, norm_eps, act)  # noqa: E731
            x = sublayer(x, mixer, lp["attn_norm"], norm_eps)
            x = sublayer(x, lambda h, lp=lp: swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], act), lp["mlp_norm"], norm_eps)
        x = rms_norm(x, weights["final_norm"], norm_eps)
        return act(x) @ weights["lm_head"]
