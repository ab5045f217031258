"""Plain reference forward of the ``minicpm_sala`` family (``families/minicpm_sala.py``).

MiniCPM-SALA's decoder, written from its published ``config.json`` keys and
the two published mechanisms its ``mixer_types`` name: InfLLM v2 (``minicpm4``:
MiniCPM4, arXiv 2506.07900, and the ``sparse_config`` of openbmb/MiniCPM4-8B)
and Lightning Attention-2 (``lightning-attn``: arXiv 2401.04658; as served in
MiniMax-01, arXiv 2501.08313), from memory (the sandbox has no network). What
``config.json`` does not state is listed under ``assumed`` in the
configuration file, eight points; each is named at its line here as
``assumed (n)``.

With ``h = RMSNorm(x)`` and ``c = scale_depth / √layers``::

    x₀ = scale_emb · E[token]
    x += c · mixer(RMSNorm(x));   x += c · SwiGLU(RMSNorm(x))
    logits = (RMSNorm(x) / (hidden / dim_model_base)) W_head

``minicpm4``: ``q = RMSNorm_head(h W_q)`` (H heads), ``k = RMSNorm_head(h
W_k)``, ``v = h W_v`` (KV heads), no rotation; group g is the H / KV query
heads of K/V head g. The query at position t with ``t + 1 ≤ dense_len`` runs
plain causal softmax attention. Otherwise: pooled keys ``c_{g,j} =
mean(k_{g, s·j} … k_{g, s·j + K − 1})``, visible iff ``s·j + K − 1 ≤ t``;
``p_{h,j} = softmax_j(q_h · c_{g,j} / √hd)`` over the visible j; ``s_{g,j} =
Σ_{h∈g} p_{h,j}``; block b (rows ``B·b … B·b + B − 1``) scores the largest
``s_{g,j}`` among the kernels that overlap it; the first ``init_blocks``
blocks and the blocks of the last ``window`` rows score +∞; the ``topk``
blocks of largest score are the query's, one set a row and group, and its
heads attend (causal softmax, scale ``hd^-½``) to the rows ≤ t of those
blocks and nothing else. ``o = concat(o_h) ⊙ sigmoid(h W_g)``; the mixer is
``o W_o``.

``lightning-attn``: ``q = rope(RMSNorm_head(h W_q))``, ``k`` likewise, ``v = h
W_v``; per head a constant decay ``λ_h``; ``S_t = λ_h S_{t−1} + k_tᵀ v_t``,
``o_t = (q_t / √d) S_t``; ``o = RMSNorm(concat o) ⊙ sigmoid(h W_g)``; the
mixer is ``o W_o``.

float32 throughout at ``highest`` matmul precision; the recurrence one token
after another; the selection as the definition reads (scores → max-pool →
force → ``top_k`` → a row-by-block mask → masked softmax), a block of query
rows at a time so that a few thousand rows fit a device; no kernels, no cache
and nothing imported from ``agentainer_tpu``. The comparison rule and its
tolerance are not here: ``harness/compare.py``, applied by
``harness/numerics_child.py`` to every family alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_ROWS = 256  # query rows of a sparse layer computed at a time (memory, not mathematics)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, H, hd]; assumed (8): rotate-half pairing, dims (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def pooled_keys(k, kernel: int, stride: int):
    """``k [T, KV, hd]`` → ``c [P, KV, hd]``, ``c_j`` the mean of rows ``stride·j
    .. stride·j + kernel − 1``, for every kernel that lies inside the T rows
    (assumed (1): kernel 32, stride 16)."""
    n = max((k.shape[0] - kernel) // stride + 1, 0)
    rows = stride * jnp.arange(n)[:, None] + jnp.arange(kernel)[None, :]  # [P, kernel]
    return jnp.mean(k[rows], axis=1)


def block_scores(q, pooled, t, sparse: dict, n_blocks: int):
    """``q [R, H, hd]`` at positions ``t [R]`` → ``S [R, KV, n_blocks]``."""
    kernel, stride, block = sparse["kernel"], sparse["stride"], sparse["block"]
    rows, heads, hd = q.shape
    n_pooled, kv = pooled.shape[0], pooled.shape[1]
    qg = q.reshape(rows, kv, heads // kv, hd)
    visible = (stride * jnp.arange(n_pooled) + kernel - 1)[None, :] <= t[:, None]  # [R, P]
    # assumed (2): an EXACT softmax over the visible pooled keys, a head
    logits = jnp.einsum("rkgd,pkd->rkgp", qg, pooled) / jnp.sqrt(jnp.float32(hd))
    logits = jnp.where(visible[:, None, None, :], logits, -jnp.inf)
    p = jnp.nan_to_num(jax.nn.softmax(logits, axis=-1))  # a row that sees no kernel: zeros
    s = jnp.sum(p, axis=2)  # [R, KV, P]: summed over the group's heads
    s = jnp.where(visible[:, None, :], s, -jnp.inf)
    # the kernels that overlap block b: stride·j ≤ block·b + block − 1 and
    # stride·j + kernel − 1 ≥ block·b (a max-pool of 5, stride 4, padding 1 at 64 / 16 / 32)
    lo, per = (kernel - 1) // stride, block // stride
    width = (block - 1) // stride + lo + 1
    need = per * (n_blocks - 1) + width
    padded = jnp.pad(s, [(0, 0), (0, 0), (lo, max(need - lo - n_pooled, 0))], constant_values=-jnp.inf)
    return jnp.max(jnp.stack([padded[..., i::per][..., :n_blocks] for i in range(width)]), axis=0)


def select(scores, t, sparse: dict):
    """The row-by-block mask ``[R, KV, n_blocks]`` of the ``topk`` blocks of
    largest score, the forced ones among them (assumed (1): init_blocks 1,
    window 2048, topk 64)."""
    block, n_blocks = sparse["block"], scores.shape[-1]
    b = jnp.arange(n_blocks)[None, None, :]
    current = (t // block)[:, None, None]
    forced = (b < sparse["init_blocks"]) | ((b > current - sparse["window"] // block) & (b <= current))
    scores = jnp.where(forced, jnp.inf, scores)
    scores = jnp.where(b > current, -jnp.inf, scores)  # a block that starts after the query has no row to read
    top, chosen = jax.lax.top_k(scores, min(sparse["topk"], n_blocks))
    hit = (chosen[..., None] == jnp.arange(n_blocks)) & (top > -jnp.inf)[..., None]
    return jnp.any(hit, axis=-2)


def sparse_attention(x, lp, heads, kv_heads, hd, eps, sparse: dict, act, selection=None):
    t = x.shape[0]
    pos = jnp.arange(t)
    # assumed (4): an RMSNorm a head on q and on k (one weight [hd] each); no rotation (attn_use_rope false)
    q = rms_norm((act(x) @ lp["wq"]).reshape(t, heads, hd), lp["q_norm"], eps)
    k = rms_norm((act(x) @ lp["wk"]).reshape(t, kv_heads, hd), lp["k_norm"], eps)
    v = (act(x) @ lp["wv"]).reshape(t, kv_heads, hd)
    block = sparse["block"]
    n_blocks = -(-t // block)
    pooled = pooled_keys(k, sparse["kernel"], sparse["stride"])
    group = heads // kv_heads

    def rows_at(start):
        rows = start + jnp.arange(QUERY_ROWS)
        at = jnp.minimum(rows, t - 1)
        q_r = q[at]
        if pooled.shape[0]:
            chosen = select(block_scores(q_r, pooled, at, sparse, n_blocks), at, sparse)
        else:
            chosen = jnp.ones((QUERY_ROWS, kv_heads, n_blocks), bool)
        # assumed (1): dense_len 8192: a query with at most that many rows of context reads them all
        blocks = chosen | (at + 1 <= sparse["dense_len"])[:, None, None]
        see = jnp.repeat(blocks, block, axis=-1)[..., :t] & (pos[None, :] <= at[:, None])[:, None, :]  # [R, KV, T]
        qg = q_r.reshape(QUERY_ROWS, kv_heads, group, hd)
        scores = jnp.einsum("rkgd,skd->rkgs", qg, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(see[:, :, None, :], scores, -jnp.inf)
        out = jnp.einsum("rkgs,skd->rkgd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(QUERY_ROWS, heads * hd), chosen

    starts = jnp.arange(0, t, QUERY_ROWS)
    out, chosen = jax.lax.map(rows_at, starts)
    out = out.reshape(-1, heads * hd)[:t]
    if selection is not None:
        selection.append(chosen.reshape(-1, kv_heads, n_blocks)[:t])
    # assumed (3): an elementwise sigmoid gate from the normed input, as wide as the output
    out = out * jax.nn.sigmoid(act(x) @ lp["wg"])
    return act(out) @ lp["wo"]


def lightning_attention(x, lp, heads, dk, eps, theta, act):
    t = x.shape[0]
    pos = jnp.arange(t)
    # assumed (4): an RMSNorm a head on q and k, BEFORE the rotation; assumed (6): no activation on q, k, v
    q = rope(rms_norm((act(x) @ lp["wq"]).reshape(t, heads, dk), lp["q_norm"], eps), pos, theta)
    k = rope(rms_norm((act(x) @ lp["wk"]).reshape(t, heads, dk), lp["k_norm"], eps), pos, theta)
    v = (act(x) @ lp["wv"]).reshape(t, heads, dk)
    # assumed (5): Lightning Attention-2's slopes, λ_h = exp(−2^(−8 (h + 1) / H)), the same in every
    # layer; the weights carry them as a vector a layer (``slope``), so a checkpoint's own would drop in
    decay = jnp.exp(-lp["slope"])  # [H]
    q = q / jnp.sqrt(jnp.float32(dk))  # lightning_scale 1/sqrt(d)

    def step(state, xs):
        q_t, k_t, v_t = xs
        state = state * decay[:, None, None] + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dk), x.dtype), (q, k, v))
    # assumed (7): ONE RMSNorm over the concatenated heads' values; assumed (3): the sigmoid gate as wide
    o = rms_norm(o.reshape(t, heads * dk), lp["o_norm"], eps) * jax.nn.sigmoid(act(x) @ lp["wg"])
    return act(o) @ lp["wo"]


def swiglu(x, w_gate, w_up, w_down, act):
    x = act(x)
    return act(jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def forward(
    weights: dict, tokens, *, n_heads: int, n_kv_heads: int, head_dim: int, lin_heads: int, lin_dim: int,
    norm_eps: float, rope_theta: float, sparse: dict, embed_scale: float, residual_scale: float,
    logit_divisor: float, act=lambda x: x, selection: list | None = None,
):
    """Logits ``[T, V]`` of one sequence ``tokens [T]``.

    ``weights``: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
    ``layers``, a list of dicts with ``attn_norm``, ``mlp_norm`` ``[D]``, the
    FFN's ``w_gate``/``w_up [D, F]``, ``w_down [F, D]``, and a sparse layer's
    ``wq``/``wg [D, H·hd]``, ``wk``/``wv [D, KV·hd]``, ``q_norm``/``k_norm
    [hd]``, ``wo [H·hd, D]``, or a lightning layer's ``wq``/``wk``/``wv``/``wg
    [D, H·dk]``, ``q_norm``/``k_norm [dk]``, ``o_norm [H·dk]``, ``slope [H]``,
    ``wo [H·dk, D]`` (the layer with a ``slope`` is the lightning one).
    ``sparse``: ``kernel``, ``stride``, ``block``, ``init_blocks``,
    ``window``, ``topk``, ``dense_len``. ``selection``: a list that receives
    each sparse layer's chosen blocks, ``[T, KV, ⌈T / block⌉]`` bool, in order.
    """
    with jax.default_matmul_precision("highest"):
        x = embed_scale * weights["embed"][tokens]
        for lp in weights["layers"]:
            h = rms_norm(x, lp["attn_norm"], norm_eps)
            if "slope" in lp:
                y = lightning_attention(h, lp, lin_heads, lin_dim, norm_eps, rope_theta, act)
            else:
                y = sparse_attention(h, lp, n_heads, n_kv_heads, head_dim, norm_eps, sparse, act, selection)
            x = x + residual_scale * y
            h = rms_norm(x, lp["mlp_norm"], norm_eps)
            x = x + residual_scale * swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], act)
        x = rms_norm(x, weights["final_norm"], norm_eps) / logit_divisor
        return act(x) @ weights["lm_head"]
