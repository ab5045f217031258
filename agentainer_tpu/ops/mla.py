"""Latent attention (MLA) without rotary embedding, over a latent arena.

A token's cache row is ``[c̄ (R), k_s (r)]``: the normalised latent every
head's keys and values are expanded from, and ``r`` more key dimensions all
heads share. Expanded, head h has ``k = [W_uk,h c̄, k_s]`` and
``v = W_uv,h c̄``. :func:`attend` is the **absorbed** form: ``W_uk`` is
folded into the query and ``W_uv`` applied to the output, so every head
scores against the one shared ``R + r`` wide row and combines the one
``R`` wide value — the arena is read once for all heads, where it lies.
:func:`expanded` is the same mathematics written the long way (the test
oracle for the absorption). On a TPU the plan (``models/hybrid.plan_hybrid``)
hands both call shapes to the kernels of ``ops/pallas_mla.py``, which read the
stacked arena in place and only up to the last position seen; :func:`attend`
over a lane's sliced row is the path everywhere else, and their oracle.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def absorb_query(q, w_kvb, nope: int):
    """``q [B, T, H, nope + r]`` and ``w_kvb [R, H, nope + dv]`` → the query
    against the latent row, ``[B, T, H, R + r]``."""
    q_abs = jnp.einsum(
        "bthn,rhn->bthr", q[..., :nope], w_kvb[..., :nope], preferred_element_type=jnp.float32
    )
    return jnp.concatenate([q_abs, q[..., nope:].astype(q_abs.dtype)], axis=-1)


def attend(q_full, rows, positions, scale: float, rank: int):
    """``q_full [B, T, H, R + r]`` against ``rows [B, S, R + r]``: query t of
    sequence b sees arena slot j iff ``j <= positions[b, t]``. Returns the
    combined latent ``[B, T, H, R]`` in float32."""
    s = rows.shape[1]
    scores = jnp.einsum(
        "bthc,bsc->bhts", q_full.astype(rows.dtype), rows, preferred_element_type=jnp.float32
    ) * scale
    seen = jnp.arange(s)[None, None, :] <= positions[:, :, None]  # [B, T, S]
    scores = jnp.where(seen[:, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum(
        "bhts,bsr->bthr", p.astype(rows.dtype), rows[..., :rank], preferred_element_type=jnp.float32
    )


def expanded(q, rows, positions, w_kvb, scale: float, rank: int, nope: int):
    """The long way: every head's keys and values expanded from the latent
    rows, plain softmax attention. ``[B, T, H, dv]`` float32."""
    f32 = jnp.float32
    c, k_s = rows[..., :rank].astype(f32), rows[..., rank:].astype(f32)
    kv = jnp.einsum("bsr,rhx->bshx", c, w_kvb.astype(f32))
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_s[:, :, None, :], kv.shape[:3] + k_s.shape[-1:])], -1
    )
    scores = jnp.einsum("bthx,bshx->bhts", q.astype(f32), k) * scale
    seen = jnp.arange(rows.shape[1])[None, None, :] <= positions[:, :, None]
    p = jnp.where(seen[:, None], scores, NEG_INF)
    p = jnp.exp(p - jnp.max(p, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhts,bshv->bthv", p, kv[..., nope:])
