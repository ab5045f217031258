"""Lightning attention (Lightning Attention-2, arXiv 2401.04658): linear
attention with a CONSTANT decay a head and nothing else.

Per head, with keys and values of ``dk`` and ``dv`` and a state ``S [dk, dv]``
kept in float32 (``λ = exp(g) ∈ (0, 1)`` the head's decay)::

    S_t = λ S_{t−1} + k_t v_tᵀ
    o_t = S_tᵀ q_t

No conv, no β and no erase term: not a delta rule (``ops/kda.py``), so there
is no triangular system to solve and a chunk is two masked matmuls. The log
decay arrives a token (``g [B, T, H]``) so that masking stays part of the
mathematics as it is there: a token with ``g = 0`` and ``k = 0`` leaves the
state exactly as it was (:func:`mask_inputs`).

Three forms of the same mathematics, as ``ops/kda.py`` has them:

- :func:`lightning_recurrent` — one token after another: the test oracle;
- :func:`lightning_chunked` — prefill: inside a chunk of :data:`CHUNK` tokens
  (``G_t = Σ_{r≤t} g_r``) ``O = (exp G ⊙ Q) S_0 + ((Q Kᵀ) ⊙ D) V`` with ``D_ts
  = exp(G_t − G_s)`` for ``s ≤ t`` (every exponent ≤ 0), and ``S_C = exp(G_C)
  S_0 + (K ⊙ exp(G_C − G))ᵀ V``; what does not read the state is built for all
  chunks at once, before the scan;
- :func:`lightning_step` — decode: one read-update-write of every lane's state.

The matmuls are float32 at ``highest`` precision: a few hundredths of the
layer's projections.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .kda import _mm

CHUNK = 64


def mask_inputs(g: jnp.ndarray, k: jnp.ndarray, valid: jnp.ndarray):
    """``g [..., H]``, ``k [..., H, dk]`` with the invalid tokens' rows
    (``valid [...]`` false) set to leave the state alone."""
    return jnp.where(valid[..., None], g, 0.0), jnp.where(valid[..., None, None], k, 0.0)


def lightning_step(q, k, v, g, state):
    """One token for every lane: ``q, k [B, H, dk]``, ``v [B, H, dv]``,
    ``g [B, H]``, ``state [B, H, dk, dv]`` float32 → ``(o [B, H, dv] float32,
    new state)``."""
    f32 = jnp.float32
    q, k, v, g = (x.astype(f32) for x in (q, k, v, g))
    new = state * jnp.exp(g)[..., None, None] + k[..., :, None] * v[..., None, :]
    return _mm("bhk,bhkv->bhv", q, new), new


def lightning_recurrent(q, k, v, g, state):
    """Token by token: ``q, k [B, T, H, dk]``, ``v [B, T, H, dv]``, ``g [B, T,
    H]``, ``state [B, H, dk, dv]`` → ``(o [B, T, H, dv] float32, last state)``."""

    def step(s, x):
        o, s = lightning_step(*x, s)
        return s, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g))
    state, o = lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def lightning_chunked(q, k, v, g, state, chunk: int = CHUNK):
    """The chunked form: shapes as :func:`lightning_recurrent`. ``T`` is
    padded up to whole chunks with tokens that leave the state alone."""
    b, t, h, _ = q.shape
    f32 = jnp.float32
    n = -(-t // chunk)
    pad = n * chunk - t

    def lay(x):  # [B, T, H, ·] -> [n, B, H, C, ·]
        x = jnp.pad(x.astype(f32), [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g = lay(q), lay(k), lay(v), lay(g[..., None])
    big_g = jnp.cumsum(g[..., 0], axis=-1)[..., None]  # [n, B, H, C, 1], ≤ 0 and falling
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(G_t − G_s) for s ≤ t: the exponent is ≤ 0 there, clamped elsewhere
    decay = jnp.where(tri, jnp.exp(jnp.minimum(big_g - jnp.swapaxes(big_g, -1, -2), 0.0)), 0.0)
    within = _mm("...ts,...sv->...tv", _mm("...tk,...sk->...ts", q, k) * decay, v)
    xs = (q * jnp.exp(big_g), within, k * jnp.exp(big_g[..., -1:, :] - big_g), v, jnp.exp(big_g[..., -1:, :]))

    def step(s, x):  # what reads the state: one matmul against it, one into it
        q_gamma, within, k_to_end, v, gamma_end = x
        o = _mm("bhtk,bhkv->bhtv", q_gamma, s) + within
        return s * gamma_end + _mm("bhsk,bhsv->bhkv", k_to_end, v), o

    state, o = lax.scan(step, state.astype(f32), xs)  # o [n, B, H, C, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * chunk, h, -1)
    return o[:, :t], state
