"""The gated delta rule: Kimi Delta Attention (KDA, a decay per key channel)
and Gated DeltaNet (one decay a head).

Per head, with keys of ``dk``, values of ``dv`` and a state ``S [dk, dv]``
kept in float32 (``a_t = exp(g_t) ∈ (0, 1]`` the decay, ``β_t`` in ``[0, 1]``
or, where the model allows negative eigenvalues, ``[0, 2]``)::

    S_t = (I − β_t k_t k_tᵀ) · diag(a_t) · S_{t−1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

The log decay ``g`` is ``[..., H, dk]`` (KDA) or ``[..., H, 1]`` (one a head:
``diag(a_t)`` is then ``a_t I`` and commutes with the rest); every function
here takes either, and ``dv`` need not be ``dk``.

Three forms of the same mathematics:

- :func:`kda_recurrent` — one token after another (a ``lax.scan`` over T):
  the test oracle, and nothing a step program traces;
- :func:`kda_chunked` — prefill: chunks of :data:`CHUNK` tokens, inside a
  chunk one lower-triangular solve, between chunks one state update, so a
  layer takes ``T / CHUNK`` sequential steps of matmuls and not T;
- :func:`kda_step` — decode: one fused read-update-write of every lane's
  state. On a TPU the Pallas kernel (``ops/pallas_kda.kda_decode``) updates
  the layer of the stacked state where it lies; :func:`kda_step` is its
  ``jnp`` twin (the CPU path and the kernel's oracle).

**Masking is part of the mathematics.** A token with ``β = 0`` and ``g = 0``
leaves the state exactly as it was (``S·1 + 0``): callers mask a parked or
idle lane, and a bucket's padding rows, that way (``mask_inputs``).

Inside a chunk (positions 1..C, ``G_t = Σ_{r≤t} g_r``, ``Γ_t = exp G_t``)
write ``S_t = diag(a_t) S_{t−1} + k_t u_tᵀ`` with ``u_t = β_t (v_t −
S_{t−1}ᵀ (a_t ⊙ k_t))``. Unrolling from the chunk's first state ``S_0``::

    A_ts = Σ_c k_t[c] k_s[c] exp(G_t[c] − G_s[c])     (s < t)
    B_ts = Σ_c q_t[c] k_s[c] exp(G_t[c] − G_s[c])     (s ≤ t)
    (I + diag(β) A) U = diag(β) (V − (Γ ⊙ K) S_0)
    O = (Γ ⊙ Q) S_0 + B U
    S_C = diag(Γ_C) S_0 + Σ_s (k_s ⊙ exp(G_C − G_s)) u_sᵀ

Every exponent above is ≤ 0, and the code keeps it so: the textbook
factorisation ``A = (Γ ⊙ K)(K / Γ)ᵀ`` overflows ``1 / Γ`` once a chunk's
summed log-decay passes −88 (a published checkpoint's decays can: ``exp(A_log)``
up to 16 times a softplus), so ``A`` and ``B`` are built in :data:`SUB`-token
blocks — a block below the diagonal factorises around the decay at the start
of its ROW block (both factors then ≤ 1), a block on the diagonal takes
``exp(G_t − G_s)`` pair by pair. With one decay a head there is nothing to
sum over channels: ``A = (K Kᵀ) ⊙ exp(G_t − G_s)`` is one matmul and a mask
with every exponent ≤ 0 (:func:`_chunk_scalar`). The matmuls inside are
float32 at ``highest`` precision: they are a few hundredths of the layer's
projections.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
SUB = 16  # tokens of a block of the in-chunk score matrices (see the module docstring)
_HI = lax.Precision.HIGHEST


def mask_inputs(g: jnp.ndarray, beta: jnp.ndarray, valid: jnp.ndarray):
    """``g [..., H, dk]`` (or ``[..., H, 1]``), ``beta [..., H]`` with the invalid tokens' rows
    (``valid [...]`` false) set to leave the state alone."""
    return jnp.where(valid[..., None, None], g, 0.0), jnp.where(valid[..., None], beta, 0.0)


def kda_step(q, k, v, g, beta, state):
    """One token for every lane: ``q, k [B, H, dk]``, ``v [B, H, dv]``,
    ``g [B, H, dk]`` or ``[B, H, 1]``, ``beta [B, H]``, ``state [B, H, dk,
    dv]`` float32 → ``(o [B, H, dv] float32, new state)``."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    a = jnp.exp(g)
    decayed = state * a[..., None]
    r = jnp.einsum("bhk,bhkv->bhv", k, decayed, precision=_HI)
    u = beta[..., None] * (v - r)
    new = decayed + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, new, precision=_HI)
    return o, new


def kda_recurrent(q, k, v, g, beta, state):
    """Token by token: ``q, k, v, g [B, T, H, dk]``, ``beta [B, T, H]``,
    ``state [B, H, dk, dv]`` → ``(o [B, T, H, dv] float32, last state)``."""

    def step(s, x):
        o, s = kda_step(*x, s)
        return s, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state, o = lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _chunk(q, k, v, g, beta, s0):
    """One chunk: ``q, k, v, g [B, H, C, dk]``, ``beta [B, H, C]``,
    ``s0 [B, H, dk, dv]``, all float32."""
    b, h, c, dk = q.shape
    sub = min(SUB, c)
    n = c // sub
    mm = lambda eq, *xs: jnp.einsum(eq, *xs, precision=_HI)  # noqa: E731
    big_g = jnp.cumsum(g, axis=2)  # G_t, ≤ 0 and falling
    blocks = lambda x: x.reshape(b, h, n, sub, *x.shape[3:])  # noqa: E731
    gb, kb, qb = blocks(big_g), blocks(k), blocks(q)
    # the decay before each block's first token
    start = jnp.concatenate([jnp.zeros_like(gb[:, :, :1, 0]), gb[:, :, :-1, -1]], axis=2)  # [B,H,n,dk]
    row = jnp.exp(gb - start[:, :, :, None])  # a row's decay since its block began
    # a column token's decay up to row block i's start (≤ 1 for the columns
    # before that block, the only ones kept; clamped elsewhere)
    col = k[:, :, None] * jnp.exp(jnp.minimum(start[:, :, :, None] - big_g[:, :, None], 0.0))  # [B,H,n,C,dk]
    pair = jnp.exp(jnp.minimum(gb[:, :, :, :, None] - gb[:, :, :, None], 0.0))  # [B,H,n,sub,sub,dk]
    block_of = jnp.arange(c) // sub
    below = block_of[:, None] > block_of[None, :]  # column's block before the row's
    on_diag = jnp.eye(n, dtype=q.dtype)[:, None, :, None]  # [n,1,n,1]

    def scores(rows):  # rows [B,H,n,sub,dk] -> Σ_c rows_t k_s exp(G_t − G_s), [B,H,C,C]
        off = mm("bhnik,bhnsk->bhnis", rows * row, col).reshape(b, h, c, c)
        diag = mm("bhnik,bhnjk,bhnijk->bhnij", rows, kb, pair)
        diag = (diag[:, :, :, :, None, :] * on_diag).reshape(b, h, c, c)
        return jnp.where(below, off, 0.0) + diag

    tri = jnp.tril(jnp.ones((c, c), bool))
    gamma = jnp.exp(big_g)
    lower = jnp.where(tri & ~jnp.eye(c, dtype=bool), scores(kb), 0.0) * beta[..., None]
    rhs = beta[..., None] * (v - mm("bhtk,bhkv->bhtv", k * gamma, s0))
    u = lax.linalg.triangular_solve(
        lower + jnp.eye(c, dtype=lower.dtype), rhs, left_side=True, lower=True, unit_diagonal=True
    )
    o = mm("bhtk,bhkv->bhtv", q * gamma, s0) + mm("bhts,bhsv->bhtv", jnp.where(tri, scores(qb), 0.0), u)
    to_end = jnp.exp(big_g[:, :, -1:] - big_g)  # a token's decay up to the chunk's end
    s1 = s0 * gamma[:, :, -1][..., None] + mm("bhsk,bhsv->bhkv", k * to_end, u)
    return o, s1


def _chunk_scalar(q, k, v, g, beta, s0):
    """One chunk with one decay a head: ``g [B, H, C, 1]``, the rest as
    :func:`_chunk`."""
    c = q.shape[2]
    mm = lambda eq, *xs: jnp.einsum(eq, *xs, precision=_HI)  # noqa: E731
    big_g = jnp.cumsum(g[..., 0], axis=2)  # [B, H, C], ≤ 0 and falling
    tri = jnp.tril(jnp.ones((c, c), bool))
    # exp(G_t − G_s) for s ≤ t: the exponent is ≤ 0 there, clamped elsewhere
    decay = jnp.where(tri, jnp.exp(jnp.minimum(big_g[..., :, None] - big_g[..., None, :], 0.0)), 0.0)
    gamma = jnp.exp(big_g)[..., None]
    lower = mm("bhtk,bhsk->bhts", k, k) * decay * ~jnp.eye(c, dtype=bool) * beta[..., None]
    rhs = beta[..., None] * (v - mm("bhtk,bhkv->bhtv", k * gamma, s0))
    u = lax.linalg.triangular_solve(
        lower + jnp.eye(c, dtype=lower.dtype), rhs, left_side=True, lower=True, unit_diagonal=True
    )
    o = mm("bhtk,bhkv->bhtv", q * gamma, s0) + mm("bhts,bhsv->bhtv", mm("bhtk,bhsk->bhts", q, k) * decay, u)
    to_end = jnp.exp(big_g[..., -1:] - big_g)[..., None]  # a token's decay up to the chunk's end
    s1 = s0 * gamma[:, :, -1][..., None] + mm("bhsk,bhsv->bhkv", k * to_end, u)
    return o, s1


def kda_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The chunked form: shapes as :func:`kda_recurrent`. ``T`` is padded up
    to whole chunks with tokens that leave the state alone."""
    b, t, h, dk = q.shape
    f32 = jnp.float32
    n = -(-t // chunk)
    pad = n * chunk - t

    def lay(x):  # [B, T, H, ·] -> [n, B, H, C, ·]
        x = jnp.pad(x.astype(f32), [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    xs = (lay(q), lay(k), lay(v), lay(g), lay(beta[..., None])[..., 0])

    one_chunk = _chunk_scalar if g.shape[-1] == 1 and dk > 1 else _chunk

    def step(s, x):
        o, s = one_chunk(*x, s)
        return s, o

    state, o = lax.scan(step, state.astype(f32), xs)  # o [n, B, H, C, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * chunk, h, -1)
    return o[:, :t], state


def causal_conv(x, conv_state, weight, n_valid):
    """Depthwise causal convolution of width ``W`` over the channels of
    ``x [B, T, C]`` continuing from ``conv_state [B, W − 1, C]`` (the inputs
    before ``x``): ``y_t = Σ_j weight[j] · in_{t − (W−1) + j}``. Returns ``y``
    and the state after the first ``n_valid [B]`` tokens of ``x`` (the rest
    are padding, or a lane that is not stepping): with ``n_valid = 0`` the
    state comes back untouched."""
    w = weight.shape[0]
    full = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B, W-1+T, C]
    t = x.shape[1]
    y = sum(full[:, j : j + t] * weight[j].astype(x.dtype) for j in range(w))  # in x's dtype (float32 from the caller)
    new = jax.vmap(lambda f, n: lax.dynamic_slice_in_dim(f, n, w - 1, axis=0))(full, n_valid)
    return y, new.astype(conv_state.dtype)
