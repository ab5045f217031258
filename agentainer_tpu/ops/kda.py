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
- :func:`kda_chunked` — prefill: chunks of :data:`CHUNK` tokens. What a
  chunk owes to its own tokens (the score matrices, the decay masks and the
  inverse of its unit triangle) is built for every chunk of the launch at
  once, before the scan; between chunks one state update, so a layer takes
  ``T / CHUNK`` sequential steps of four matmuls and not T;
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

``T = (I + diag(β) A)⁻¹`` does not read the state, so with ``W = T diag(β)
(Γ ⊙ K)`` and ``U_0 = T diag(β) V`` (the UT transform of Yang et al., arXiv
2406.06484) the solve is ``U = U_0 − W S_0``: :func:`_before_the_state` makes
``W, U_0, Γ ⊙ Q, B, K ⊙ exp(G_C − G)`` and ``Γ_C`` for all chunks in one
batched pass, and the scan's body is three matmuls against ``S`` and one
against ``U``. ``T`` is an explicit inverse made of matmuls
(:func:`_unit_lower_inverse`): the :data:`SUB`-token diagonal blocks by forward
substitution over their rows, merged to 32 and to 64 by ``[[A, 0], [X, B]]⁻¹ =
[[A⁻¹, 0], [−B⁻¹ X A⁻¹, B⁻¹]]``, as flash-linear-attention's ``solve_tril``
does for these models. ``lax.linalg.triangular_solve`` did this until PR 35: on
a TPU it is a 64-step substitution a head on the vector unit, a custom call
that took 27 % of Olmo-Hybrid's prefill chunk and half of Kimi's chunked rule.
**Not** the nilpotent product form ``(I − L)(I + L²)(I + L⁴)…``: the powers of
``L`` grow binomially and cancel. On 64 near-identical unit keys (a repeated
token) its error against a float64 solve reads 4.5e10 at β = 1 and 6.8e20 at
β = 2, and 0.19 on rank-4 keys at β = 1, where the blocked inverse reads
2.0e-7, 1.7e-6 and 2.3e-7 and ``triangular_solve`` 3.0e-7, 3.0e-6 and 2.6e-7
(float32 on the CPU, PR 35; ``tests/test_olmo_hybrid.py`` holds the chunked
rule to 4 × the solve's error on such keys). A masked token's row of ``T`` is
the unit row exactly, so the masking rule above holds bit for bit.

Every exponent above is ≤ 0, and the code keeps it so: the textbook
factorisation ``A = (Γ ⊙ K)(K / Γ)ᵀ`` overflows ``1 / Γ`` once a chunk's
summed log-decay passes −88 (a published checkpoint's decays can: ``exp(A_log)``
up to 16 times a softplus), so ``A`` and ``B`` are built in :data:`SUB`-token
blocks — a block below the diagonal factorises around the decay at the start
of its ROW block (both factors then ≤ 1), a block on the diagonal takes
``exp(G_t − G_s)`` pair by pair. With one decay a head there is nothing to
sum over channels: ``A = (K Kᵀ) ⊙ exp(G_t − G_s)`` is one matmul and a mask
with every exponent ≤ 0 (:func:`_scores_scalar`). The matmuls inside are
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


def _mm(eq: str, *xs):
    """An einsum in float32 at ``highest`` precision."""
    return jnp.einsum(eq, *xs, precision=_HI)


def _block_diagonal(blocks):
    """``[..., n, s, s]`` → ``[..., n·s, n·s]`` with the blocks down the diagonal."""
    *lead, n, s, _ = blocks.shape
    on_diag = jnp.eye(n, dtype=blocks.dtype)[:, None, :, None]  # [n,1,n,1]
    return (blocks[..., :, :, None, :] * on_diag).reshape(*lead, n * s, n * s)


def _unit_lower_inverse(lower):
    """``(I + lower)⁻¹`` for ``lower [..., C, C]`` strictly lower-triangular,
    float32: the diagonal blocks of ``min(SUB, C)`` by forward substitution
    over their rows (every block of every leading index at once), then merged
    pair by pair, ``[[A, 0], [X, B]]⁻¹ = [[A⁻¹, 0], [−B⁻¹ X A⁻¹, B⁻¹]]``, up
    to ``C`` (a power-of-two multiple of the block). A zero row of ``lower``
    gives the unit row exactly."""
    c = lower.shape[-1]
    sub = min(SUB, c)
    n = c // sub
    if n * sub != c or n & (n - 1):
        raise ValueError(f"a chunk of {c} tokens is not a power-of-two multiple of {sub}")
    blocks = jnp.einsum("...isit->...ist", lower.reshape(*lower.shape[:-2], n, sub, n, sub))  # [..., n, sub, sub]
    rows = jnp.arange(sub)[:, None]

    def substitute(i, inv):  # row i of a block's inverse from the rows above it: e_i − Σ_j L_ij inv_j
        l_i = lax.dynamic_slice_in_dim(blocks, i, 1, axis=-2)  # [..., n, 1, sub]
        row = -jnp.sum(jnp.swapaxes(l_i, -1, -2) * inv, axis=-2, keepdims=True)
        return jnp.where(rows == i, row + (jnp.arange(sub) == i), inv)

    inv = lax.fori_loop(1, sub, substitute, jnp.broadcast_to(jnp.eye(sub, dtype=lower.dtype), blocks.shape))
    inv = _block_diagonal(inv)
    at = jnp.arange(c)
    size = sub
    while size < c:  # the blocks of `lower` that join two inverted blocks of `size` into one of twice that
        joins = (at[:, None] // (2 * size) == at[None, :] // (2 * size)) & (at[:, None] // size != at[None, :] // size)
        inv = inv - _mm("...ij,...jk->...ik", _mm("...ij,...jk->...ik", inv, jnp.where(joins, lower, 0.0)), inv)
        size *= 2
    return inv


def _scores(q, k, g):
    """``A`` and ``B`` of the module docstring with a decay per channel:
    ``q, k, g [..., C, dk]`` → ``(A [..., C, C]`` strictly lower, ``B`` lower,
    ``G [..., C, dk])``, built in :data:`SUB`-token blocks so that no exponent
    is positive."""
    *lead, c, dk = q.shape
    sub = min(SUB, c)
    n = c // sub
    big_g = jnp.cumsum(g, axis=-2)  # G_t, ≤ 0 and falling
    blocks = lambda x: x.reshape(*lead, n, sub, dk)  # noqa: E731
    gb, kb, qb = blocks(big_g), blocks(k), blocks(q)
    # the decay before each block's first token
    start = jnp.concatenate([jnp.zeros_like(gb[..., :1, 0, :]), gb[..., :-1, -1, :]], axis=-2)  # [..., n, dk]
    row = jnp.exp(gb - start[..., None, :])  # a row's decay since its block began
    # a column token's decay up to row block i's start (≤ 1 for the columns
    # before that block, the only ones kept; clamped elsewhere)
    col = k[..., None, :, :] * jnp.exp(jnp.minimum(start[..., None, :] - big_g[..., None, :, :], 0.0))  # [..., n, C, dk]
    pair = jnp.exp(jnp.minimum(gb[..., :, None, :] - gb[..., None, :, :], 0.0))  # [..., n, sub, sub, dk]
    block_of = jnp.arange(c) // sub
    below = block_of[:, None] > block_of[None, :]  # column's block before the row's

    def scores(rows):  # rows [..., n, sub, dk] -> Σ_c rows_t k_s exp(G_t − G_s), [..., C, C]
        off = _mm("...nik,...nsk->...nis", rows * row, col).reshape(*lead, c, c)
        diag = _mm("...nik,...njk,...nijk->...nij", rows, kb, pair)
        return jnp.where(below, off, 0.0) + _block_diagonal(diag)

    tri = jnp.tril(jnp.ones((c, c), bool))
    return jnp.where(tri & ~jnp.eye(c, dtype=bool), scores(kb), 0.0), jnp.where(tri, scores(qb), 0.0), big_g


def _scores_scalar(q, k, g):
    """:func:`_scores` with one decay a head, ``g [..., C, 1]``: one matmul
    and a mask each."""
    c = q.shape[-2]
    big_g = jnp.cumsum(g[..., 0], axis=-1)[..., None]  # [..., C, 1], ≤ 0 and falling
    tri = jnp.tril(jnp.ones((c, c), bool))
    # exp(G_t − G_s) for s ≤ t: the exponent is ≤ 0 there, clamped elsewhere
    decay = jnp.where(tri, jnp.exp(jnp.minimum(big_g - jnp.swapaxes(big_g, -1, -2), 0.0)), 0.0)
    kk, qk = _mm("...tk,...sk->...ts", k, k), _mm("...tk,...sk->...ts", q, k)
    return kk * decay * ~jnp.eye(c, dtype=bool), qk * decay, big_g


def _before_the_state(q, k, v, g, beta):
    """Everything of a chunk that does not read the carried state, for every
    chunk at once: ``q, k, g [..., C, dk]`` (``g [..., C, 1]`` with one decay
    a head), ``v [..., C, dv]``, ``beta [..., C]``, all float32 →
    ``(W, U_0, Γ ⊙ Q, B, K ⊙ exp(G_C − G), Γ_C)``."""
    a, b, big_g = (_scores_scalar if g.shape[-1] == 1 and q.shape[-1] > 1 else _scores)(q, k, g)
    gamma = jnp.exp(big_g)
    t_beta = _unit_lower_inverse(a * beta[..., None]) * beta[..., None, :]  # T · diag(β)
    to_end = jnp.exp(big_g[..., -1:, :] - big_g)  # a token's decay up to the chunk's end
    w, u0 = _mm("...ts,...sk->...tk", t_beta, k * gamma), _mm("...ts,...sv->...tv", t_beta, v)
    return w, u0, q * gamma, b, k * to_end, jnp.swapaxes(gamma[..., -1:, :], -1, -2)


def kda_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The chunked form: shapes as :func:`kda_recurrent`. ``T`` is padded up
    to whole chunks with tokens that leave the state alone."""
    b, t, h, _ = q.shape
    f32 = jnp.float32
    n = -(-t // chunk)
    pad = n * chunk - t

    def lay(x):  # [B, T, H, ·] -> [n, B, H, C, ·]
        x = jnp.pad(x.astype(f32), [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    def step(s, x):  # what reads the state: three matmuls against it and one against U
        w, u0, q_gamma, scores_q, k_to_end, gamma_end = x
        u = u0 - _mm("bhtk,bhkv->bhtv", w, s)
        o = _mm("bhtk,bhkv->bhtv", q_gamma, s) + _mm("bhts,bhsv->bhtv", scores_q, u)
        return s * gamma_end + _mm("bhsk,bhsv->bhkv", k_to_end, u), o

    with jax.named_scope("kda_prepass"):
        xs = _before_the_state(lay(q), lay(k), lay(v), lay(g), lay(beta[..., None])[..., 0])
    with jax.named_scope("kda_scan"):
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
