"""Rotary position embeddings (RoPE): Llama's, and YaRN's frequencies.

No reference counterpart (the reference has no model code, SURVEY.md §2.3);
this is green-field TPU-first design: pure functions of (x, positions) with
static shapes so XLA fuses the rotation into the surrounding matmuls. The
default layout is split-half (rotate_half, Llama's convention: the pair of
dims ``(i, i + d/2)``); ``interleave`` rotates adjacent pairs ``(2i, 2i + 1)``
(the DeepSeek family's ``rope_interleave``). The inverse frequencies are
Llama's, or YaRN's (:func:`yarn_frequencies`). Angles are float32 products of
an exact float32 position (a whole number under 2**24) and a frequency: at
position 16k the fastest pair's angle is still resolved to 1e-3 rad, and a
reference that forms the same product reads the same angle.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)


def yarn_frequencies(
    head_dim: int, theta: float, factor: float, original_max: int, beta_fast: float = 32.0, beta_slow: float = 1.0
) -> jnp.ndarray:
    """YaRN's inverse frequencies (arXiv 2309.00071, "NTK-by-parts"), shape
    [head_dim // 2], float32. Pair ``i`` turns ``original_max · f_i / 2π``
    times over the original context; pairs that turn ``beta_fast`` times or
    more keep ``f_i``, pairs that turn ``beta_slow`` times or fewer take
    ``f_i / factor``, and a linear ramp over the pair index joins the two
    (the bounds are the whole pair indices around those turn counts, as the
    public modelling code rounds them). Computed on the host in float64 and
    rounded once: a table of ``head_dim // 2`` constants in the program."""
    half = head_dim // 2
    f = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def pair_of(turns: float) -> float:  # the (fractional) pair that turns ``turns`` times
        return head_dim * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray((f / factor) * ramp + f * (1.0 - ramp), jnp.float32)


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float, *, interleave: bool = False, inv_freq=None,
    rotary_dim: int | None = None, scale: float = 1.0,
) -> jnp.ndarray:
    """Rotate ``x`` [..., T, n_heads, head_dim] by per-token ``positions`` [..., T].

    Computed in float32 regardless of input dtype (bf16 angles lose precision
    at long context), cast back on return. ``inv_freq``: the frequencies where
    they are not Llama's (:func:`yarn_frequencies`); ``interleave``: adjacent
    pairs (module docstring). ``rotary_dim``: only the FIRST so many dims of a
    head are rotated (a partial rotary embedding: the frequencies and the
    pairing are those of a head that wide) and the rest pass as they are;
    ``scale`` multiplies cos and sin (YaRN's ``attention_factor``: a product
    of two rotated halves takes it squared, the unrotated dims' not at all).
    """
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        rotated = apply_rope(
            x[..., :rotary_dim], positions, theta, interleave=interleave, inv_freq=inv_freq, scale=scale
        )
        return jnp.concatenate([rotated, x[..., rotary_dim:]], axis=-1)
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta) if inv_freq is None else inv_freq  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if interleave:
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (head_dim // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)
