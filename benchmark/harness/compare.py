"""The comparison that decides the numerics part of ``correct``, once, for
every family: ``harness/numerics_child.py`` applies it to the logits of
whatever family it loads, and a family file holds no tolerance.
"""

from __future__ import annotations

import jax.numpy as jnp

# Agreement asked of the program's logits against the family's plain
# reference: per compared position, the root mean square of the difference
# over the standard deviation of the reference's logits there
# (``position_errs``); the median over the positions has to be under the
# tolerance, and so do at least ``MIN_SHARE_WITHIN`` of the positions. Per
# position, because a mixture's router is a discontinuity: random weights give
# near-tied router logits, a rounding in bfloat16 then sends a token to another
# expert, and that one position is far off in the program and in the bf16
# control alike (measured: one seed of five, whole-sample error 8.8 % against
# 0.9 %) while every other position agrees. A term left out of the mathematics
# moves every position. The program computes in bfloat16 with float32
# accumulation; every run measures, beside the program's own error, two
# controls computed by the family's reference itself at the same widths on the
# same tokens: the reference with every matmul input rounded to bfloat16 (what
# the configuration states: it must pass) and with every matmul input
# quantized to int8 per tensor (a lower precision than stated: it must fail).
# A run whose controls do not straddle the tolerance reports ``correct:
# false``, so the tolerance cannot silently go slack. Readings at the
# published widths, 2 layers (my chip run, PR 22): see PERF.md section 6.
REL_TOL = 0.02
MIN_SHARE_WITHIN = 0.85


def position_errs(got, want):
    """``got``, ``want`` ``[positions, vocab]`` -> relative error per position."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return jnp.sqrt(jnp.mean((got - want) ** 2, axis=-1)) / jnp.std(want, axis=-1)


def rel_err(got, want) -> float:
    """Median over the positions of the relative error."""
    return float(jnp.median(position_errs(got, want)))


def share_within(got, want) -> float:
    return float(jnp.mean(position_errs(got, want) < REL_TOL))


def as_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def as_int8(x):
    """Per-tensor absmax int8 round trip of an activation."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale
