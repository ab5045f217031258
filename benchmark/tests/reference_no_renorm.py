"""A reference that is wrong for the program's block, for the test-only
family ``renamed_keys_no_renorm``: the ``llama`` block with the gates of the
chosen experts taken from a softmax over all experts and not renormalised
over the chosen k (the rule of OLMoE's router; Mixtral's, and the program's,
is a softmax over the chosen k). Every other term is ``harness/reference.py``'s."""

from unittest import mock

import jax
import jax.numpy as jnp

from harness import reference


def moe(x, lp, top_k, act):
    probs = jax.nn.softmax(act(x) @ lp["router"], axis=-1)  # over all experts
    gates, chosen = jax.lax.top_k(probs, top_k)  # the term left out: gates / sum(gates)
    out = jnp.zeros_like(x)
    for e in range(lp["router"].shape[-1]):
        w = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = out + w[:, None] * reference.swiglu(x, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e], act)
    return out


def forward(weights, tokens, **kw):
    with mock.patch.object(reference, "moe", moe):
        return reference.forward(weights, tokens, **kw)
