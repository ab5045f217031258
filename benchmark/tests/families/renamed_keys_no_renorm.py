"""Test-only family: ``renamed_keys`` with a reference of another block's
mathematics (``tests/reference_no_renorm.py``). The numerics child has to fail
it on ``rel_err``: the file compared against is the family's own, not
``harness/reference.py``."""

from families import renamed_keys
from families.renamed_keys import *  # noqa: F401,F403


def reference(params, cfg):
    import reference_no_renorm

    weights, _ = renamed_keys.reference(params, cfg)
    kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
              top_k=cfg.experts_per_token)
    return weights, lambda w, tokens, act: reference_no_renorm.forward(w, tokens, act=act, **kw)
