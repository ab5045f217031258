"""The plain reference against the program's ``models/llama.forward`` on the
CPU in float32, on ``tiny`` and ``tiny-moe`` with seeded random weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agentainer_tpu.models.configs import get_config
from agentainer_tpu.models.llama import forward, init_params

from harness import compare, reference


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_reference_matches_the_program_forward(name):
    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(3, cfg.vocab_size, size=48), jnp.int32)
    pos = jnp.arange(48, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, tokens[None], pos, None, use_flash=False)
    layers = [{k: v[i].astype(jnp.float32) for k, v in params["layers"].items()} for i in range(cfg.n_layers)]
    weights = {"embed": params["embed"], "layers": layers, "final_norm": params["final_norm"], "lm_head": params["lm_head"]}
    kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
              top_k=cfg.experts_per_token if cfg.is_moe else 0)
    want = reference.forward(weights, tokens, **kw)
    assert compare.rel_err(got[0], want) < 1e-5
    # the controls: bf16 activations are closer than int8 activations
    bf16 = compare.rel_err(reference.forward(weights, tokens, act=compare.as_bf16, **kw), want)
    int8 = compare.rel_err(reference.forward(weights, tokens, act=compare.as_int8, **kw), want)
    assert 0 < bf16 < int8
    # a wrong rotary base is a thousand times the agreement asked above, even
    # at these widths, where attention moves the logits little
    assert compare.rel_err(reference.forward(weights, tokens, **{**kw, "rope_theta": 1.0}), want) > 5e-3
