"""The ``llama`` family: the block of ``agentainer_tpu/models/llama.py``
(grouped-query attention with rotary embeddings, SwiGLU or a softmax-over-
top-k mixture of SwiGLU experts; Llama, Mistral, Mixtral), and the only file
of the benchmark that knows it. A configuration file names its family under
``"family"`` (absent: this one) and the harness finds the module by that
name (``harness/family.py``). What a family answers:

- ``model_config(doc, n_layers=None)``: the program's ``ModelConfig`` from
  the file's published ``config.json`` keys (``site/sitecustomize.py``
  registers it in the daemon and the engine host; the numerics child builds
  it at fewer layers);
- ``REHEARSAL_WIDTHS``: the keys ``run.py --rehearse`` overrides so that a
  CPU can serve the cell (control flow only);
- ``numerics_sizes(doc)``: layers, prefill tokens, decode steps and cache
  length of the numerics check;
- ``program(cfg, dev, dtype, cache_len)``: the program's side of that check;
- ``reference(params, cfg)``: the plain reference's side;
- ``decode_step_bytes``, ``prefill_flops``, ``kv_bytes_per_token``: the
  yardstick's arithmetic from the file's sizes alone (its own copy of what
  ``ModelConfig.flops_per_token`` models; the program's host-side MFU/MBU
  model is not read).

What a family does not hold, because a model PR adds family files and may
not loosen the check with one: the tokens, the positions compared, the error
measure, a tolerance, the controls (``harness/numerics_child.py``,
``harness/compare.py``). ``doc`` is a configuration file: published
``config.json`` keys at its top level. Nothing heavy is imported at module
level: ``run.py`` and the daemon's start-up hook import this file and never
JAX.
"""

from __future__ import annotations

REHEARSAL_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512, "num_hidden_layers": 2,
}

MAX_LAYERS = 2  # every layer is the same kind: two show that layers chain
REFERENCE_HBM_BYTES = 9e9  # float32 copy of the checked layers, at most
N_PREFILL = 96
N_DECODE = 8
CACHE_LEN = 256


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds the model's published ``config.json`` keys, as run."""
    from agentainer_tpu.models.configs import ModelConfig

    heads = int(doc["num_attention_heads"])
    if "head_dim" in doc and int(doc["head_dim"]) * heads != int(doc["hidden_size"]):
        raise ValueError("the program's block derives head_dim as hidden_size / heads")
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=int(n_layers if n_layers is not None else doc["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(doc.get("num_key_value_heads", heads)),
        ffn_dim=int(doc["intermediate_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=int(doc.get("num_local_experts", 0) or 0),
        experts_per_token=int(doc.get("num_experts_per_tok", 2) or 2),
    )


def numerics_sizes(doc: dict) -> dict:
    """2 layers, or 1 where two layers' float32 copy for the reference would
    not fit the device beside the program's int8 weights (a Mixtral layer is
    5.6 GB in float32)."""
    one = model_config(doc, n_layers=1)
    layer_f32 = 4 * (one.param_count() - 2 * one.vocab_size * one.dim)
    layers = max(1, min(MAX_LAYERS, int(REFERENCE_HBM_BYTES // layer_f32)))
    return {"layers": layers, "prefill": N_PREFILL, "decode": N_DECODE, "cache_len": CACHE_LEN}


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator), a fresh KV arena, and jitted prefill and one-token decode
    through that arena and the attention kernels the program plans on this
    device. ``prefill(params, cache, toks [T]) -> (logits [T, V], cache)``,
    ``decode(params, cache, tok, pos) -> (logits [V], cache)``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.llama import KVCache, forward
    from agentainer_tpu.ops.attention import plan_cache_attention

    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_cache_attention(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    @jax.jit
    def prefill(params, cache, toks):
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)[None]
        logits, cache = forward(params, cfg, toks[None], pos, cache, cache_attn_impl=plan.fn)
        return logits[0], cache

    @jax.jit
    def decode(params, cache, tok, pos):
        logits, cache = forward(params, cfg, tok[None, None], pos[None, None], cache, cache_attn_impl=plan.fn)
        return logits[0, 0], cache

    return {
        "params": params,
        "new_cache": lambda: KVCache.create(cfg, 1, cache_len, dtype=dtype),
        "prefill": prefill,
        "decode": decode,
        "attention": {"prefill": plan.prefill, "decode": plan.decode},
    }


def reference(params, cfg):
    """The reference's side: float32 weights in the layout of
    ``harness/reference.py`` from the program's pytree (int8 leaves
    dequantized), and ``forward(weights, tokens, act) -> logits [T, V]``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    from harness import reference as block

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    layers = [
        {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params["layers"].items()}
        for i in range(cfg.n_layers)
    ]
    weights = {
        "embed": dense(params["embed"]),
        "layers": layers,
        "final_norm": dense(params["final_norm"]),
        "lm_head": dense(params["lm_head"]),
    }
    kw = dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token if cfg.is_moe else 0,
    )
    return weights, lambda w, tokens, act: block.forward(w, tokens, act=act, **kw)


KV_BYTES = 2  # the arena is bf16
INT8 = 1


def _sizes(doc: dict) -> dict:
    d, f = int(doc["hidden_size"]), int(doc["intermediate_size"])
    h, kv = int(doc["num_attention_heads"]), int(doc.get("num_key_value_heads", doc["num_attention_heads"]))
    hd = d // h
    e = int(doc.get("num_local_experts", 0) or 0)
    return {
        "d": d, "f": f, "h": h, "kv": kv, "hd": hd, "e": e,
        "k": int(doc.get("num_experts_per_tok", 2) or 2),
        "layers": int(doc["num_hidden_layers"]), "vocab": int(doc["vocab_size"]),
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements of one layer, by part (norm vectors left out)."""
    s = _sizes(doc)
    attn = s["d"] * s["h"] * s["hd"] * 2 + s["d"] * s["kv"] * s["hd"] * 2
    ffn_one = 3 * s["d"] * s["f"]
    return {
        "attention": attn,
        "ffn": ffn_one * max(1, s["e"]),
        "ffn_routed": ffn_one * (s["k"] if s["e"] else 1),
        "router": s["d"] * s["e"],
    }


def decode_step_bytes(doc: dict, live_kv_tokens: float) -> float:
    """Bytes one decode step (one token for every lane) must read: every
    layer's weights as served (int8; on one chip every expert, because at a
    batch of 8 with two experts a token nearly every expert is chosen and
    the all-experts einsum reads them all anyway), the output head, and the
    keys and values of the live context. The embedding table is a row
    gather, not a stream, and is left out. Scales are 2 bytes per output
    channel and under 0.1 % of the stream: left out."""
    s = _sizes(doc)
    lw = layer_weight_elements(doc)
    per_layer = lw["attention"] + lw["ffn"] + lw["router"]
    weights = (s["layers"] * per_layer + s["d"] * s["vocab"]) * INT8
    kv = live_kv_tokens * kv_bytes_per_token(doc)
    return weights + kv


def kv_bytes_per_token(doc: dict) -> int:
    s = _sizes(doc)
    return 2 * s["layers"] * s["kv"] * s["hd"] * KV_BYTES


def prefill_flops(doc: dict, n_tokens: int, mean_context: float, routed: bool = True) -> float:
    """Matmul FLOPs (2 per multiply-add) to prefill ``n_tokens`` whose mean
    attendable context is ``mean_context``. ``routed`` counts the experts a
    token is routed to (the algorithm's need); ``False`` counts every expert
    (what the all-experts einsum executes)."""
    s = _sizes(doc)
    lw = layer_weight_elements(doc)
    per_layer = lw["attention"] + (lw["ffn_routed"] if routed else lw["ffn"]) + lw["router"]
    matmul = 2.0 * (s["layers"] * per_layer + s["d"] * s["vocab"])
    attn = 4.0 * s["h"] * s["hd"] * mean_context * s["layers"]
    return n_tokens * (matmul + attn)
