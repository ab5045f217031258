"""The ``smallthinker`` family: SmallThinker's block as
``agentainer_tpu/models/llama.py`` computes it with per-layer switches
(grouped-query attention with a head width of its own; layers with a sliding
window and rotary embeddings beside global layers with no positional embedding
at all; a router that reads the layer's input; ReGLU experts, softmax over the
chosen top k; the chip's share of the experts). ``families/llama.py`` says what
a family answers; the plain reference is ``families/smallthinker_reference.py``.
Beside the usual answers: ``ring_rows`` (what a lane keeps of a window layer)
and the arithmetic by kind of layer (``kv_rows_read``, ``attended_rows``).
Nothing heavy is imported at module level.

A configuration file of this family holds the published ``config.json`` keys
as run; ``moe_num_primary_experts`` counts the experts **held here**,
``experts_published`` the router's width, ``expert_parallel`` the deployment
(``ep`` chips share each layer; this is chip ``chip``, holding experts
``chip · held ..``), as ``families/kimi_linear.py`` has them.
"""

from __future__ import annotations

# CPU rehearsal: control flow only. The window is a width and stays 4096
# (``sliding_window_size`` is not overridden); two periods of four layers
REHEARSAL_WIDTHS = {
    "hidden_size": 48, "moe_ffn_hidden_size": 32, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 512, "num_hidden_layers": 8,
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "moe_num_primary_experts": 2, "experts_published": 8, "moe_num_active_primary_experts": 2,
    "expert_parallel": {"ep": 4, "chip": 0},
}

# one whole period at published widths (G W W W): a global NoPE layer and
# three window layers after it. Their float32 copy for the reference is
# 1.85 GB, the vocabulary's two matrices 3.1 GB more, beside 1.24 GB of int8
# weights. The prefill WRAPS THE RING: it is fed in the engine's chunks of 256
# through a cache sized as the engine sizes it (R = 4096 + 256 -> 4608 rows),
# 5,632 tokens = R + 1,024, so the last 32 prefill positions and every decode
# step read a ring whose every row has been overwritten once
PREFILL_CHUNK = 256  # the engine's shipped default of its ``prefill_chunk`` option
N_LAYERS = 4
N_PREFILL = 5632
N_DECODE = 8
CACHE_LEN = 6144

KV_BYTES = 2  # the arena is bf16
INT8 = 1


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds SmallThinker's published ``config.json`` keys, as run. A
    program without the K/V block's per-layer switches cannot build it
    (``TypeError``: the parent of the PR that adds the family fails the cell
    cleanly)."""
    from agentainer_tpu.models.configs import ModelConfig

    if doc.get("rope_scaling") is not None or doc.get("tie_word_embeddings"):
        raise ValueError("the program's block has no rope scaling and unties the output head")
    if not (doc.get("moe_primary_router_apply_softmax") and doc.get("norm_topk_prob")):
        raise ValueError("the program's rule here is a softmax over the chosen top k (both keys true)")
    layers = int(n_layers if n_layers is not None else doc["num_hidden_layers"])
    rope, window = (tuple(int(x) for x in doc[k][:layers]) for k in ("rope_layout", "sliding_window_layout"))
    if len(rope) < layers or len(window) < layers:
        raise ValueError(f"rope_layout / sliding_window_layout name fewer than {layers} layers")
    held = int(doc["moe_num_primary_experts"])
    published = int(doc.get("experts_published", held))
    chip = int((doc.get("expert_parallel") or {}).get("chip", 0))
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=layers,
        n_heads=int(doc["num_attention_heads"]),
        n_kv_heads=int(doc["num_key_value_heads"]),
        head_size=int(doc["head_dim"]),
        ffn_dim=int(doc["moe_ffn_hidden_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        n_experts=published,
        experts_per_token=int(doc["moe_num_active_primary_experts"]),
        moe_renormalize=True,
        window=int(doc["sliding_window_size"]),
        window_layers=window,
        rope_layers=rope,
        ffn_act="relu",
        early_router=True,
        experts_held=held if held < published else 0,
        expert_offset=chip * held if held < published else 0,
    )


def numerics_sizes(doc: dict) -> dict:
    layers = min(N_LAYERS, int(doc["num_hidden_layers"]))
    return {"layers": layers, "prefill": N_PREFILL, "decode": N_DECODE, "cache_len": CACHE_LEN}


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator; the norm vectors stay dense), a fresh two-leaf cache as the
    model builds it for launches of ``PREFILL_CHUNK`` rows, and jitted prefill
    and one-token decode through it with the attention kernels the program
    plans on this device. The prefill feeds its tokens as an engine does, a
    chunk of ``PREFILL_CHUNK`` rows a launch (one scan over the chunks; a
    launch longer than the ring was sized for is refused by ``forward``), and
    returns the LAST chunk's logits ``[PREFILL_CHUNK, V]``: the harness reads
    the last 32 rows, and 5,632 x 151,936 float32 logits would be 3.4 GB.
    A chunk of 256 rows is over the MoE cut, so prefill takes the sorted
    grouped FFN over the held experts and decode the all-held-experts einsum.
    Signatures otherwise as ``families/llama.py``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.llama import forward, init_cache, ring_plan
    from agentainer_tpu.ops.attention import plan_cache_attention

    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_cache_attention(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    chunk = PREFILL_CHUNK

    @jax.jit
    def prefill(params, cache, toks):
        if toks.shape[0] % chunk:
            raise ValueError(f"the prefill is fed in whole chunks of {chunk}")
        chunks = toks.reshape(-1, chunk)
        starts = jnp.arange(chunks.shape[0], dtype=jnp.int32) * chunk

        def step(cache, inp):
            tok, start = inp
            pos = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
            logits, cache = forward(params, cfg, tok[None], pos, cache, cache_attn_impl=plan.fn, slot=jnp.int32(0))
            return cache, logits[0]

        # every chunk but the last only fills the cache
        cache, _ = jax.lax.scan(lambda c, i: (step(c, i)[0], None), cache, (chunks[:-1], starts[:-1]))
        return step(cache, (chunks[-1], starts[-1]))[::-1]

    @jax.jit
    def decode(params, cache, tok, pos):
        logits, cache = forward(params, cfg, tok[None, None], pos[None, None], cache, cache_attn_impl=plan.fn)
        return logits[0, 0], cache

    def new_cache():
        return init_cache(cfg, 1, cache_len, dtype=dtype, **ring_plan(cfg, dtype, chunk))

    return {
        "params": params,
        "new_cache": new_cache,
        "prefill": prefill,
        "decode": decode,
        "attention": {"prefill": plan.prefill, "decode": plan.decode},
    }


def reference(params, cfg):
    """The reference's side: float32 weights in the layout of
    ``families/smallthinker_reference.py`` from the program's pytree (int8
    leaves dequantized; the experts are the held share), and ``forward(weights,
    tokens, act) -> logits [T, V]``."""
    import importlib

    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    block = importlib.import_module("families.smallthinker_reference")

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
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token, rope_layout=cfg.rope_layers,
        window_layout=cfg.window_layers, window=cfg.window, expert_offset=cfg.expert_offset,
    )
    return weights, lambda w, tokens, act: block.forward(w, tokens, act=act, **kw)


# -- the yardstick's arithmetic, from the file's sizes alone ---------------------


def _sizes(doc: dict) -> dict:
    layers = int(doc["num_hidden_layers"])
    window = [int(x) for x in doc["sliding_window_layout"][:layers]]
    held = int(doc["moe_num_primary_experts"])
    return {
        "d": int(doc["hidden_size"]), "layers": layers, "vocab": int(doc["vocab_size"]),
        "h": int(doc["num_attention_heads"]), "kv": int(doc["num_key_value_heads"]), "hd": int(doc["head_dim"]),
        "f": int(doc["moe_ffn_hidden_size"]), "held": held, "experts": int(doc.get("experts_published", held)),
        "k": int(doc["moe_num_active_primary_experts"]),
        "n_window": sum(window), "n_global": layers - sum(window), "window": int(doc["sliding_window_size"]),
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements of one layer, by part (the two norm vectors left out:
    5,120 elements beside 115 M)."""
    s = _sizes(doc)
    return {
        "attention": 2 * s["d"] * s["hd"] * (s["h"] + s["kv"]),
        "expert": 3 * s["d"] * s["f"],
        "router": s["d"] * s["experts"],
    }


def param_count(doc: dict) -> int:
    """Every parameter held here, vectors included (the count
    ``ModelConfig.param_count`` has to agree with)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    per_layer = lw["attention"] + lw["router"] + s["held"] * lw["expert"] + 2 * s["d"]
    return s["layers"] * per_layer + 2 * s["d"] * s["vocab"] + s["d"]


def weight_bytes(doc: dict) -> float:
    """Bytes of weights a step streams, once: every layer's as served (int8)
    with the experts HELD here all counted (8 lanes x 6 choices over 64
    experts reach 12 of the 16 held a step on average, a prefill chunk all of
    them; the einsum of the decode step reads them all), the router and the
    output head. The embedding is a row gather."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    per_layer = lw["attention"] + lw["router"] + s["held"] * lw["expert"]
    return INT8 * (s["layers"] * per_layer + s["d"] * s["vocab"])


def row_bytes(doc: dict) -> int:
    """One position's K and V row in ONE layer."""
    s = _sizes(doc)
    return 2 * s["kv"] * s["hd"] * KV_BYTES


def ring_rows(doc: dict) -> int:
    """Rows a lane keeps of a window layer as the engine sizes the ring
    (``agentainer_tpu/models/llama.ring_rows``): window + a prefill chunk's
    rows, rounded up to the K/V block of 512, never more than the arena."""
    s = _sizes(doc)
    opts = doc.get("engine_options") or {}
    chunk, block = int(opts.get("prefill_chunk", PREFILL_CHUNK)), 512
    up = lambda n: -(-n // block) * block  # noqa: E731
    return min(up(s["window"] + chunk), up(int(opts.get("max_seq", doc["max_position_embeddings"]))))


def kv_rows_read(doc: dict, context: float) -> dict:
    """Rows one query at ``context`` reads, by kind of layer: a global layer
    its whole context, a window layer ``min(context, window)``."""
    s = _sizes(doc)
    return {"global": float(context), "window": float(min(context, s["window"]))}


def kv_bytes_per_token(doc: dict, context: float | None = None) -> float:
    """Bytes of cache a token of context costs a reader: a K and a V row in
    every layer that still holds it. ``context`` absent: a token inside the
    window (every layer holds it). At ``context`` the MEAN over the context's
    tokens: the global layers hold every one, the window layers the last
    ``window`` of them."""
    s = _sizes(doc)
    if context is None or context <= 0:
        return float(s["layers"] * row_bytes(doc))
    rows = kv_rows_read(doc, context)
    return row_bytes(doc) * (s["n_global"] * rows["global"] + s["n_window"] * rows["window"]) / context


def kv_resident_bytes_per_lane(doc: dict) -> int:
    """What a lane's cache occupies: ``max_seq`` rows of every global layer,
    ``ring_rows`` of every window layer."""
    s = _sizes(doc)
    max_seq = int((doc.get("engine_options") or {}).get("max_seq", doc["max_position_embeddings"]))
    return row_bytes(doc) * (s["n_global"] * max_seq + s["n_window"] * ring_rows(doc))


def decode_step_bytes(doc: dict, live_kv_tokens: float = 0.0, kv_bytes: float | None = None, lanes: float = 1.0) -> float:
    """Bytes one decode step (one token for every lane) must read: the weights
    as served, once, and the K and V rows the step's queries see. ``kv_bytes``
    given (the reader's, from the engine's block counters): those. Otherwise
    ``live_kv_tokens`` rows spread over ``lanes`` lanes of equal context, a
    window layer counted at ``min(context, window)`` rows a lane."""
    if kv_bytes is None:
        s = _sizes(doc)
        rows = kv_rows_read(doc, live_kv_tokens / max(lanes, 1e-9))
        kv_bytes = lanes * row_bytes(doc) * (s["n_global"] * rows["global"] + s["n_window"] * rows["window"])
    return weight_bytes(doc) + kv_bytes


def attended_rows(doc: dict, prompt_tokens: float) -> dict:
    """Sum over a prompt's tokens of the keys each attends to, by kind of
    layer: ``P² / 2`` where every key is seen, and ``W² / 2 + (P - W) · W``
    in a window layer once the prompt passes the window."""
    s = _sizes(doc)
    p, w = float(prompt_tokens), float(s["window"])
    return {"global": p * p / 2.0, "window": p * p / 2.0 if p <= w else w * w / 2.0 + (p - w) * w}


def prefill_flops(
    doc: dict, n_tokens: float, mean_context: float, routed: bool = True, mean_window_context: float | None = None
) -> float:
    """Matmul FLOPs (2 per multiply-add) to prefill ``n_tokens`` on this chip
    whose mean attendable context is ``mean_context`` in a global layer and
    ``mean_window_context`` in a window layer (absent: ``min(mean_context,
    window)``): the weights a token meets (``routed``: its chosen experts that
    are held here, k · held / E on average; otherwise every held expert), the
    router, the head, and the attention of each kind of layer."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    experts = s["k"] * s["held"] / s["experts"] if routed else s["held"]
    matmul = 2.0 * (s["layers"] * (lw["attention"] + lw["router"] + experts * lw["expert"]) + s["d"] * s["vocab"])
    if mean_window_context is None:
        mean_window_context = min(mean_context, s["window"])
    attn = 4.0 * s["h"] * s["hd"] * (s["n_global"] * mean_context + s["n_window"] * mean_window_context)
    return n_tokens * (matmul + attn)
