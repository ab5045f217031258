"""The ``olmo_hybrid`` family: Olmo-Hybrid's block as
``agentainer_tpu/models/hybrid.py`` computes it (a gated delta rule with one
decay a head and negative eigenvalues beside full softmax attention with
QK-norm and no rotary embedding, a dense SwiGLU in every layer, the OLMo-2
norm placement). ``families/llama.py`` says what a family answers; the plain
reference is ``families/olmo_hybrid_reference.py``. Beside the usual answers:
``state_bytes_per_lane``, and the bytes and FLOPs a call of each new kernel
needs (``gdn_decode_bytes``, ``full_decode_bytes``, ``gdn_prefill_flops``).
Nothing heavy is imported at module level.

A configuration file of this family holds the published ``config.json`` keys
as run.
"""

from __future__ import annotations

REHEARSAL_WIDTHS = {
    "hidden_size": 96, "intermediate_size": 128, "num_attention_heads": 6, "num_key_value_heads": 6,
    "vocab_size": 512, "num_hidden_layers": 4,
    "layer_types": ["linear_attention", "linear_attention", "linear_attention", "full_attention"],
    "linear_num_key_heads": 6, "linear_num_value_heads": 6, "linear_key_head_dim": 12,
    "linear_value_head_dim": 24, "linear_conv_kernel_dim": 4,
}

# one whole period at published widths (L L L F): every kind of mixer, a full
# layer after three delta-rule layers. Its float32 copy for the reference is
# 6.4 GB (3.1 GB of it the vocabulary's two matrices) beside 1.6 GB of int8
# weights; two periods (9.7 + 2.4 GB) leave the reference's own temporaries
# too little of a 16 GB chip
N_LAYERS = 4
N_PREFILL = 192  # three delta-rule chunks of 64
N_DECODE = 8
CACHE_LEN = 256

STATE_BYTES = 4  # the recurrent state is float32
ARENA_BYTES = 2  # K/V rows and conv state are bf16
INT8 = 1
LINEAR, FULL = "linear_attention", "full_attention"


def _kinds(doc: dict, n_layers: int) -> tuple:
    types = list(doc["layer_types"])[:n_layers]
    if len(types) < n_layers or set(types) - {LINEAR, FULL}:
        raise ValueError(f"layer_types names {sorted(set(types))} for {n_layers} layers")
    return tuple("full" if t == FULL else "gdn" for t in types)


def stored_kv_heads(kv: int) -> int:
    """Heads a stored K/V row holds: a count that is not 1, 2, 4 or a multiple
    of 8 is stored rounded up to one (the flash kernels read whole ``[KV, hd]``
    tiles, and HBM pads the unrounded row to the same bytes anyway)."""
    return kv if kv in (1, 2, 4) else -(-kv // 8) * 8


def model_config(doc: dict, n_layers: int | None = None):
    """The program's ``ModelConfig`` from a configuration file whose top
    level holds Olmo-Hybrid's published ``config.json`` keys, as run. A
    program without the fields of this family's mixers cannot build it
    (``TypeError``: the parent of the PR that adds the family fails the cell
    cleanly)."""
    from agentainer_tpu.models.configs import ModelConfig

    if doc.get("attention_bias") or doc.get("tie_word_embeddings"):
        raise ValueError("the program's block has no attention bias and unties the output head")
    if (doc.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("this family's full layers carry no rotary embedding (rope_theta null)")
    if int(doc["linear_num_key_heads"]) != int(doc["linear_num_value_heads"]):
        raise ValueError("the program's delta rule has one key head a value head")
    layers = int(n_layers if n_layers is not None else doc["num_hidden_layers"])
    heads = int(doc["num_attention_heads"])
    return ModelConfig(
        name=doc["name"],
        vocab_size=int(doc["vocab_size"]),
        dim=int(doc["hidden_size"]),
        n_layers=layers,
        n_heads=heads,
        n_kv_heads=int(doc.get("num_key_value_heads", heads)),
        ffn_dim=int(doc["intermediate_size"]),
        max_seq_len=int(doc["max_position_embeddings"]),
        rope_theta=0.0,
        norm_eps=float(doc["rms_norm_eps"]),
        qk_norm=True,
        layer_kinds=_kinds(doc, layers),
        kda_heads=int(doc["linear_num_value_heads"]),
        kda_head_dim=int(doc["linear_key_head_dim"]),
        kda_v_dim=int(doc["linear_value_head_dim"]),
        kda_conv=int(doc["linear_conv_kernel_dim"]),
        delta_neg_eigval=bool(doc["linear_allow_neg_eigval"]),
        post_norm=True,
        n_dense_layers=layers,
        dense_ffn_dim=int(doc["intermediate_size"]),
    )


def numerics_sizes(doc: dict) -> dict:
    layers = min(N_LAYERS, int(doc["num_hidden_layers"]))
    return {"layers": layers, "prefill": N_PREFILL, "decode": N_DECODE, "cache_len": CACHE_LEN}


def program(cfg, dev, dtype, cache_len: int) -> dict:
    """The program's side: seeded synthetic weights as served (its own int8
    generator; the vectors stay dense), a fresh cache as the model builds it
    (K/V rows, recurrent state, conv state), and jitted prefill and one-token
    decode through it with the kernels the program plans on this device
    (prefill of 192 rows takes the chunked delta rule and ``flash_prefill``;
    decode the fused state update and ``flash_decode``). Signatures as
    ``families/llama.py``."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.hybrid import plan_hybrid
    from agentainer_tpu.models.llama import forward, init_cache

    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_hybrid(cfg)

    @jax.jit
    def prefill(params, cache, toks):
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)[None]
        logits, cache = forward(params, cfg, toks[None], pos, cache, cache_attn_impl=plan)
        return logits[0], cache

    @jax.jit
    def decode(params, cache, tok, pos):
        logits, cache = forward(params, cfg, tok[None, None], pos[None, None], cache, cache_attn_impl=plan)
        return logits[0, 0], cache

    return {
        "params": params,
        "new_cache": lambda: init_cache(cfg, 1, cache_len, dtype=dtype),
        "prefill": prefill,
        "decode": decode,
        "attention": {k: v for k, v in plan.describe().items() if k != "reason"},
    }


def reference(params, cfg):
    """The reference's side: float32 weights in the layout of
    ``families/olmo_hybrid_reference.py`` from the program's pytree (int8
    leaves dequantized; the program's merged q|k|v projection and conv
    filters split into the published three), and ``forward(weights, tokens,
    act) -> logits [T, V]``."""
    import importlib

    import jax
    import jax.numpy as jnp

    from agentainer_tpu.ops.quant import QTensor

    block = importlib.import_module("families.olmo_hybrid_reference")

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    def layer_of(group: str, i: int) -> dict:
        return {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params[group].items()}

    ck = cfg.kda_heads * cfg.kda_head_dim
    layers, seen = [], {"gdn": 0, "full": 0}
    for i, kind in enumerate(cfg.layer_kinds):
        lp = {k: dense(v[i]) for k, v in params["layers"].items()}
        mixer = layer_of(kind, seen[kind])
        seen[kind] += 1
        if kind == "gdn":
            for name, part in zip("qkv", jnp.split(mixer.pop("wqkv"), [ck, 2 * ck], axis=-1)):
                lp["w" + name] = part
            for name, part in zip("qkv", jnp.split(mixer.pop("conv"), [ck, 2 * ck], axis=-1)):
                lp["conv_" + name] = part
        lp.update(mixer)
        lp.update(layer_of("dense", i))
        layers.append(lp)
    weights = {
        "embed": dense(params["embed"]),
        "layers": layers,
        "final_norm": dense(params["final_norm"]),
        "lm_head": dense(params["lm_head"]),
    }
    kw = dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, lin_heads=cfg.kda_heads, lin_key_dim=cfg.kda_head_dim,
        lin_value_dim=cfg.kda_v_dim, norm_eps=cfg.norm_eps, neg_eigval=cfg.delta_neg_eigval,
    )
    return weights, lambda w, tokens, act: block.forward(w, tokens, act=act, **kw)


# -- the yardstick's arithmetic, from the file's sizes alone ---------------------


def _sizes(doc: dict) -> dict:
    layers = int(doc["num_hidden_layers"])
    kinds = _kinds(doc, layers)
    d, h = int(doc["hidden_size"]), int(doc["num_attention_heads"])
    return {
        "d": d, "layers": layers, "vocab": int(doc["vocab_size"]), "f": int(doc["intermediate_size"]),
        "n_gdn": kinds.count("gdn"), "n_full": kinds.count("full"),
        "lh": int(doc["linear_num_value_heads"]), "dk": int(doc["linear_key_head_dim"]),
        "dv": int(doc["linear_value_head_dim"]), "conv": int(doc["linear_conv_kernel_dim"]),
        "h": h, "kv": int(doc.get("num_key_value_heads", h)), "hd": d // h,
    }


def layer_weight_elements(doc: dict) -> dict:
    """Matrix elements by part (vectors left out: a delta-rule layer's conv
    filters, decay bias and norms are 46 K elements beside 88.7 M)."""
    s = _sizes(doc)
    ck, cv = s["lh"] * s["dk"], s["lh"] * s["dv"]
    return {
        "gdn": s["d"] * (2 * ck + cv) + s["d"] * cv + cv * s["d"] + 2 * s["d"] * s["lh"],  # q k v, gate, out, decay and beta
        "full": 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["kv"] * s["hd"],
        "ffn": 3 * s["d"] * s["f"],
    }


def param_count(doc: dict) -> int:
    """Every parameter, vectors included (the count ``ModelConfig.param_count``
    has to agree with)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    channels = s["lh"] * (2 * s["dk"] + s["dv"])
    gdn_vectors = s["conv"] * channels + 2 * s["lh"] + s["dv"]
    full_vectors = (s["h"] + s["kv"]) * s["hd"]
    return (
        s["n_gdn"] * (lw["gdn"] + gdn_vectors) + s["n_full"] * (lw["full"] + full_vectors)
        + s["layers"] * (lw["ffn"] + 2 * s["d"]) + 2 * s["d"] * s["vocab"] + s["d"]
    )


def weight_bytes(doc: dict) -> float:
    """Bytes of weights a step streams: every layer's matrices as served
    (int8) and the output head (the embedding is a row gather)."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    return INT8 * (s["n_gdn"] * lw["gdn"] + s["n_full"] * lw["full"] + s["layers"] * lw["ffn"] + s["d"] * s["vocab"])


def state_bytes_per_lane(doc: dict) -> int:
    """The per-lane recurrent state (float32, ``[dk, H·dv]`` a layer: whole
    tiles, nothing padded) and conv state (bf16)."""
    s = _sizes(doc)
    state = s["n_gdn"] * s["lh"] * s["dk"] * s["dv"] * STATE_BYTES
    conv = s["n_gdn"] * (s["conv"] - 1) * s["lh"] * (2 * s["dk"] + s["dv"]) * ARENA_BYTES
    return state + conv


def kv_bytes_per_token(doc: dict, stored: bool = True) -> int:
    """Positional bytes a token adds: a K and a V row in every full layer, at
    the head count the rows are STORED with (``stored=False``: the model's)."""
    s = _sizes(doc)
    kv = stored_kv_heads(s["kv"]) if stored else s["kv"]
    return 2 * s["n_full"] * kv * s["hd"] * ARENA_BYTES


def kernel_calls_per_step(doc: dict) -> dict:
    """Calls of each kernel in one decode step: one a layer of its kind."""
    s = _sizes(doc)
    return {"gdn_decode": s["n_gdn"], "flash_decode": s["n_full"]}


def gdn_decode_bytes(doc: dict, lanes: float) -> float:
    """One call of the GDN decode kernel (one layer): the state of EVERY lane
    the call is given read and written (a masked lane's tile is written back
    as read), 147 KB a lane and head both ways."""
    s = _sizes(doc)
    return 2.0 * lanes * s["lh"] * s["dk"] * s["dv"] * STATE_BYTES


def full_decode_bytes(doc: dict, live_kv_tokens: float) -> float:
    """One call of ``flash_decode`` (one full layer): the K and V rows of the
    live context at the stored head count."""
    s = _sizes(doc)
    return live_kv_tokens * 2 * stored_kv_heads(s["kv"]) * s["hd"] * ARENA_BYTES


def decode_step_bytes(doc: dict, live_kv_tokens: float, live_lanes: float | None = None) -> float:
    """Bytes one decode step (one token for every lane) must move: the
    weights as served, the recurrent state of the stepping lanes read AND
    written (and their conv state), and the K/V rows of the live context at
    the stored head count. ``live_lanes`` absent: every lane of the
    configuration's ``max_batch``."""
    lanes = live_lanes if live_lanes is not None else float((doc.get("engine_options") or {}).get("max_batch", 1))
    return weight_bytes(doc) + 2.0 * lanes * state_bytes_per_lane(doc) + live_kv_tokens * kv_bytes_per_token(doc)


def gdn_prefill_flops(doc: dict, n_tokens: int, chunk: int = 64) -> float:
    """Matmul FLOPs of the chunked delta rule for ``n_tokens`` of one layer
    (projections not counted): per chunk of C tokens and head, K Kᵀ and Q Kᵀ
    (2 · 2·C²·dk), the solve (C²·dv), K S₀, Q S₀, the state update
    (3 · 2·C·dk·dv) and B·U (2·C²·dv)."""
    s = _sizes(doc)
    per_chunk = 4.0 * chunk * chunk * s["dk"] + 3.0 * chunk * chunk * s["dv"] + 6.0 * chunk * s["dk"] * s["dv"]
    return s["lh"] * per_chunk * (n_tokens / chunk)


def prefill_flops(doc: dict, n_tokens: int, mean_context: float, routed: bool = True) -> float:
    """Matmul FLOPs (2 per multiply-add) to prefill ``n_tokens`` whose mean
    attendable context is ``mean_context``: every weight a token meets (the
    FFN is dense: ``routed`` changes nothing), the delta rule of the linear
    layers and the attention of the full layers."""
    s, lw = _sizes(doc), layer_weight_elements(doc)
    matmul = 2.0 * (s["n_gdn"] * lw["gdn"] + s["n_full"] * lw["full"] + s["layers"] * lw["ffn"] + s["d"] * s["vocab"])
    attn = 4.0 * s["h"] * s["hd"] * mean_context * s["n_full"]
    return n_tokens * (matmul + attn) + s["n_gdn"] * gdn_prefill_flops(doc, n_tokens)
