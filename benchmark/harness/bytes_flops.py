"""Bytes and operations a step of the model has to move and do, from the
configuration file's sizes alone (the yardstick's copy of the arithmetic in
``ModelConfig.flops_per_token``; the program's host-side MFU/MBU model is not
read). ``doc`` is a configuration file: published ``config.json`` keys.
"""

from __future__ import annotations

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
