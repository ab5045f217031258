"""model runner: token rows the MoE path pushes through expert FFNs for each
(token, chosen expert) pair it routes, from the ``moe`` block of the engine's
``/metrics`` (the path the compiled steps trace). The all-experts einsum
pushes every token through every expert: experts / top-k (OLMoE 8.0, Mixtral
4.0), whatever the traffic. A routed path would push what its buffers hold,
towards 1: nothing counts that yet, so it gives nothing to read, like a dense
model or a program without the block."""


def read(before, after, responses, trace, cell):
    for m in after:
        moe = m.get("moe") or {}
        if moe.get("impl") == "all_experts_einsum" and moe.get("top_k"):
            return moe["experts"] / moe["top_k"]
    return None
