"""Child process: the program's forward against the plain reference, on this
process's device, at the cell's published widths and 2 layers (1 where two
layers' float32 copy for the reference would not fit the device beside the
program's int8 weights: a Mixtral layer is 5.6 GB in float32).

Runs before the engine takes the chip (a chip belongs to one process). Builds
the cell's model at 2 layers with the program's own int8 generator, runs the
program's prefill and then 8 decode steps through its KV arena and the
attention kernels it chooses on this device, and compares the logits of the
last prefill positions and of every decode step with
``benchmark/harness/reference.py`` run on the dequantized weights over the
whole sequence. Prints one JSON line; exit code 0 iff it passed.

    python -m benchmark.harness.numerics_child <config.json> <seed> [--rehearse] [--trace DIR]
"""

from __future__ import annotations

import json
import os
import sys

MAX_LAYERS = 2
REFERENCE_HBM_BYTES = 9e9  # float32 copy of the checked layers, at most
N_PREFILL = 96
N_DECODE = 8
CACHE_LEN = 256
COMPARE_LAST = 32  # prefill positions compared (the reference computes all)


def main() -> int:
    path, seed = sys.argv[1], int(sys.argv[2])
    rehearse = "--rehearse" in sys.argv
    trace_dir = sys.argv[sys.argv.index("--trace") + 1] if "--trace" in sys.argv else None
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(here), "site"))
    from sitecustomize import model_fields

    import jax
    import jax.numpy as jnp
    import numpy as np

    from agentainer_tpu.engine.quant import synthetic_quantized_params
    from agentainer_tpu.models.configs import ModelConfig
    from agentainer_tpu.models.llama import KVCache, forward
    from agentainer_tpu.ops.attention import plan_cache_attention
    from agentainer_tpu.ops.quant import QTensor
    from agentainer_tpu.utils.compile_cache import enable_compile_cache

    from benchmark.harness import reference

    enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if dev.platform != "tpu" and not rehearse:
        print(json.dumps({"ok": False, "device": device, "error": "no accelerator"}))
        return 3
    with open(path) as f:
        doc = json.load(f)
    one = ModelConfig(**model_fields(doc, n_layers=1))
    layer_f32 = 4 * (one.param_count() - 2 * one.vocab_size * one.dim)
    n_layers = max(1, min(MAX_LAYERS, int(REFERENCE_HBM_BYTES // layer_f32)))
    cfg = ModelConfig(**model_fields(doc, n_layers=n_layers))
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    params = synthetic_quantized_params(cfg, dtype, device=dev)
    plan = plan_cache_attention(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    rng = np.random.default_rng(seed)
    total = N_PREFILL + N_DECODE
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, size=total), jnp.int32)

    @jax.jit
    def prefill(params, cache, toks):
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)[None]
        logits, cache = forward(params, cfg, toks[None], pos, cache, cache_attn_impl=plan.fn)
        return logits[0], cache

    @jax.jit
    def decode(params, cache, tok, pos):
        logits, cache = forward(params, cfg, tok[None, None], pos[None, None], cache, cache_attn_impl=plan.fn)
        return logits[0, 0], cache

    def program():
        cache = KVCache.create(cfg, 1, CACHE_LEN, dtype=dtype)
        logits, cache = prefill(params, cache, tokens[:N_PREFILL])
        rows = [logits[-COMPARE_LAST:]]
        for i in range(N_PREFILL, total):  # teacher-forced: the seeded tokens, not the argmax
            step, cache = decode(params, cache, tokens[i], jnp.int32(i))
            rows.append(step[None])
        return jnp.concatenate(rows, axis=0)

    got = jax.block_until_ready(program())
    if trace_dir:  # a small recorded trace of known content, for trace_reduce's test
        jax.profiler.start_trace(trace_dir)
        jax.block_until_ready(program())
        jax.profiler.stop_trace()

    def dense(x):
        return (x.q.astype(jnp.float32) * x.scale.astype(jnp.float32)) if isinstance(x, QTensor) else x.astype(jnp.float32)

    layers = [
        {k: dense(jax.tree.map(lambda a: a[i], v)) for k, v in params["layers"].items()}
        for i in range(n_layers)
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
    ref_fn = jax.jit(lambda w, t, which: reference.forward(
        w, t, act=(lambda x: x, reference.as_bf16, reference.as_int8)[which], **kw), static_argnums=2)
    keep = slice(N_PREFILL - COMPARE_LAST, total)
    want = ref_fn(weights, tokens, 0)[keep]
    err = reference.rel_err(got, want)
    within = reference.share_within(got, want)
    bf16_control = reference.rel_err(ref_fn(weights, tokens, 1)[keep], want)
    int8_control = reference.rel_err(ref_fn(weights, tokens, 2)[keep], want)
    top1 = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    finite = bool(jnp.isfinite(got).all())
    # on the CPU the program computes in float32 and the controls say
    # nothing about it: a rehearsal checks the control flow only
    straddle = rehearse or (bf16_control < reference.REL_TOL < int8_control)
    ok = finite and err < reference.REL_TOL and within >= reference.MIN_SHARE_WITHIN and straddle
    print(
        json.dumps(
            {
                "ok": ok,
                "device": device,
                "config": cfg.name,
                "layers": n_layers,
                "positions_compared": int(got.shape[0]),
                "attention": {"prefill": plan.prefill, "decode": plan.decode},
                "rel_err": err,
                "share_of_positions_within": within,
                "tolerance": reference.REL_TOL,
                "control_bf16_activations": bf16_control,
                "control_int8_activations": int8_control,
                "controls_straddle_tolerance": bool(straddle),
                "top1_agreement": top1,
                "dtype": str(jnp.dtype(dtype)),
            }
        )
    )
    return 0 if ok else 4


if __name__ == "__main__":
    sys.exit(main())
