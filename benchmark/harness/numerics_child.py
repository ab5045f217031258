"""Child process: the program's forward against the family's plain reference,
on this process's device, at the cell's published widths and the few layers
its family says (``llama``: 2, or 1 where two layers' float32 copy for the
reference would not fit the device beside the program's int8 weights).

Runs before the engine takes the chip (a chip belongs to one process). The
configuration's family (``harness/family.py``) builds both sides: the
program's model with its own weight generator, its prefill and one-token
decode through its cache and the attention kernels it chooses on this device;
and the plain reference with the same weights in float32. Everything that
decides the verdict is here and in ``harness/compare.py``, the same for
every family: the seeded tokens, teacher forcing, the positions compared
(the last ``COMPARE_LAST`` of the prefill and every decode step, against the
reference's full forward over the whole sequence), the error measure and its
tolerance, and the two controls. Prints one JSON line; exit code 0 iff it
passed.

    python -m benchmark.harness.numerics_child <config.json> <seed> [--rehearse] [--trace DIR]
"""

from __future__ import annotations

import json
import os
import sys

COMPARE_LAST = 32  # prefill positions compared (the reference computes all)


def main() -> int:
    path, seed = sys.argv[1], int(sys.argv[2])
    rehearse = "--rehearse" in sys.argv
    trace_dir = sys.argv[sys.argv.index("--trace") + 1] if "--trace" in sys.argv else None
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # benchmark/

    import jax
    import jax.numpy as jnp
    import numpy as np

    from agentainer_tpu.utils.compile_cache import enable_compile_cache

    from harness import compare
    from harness.family import family_of

    enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if dev.platform != "tpu" and not rehearse:
        print(json.dumps({"ok": False, "device": device, "error": "no accelerator"}))
        return 3
    with open(path) as f:
        doc = json.load(f)
    family = family_of(doc)
    sizes = family.numerics_sizes(doc)
    n_prefill, total = sizes["prefill"], sizes["prefill"] + sizes["decode"]
    if not COMPARE_LAST <= n_prefill < total <= sizes["cache_len"]:
        raise ValueError(f"family {family.__name__} gives lengths the check cannot use: {sizes}")
    cfg = family.model_config(doc, n_layers=sizes["layers"])
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    prog = family.program(cfg, dev, dtype, sizes["cache_len"])
    params, prefill, decode = prog["params"], prog["prefill"], prog["decode"]

    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, size=total), jnp.int32)

    def program():
        logits, cache = prefill(params, prog["new_cache"](), tokens[:n_prefill])
        rows = [logits[-COMPARE_LAST:]]
        for i in range(n_prefill, total):  # teacher-forced: the seeded tokens, not the argmax
            step, cache = decode(params, cache, tokens[i], jnp.int32(i))
            rows.append(step[None])
        return jnp.concatenate(rows, axis=0)

    got = jax.block_until_ready(program())
    if trace_dir:  # a small recorded trace of known content, for trace_reduce's test
        jax.profiler.start_trace(trace_dir)
        jax.block_until_ready(program())
        jax.profiler.stop_trace()

    weights, forward = family.reference(params, cfg)
    ref_fn = jax.jit(lambda w, t, which: forward(
        w, t, (lambda x: x, compare.as_bf16, compare.as_int8)[which]), static_argnums=2)
    keep = slice(n_prefill - COMPARE_LAST, total)
    want = ref_fn(weights, tokens, 0)[keep]
    err = compare.rel_err(got, want)
    within = compare.share_within(got, want)
    bf16_control = compare.rel_err(ref_fn(weights, tokens, 1)[keep], want)
    int8_control = compare.rel_err(ref_fn(weights, tokens, 2)[keep], want)
    top1 = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    finite = bool(jnp.isfinite(got).all())
    # on the CPU the program computes in float32 and the controls say
    # nothing about it: a rehearsal checks the control flow only
    straddle = rehearse or (bf16_control < compare.REL_TOL < int8_control)
    ok = finite and err < compare.REL_TOL and within >= compare.MIN_SHARE_WITHIN and straddle
    print(
        json.dumps(
            {
                "ok": ok,
                "device": device,
                "config": cfg.name,
                "layers": sizes["layers"],
                "positions_compared": int(got.shape[0]),
                "attention": prog["attention"],
                "rel_err": err,
                "share_of_positions_within": within,
                "tolerance": compare.REL_TOL,
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
