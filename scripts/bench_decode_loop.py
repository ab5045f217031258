"""Fused decode-loop benchmark: ITL + host syncs, fused_decode on vs off.

A/B for the fused on-device decode loop (engine/llm.py `_fused_fn`): the
SAME engine config is driven twice, once dispatching one compiled chunk
per readback (the per-chunk baseline) and once running the multi-step
``lax.while_loop`` with in-loop sampling and ONE readback per loop.

Three measurements:

  batch sweep — per-request decode ITL ((wall - TTFT) / (tokens - 1)) at
             batch 1 / 4 / max, ignore_eos so every lane runs its full
             budget (fixed-length: the pure dispatch-overhead A/B). The
             per-batch ``itl_ratio_fused_over_off_b{1,4,max}`` fields are
             first-class artifact outputs, each with an explicit <= 1.0
             acceptance bar: the dynamic-rung loop (one executable, nsteps
             a runtime operand up to the fused cap) covers a request's
             whole budget in a few long loops where the per-chunk baseline
             pays dispatch + readback every decode_chunk steps. A second
             sampled tier (temperature > 0) re-runs the mid batch through
             the in-loop sampler — recorded as
             ``itl_ratio_fused_over_off_sampled`` (no hard bar: sampling
             cost is shared by both modes, the ratio is tracked for
             drift);
  raw step — per-step wall of the bare jitted (forward + sample_step)
             body (cache donated, token fed back, best-of): the compute
             the loop repeats, with zero scheduling around it. The
             acceptance bar is fused batch-1 ITL p50 within 1.2x of this
             floor — i.e. dispatch + readback + host processing amortized
             over the loop cost < 20%;
  natural EOS — greedy requests that stop at a real EOS mid-loop: the
             per-lane EOS mask parks the lane and the whole-batch early
             exit lands the packed readback on the host a few forwards
             after the stop instead of a full chunk later — the worker's
             ready-poll processes the finish BEFORE dispatching another
             (stale) loop, so host syncs per token come out strictly
             below the per-chunk baseline, which keeps paying for its
             pipelined stale successors after the lane is done.

The artifact being measured is scheduler+compiled-graph behavior identical
on any JAX platform, so a CPU run shows the control flow and the counts
(host syncs per token, early exits); its times are CPU times and say
nothing about the chip, where this A/B has not been measured yet.

Usage: JAX_PLATFORMS=cpu python scripts/bench_decode_loop.py
       ATPU_DECODELOOP_SMOKE=1 shortens every pass (make decodeloop).
Emits one JSON line on stdout AND writes BENCH_decode_loop.json at the
repo root (the committed artifact).
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _benchlib import make_engine, p50, percentile, write_artifact

SMOKE = os.environ.get("ATPU_DECODELOOP_SMOKE", "") not in ("", "0", "false")
MODEL = os.environ.get("ATPU_DECODELOOP_MODEL", "tiny")
MAX_BATCH = int(os.environ.get("ATPU_DECODELOOP_MAX_BATCH", "8"))
MAX_TOKENS = int(os.environ.get("ATPU_DECODELOOP_MAX_TOKENS", "24" if SMOKE else "64"))
PASSES = int(os.environ.get("ATPU_DECODELOOP_PASSES", "2" if SMOKE else "4"))
EOS_REQS = int(os.environ.get("ATPU_DECODELOOP_EOS_REQS", "6" if SMOKE else "16"))
FWD_ITERS = int(os.environ.get("ATPU_DECODELOOP_FWD_ITERS", "40" if SMOKE else "200"))

BATCHES = [1, 4, MAX_BATCH]


def _mk_engine(fused: bool, **extra):
    return make_engine(
        MODEL,
        max_batch=MAX_BATCH,
        max_seq=256,
        decode_chunk=8,
        prefill_chunk=32,
        fused_decode=fused,
        # spec off: prompt-lookup rounds would absorb most decode steps on
        # these repetitive bench prompts and dilute the loop A/B to noise
        # (spec x fused composition is pinned by tests/test_fused_decode.py)
        speculative=False,
        **extra,
    )


def _decode_itl(r: dict, wall_ms: float):
    if r["completion_tokens"] < 2 or r.get("ttft_ms") is None:
        return None
    return (wall_ms - r["ttft_ms"]) / (r["completion_tokens"] - 1)


async def _batch_pass(eng, batch: int, temperature: float = 0.0) -> list[float]:
    """One concurrent wave of ``batch`` fixed-length requests."""

    async def one(i):
        t0 = time.monotonic()
        r = await eng.generate(
            f"decode loop lane {i}",
            max_tokens=MAX_TOKENS,
            temperature=temperature,
            top_p=0.9 if temperature > 0 else 1.0,
            ignore_eos=True,
        )
        return _decode_itl(r, 1000 * (time.monotonic() - t0))

    itls = await asyncio.gather(*(one(i) for i in range(batch)))
    return [x for x in itls if x is not None]


async def _sweep(eng) -> dict:
    out = {}
    for b in BATCHES:
        itls: list[float] = []
        for _ in range(PASSES):
            itls.extend(await _batch_pass(eng, b))
        s = sorted(itls)
        out[f"itl_ms_p50_b{b}"] = p50(itls)
        out[f"itl_ms_p99_b{b}"] = percentile(s, 0.99)
    # sampled tier: temperature > 0 lanes exercise the full in-loop sampler
    # (top-k/top-p filter + categorical draw per step) instead of the
    # greedy argmax fast path
    sampled: list[float] = []
    for _ in range(PASSES):
        sampled.extend(await _batch_pass(eng, min(4, MAX_BATCH), temperature=0.8))
    out["itl_ms_p50_sampled"] = p50(sampled)
    return out


async def _eos_pass(fused: bool, eos_tok: int) -> dict:
    """Sequential greedy requests on a tokenizer whose EOS is pinned to a
    token the model actually emits (the only way a random tiny model stops
    naturally). skip_warmup so the fused loop bakes the pinned id."""
    eng = _mk_engine(fused, skip_warmup=True)
    eng.tokenizer.eos_id = eos_tok
    try:
        toks = 0
        for i in range(EOS_REQS):
            r = await eng.generate(
                "stop at eos", max_tokens=MAX_TOKENS, temperature=0.0
            )
            toks += r["completion_tokens"]
        m = eng.metrics()
        return {
            "requests": EOS_REQS,
            "tokens": toks,
            "completion_tokens_p50": toks / EOS_REQS,
            "host_syncs_total": m["host_syncs_total"],
            "host_syncs_per_token": m["host_syncs_per_token"],
        }
    finally:
        eng.shutdown()


def _raw_step_ms(eng) -> float:
    """Per-step wall of the bare jitted loop body — single-token forward
    (full slot batch, the tensor shape every decode step runs) + the
    in-loop sampler, sampled token fed back, cache donated so the
    measurement doesn't pay an arena copy the serving path never pays.
    Chains the donated cache; only run right before shutdown."""
    import jax
    import jax.numpy as jnp

    from agentainer_tpu.engine.sampling import sample_step

    B = eng.max_batch
    key = jax.random.PRNGKey(0)

    # sampler knobs are jit ARGS, not closure constants: closed over, XLA
    # constant-folds the greedy case down to a bare argmax and the "floor"
    # stops measuring the step the serving loop actually runs
    def step(params, cache, tok, pos, temps, topk, topp):
        logits, cache = eng._run_forward(
            params, tok[:, None], pos[:, None], cache, None
        )
        nxt = sample_step(logits[:, 0], key, temps, topk, topp)
        return nxt.astype(jnp.int32), cache

    fwd = jax.jit(step, donate_argnums=(1,))
    tok = jnp.zeros((B,), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    temps = jnp.zeros((B,), jnp.float32)
    topk = jnp.zeros((B,), jnp.int32)
    topp = jnp.ones((B,), jnp.float32)
    cache = eng.cache
    # compile outside the clock
    tok, cache = fwd(eng.params, cache, tok, pos, temps, topk, topp)
    tok.block_until_ready()
    best = float("inf")
    burst = 10
    for _ in range(max(1, FWD_ITERS // burst)):
        t0 = time.monotonic()
        for _ in range(burst):
            tok, cache = fwd(eng.params, cache, tok, pos, temps, topk, topp)
        tok.block_until_ready()
        best = min(best, 1000 * (time.monotonic() - t0) / burst)
    return round(best, 4)


async def _measure(fused: bool) -> dict:
    eng = _mk_engine(fused)
    try:
        syncs0 = eng.metrics()["host_syncs_total"]
        toks0 = eng.tokens_generated
        sweep = await _sweep(eng)
        m = eng.metrics()
        fixed_syncs_per_token = round(
            (m["host_syncs_total"] - syncs0) / max(1, eng.tokens_generated - toks0), 4
        )
        out = {
            "fused_decode": fused,
            **sweep,
            "host_syncs_per_token_fixed_len": fixed_syncs_per_token,
            "fused_loops_total": m["fused_loops_total"],
            "fused_steps_total": m["fused_steps_total"],
            "fused_early_exits_total": m["fused_early_exits_total"],
            "fused_exit_reason_hist": m["fused_exit_reason_hist"],
            "worker_errors": m["worker_errors"],
        }
        if not fused:
            out["raw_step_ms"] = _raw_step_ms(eng)
        return out
    finally:
        eng.shutdown()


async def run() -> dict:
    t0 = time.monotonic()
    base = await _measure(fused=False)
    fused = await _measure(fused=True)

    # pin the natural-EOS token from a greedy probe: the 3rd generated
    # token, so the stop lands INSIDE the first fused loop (chunk 8)
    probe = _mk_engine(False, skip_warmup=True)
    try:
        ref = await probe.generate(
            "stop at eos", max_tokens=8, temperature=0.0, ignore_eos=True
        )
        eos_tok = int(ref["tokens"][2])
    finally:
        probe.shutdown()
    eos_base = await _eos_pass(False, eos_tok)
    eos_fused = await _eos_pass(True, eos_tok)

    import jax

    raw = base.get("raw_step_ms")
    b1 = fused.get("itl_ms_p50_b1")

    def _ratio(key: str):
        f, o = fused.get(key), base.get(key)
        return round(f / o, 3) if (f and o) else None

    out = {
        "metric": "llm_fused_decode_itl_p50_b1_over_raw_step",
        "value": round(b1 / raw, 3) if (b1 and raw) else None,
        "unit": "ratio",
        # first-class per-batch fused/off ITL ratios, each barred <= 1.0
        **{
            f"itl_ratio_fused_over_off_b{b}": _ratio(f"itl_ms_p50_b{b}")
            for b in BATCHES
        },
        "itl_ratio_fused_over_off_sampled": _ratio("itl_ms_p50_sampled"),
        "syncs_per_token_fused": fused["host_syncs_per_token_fixed_len"],
        "syncs_per_token_off": base["host_syncs_per_token_fixed_len"],
        "eos_syncs_per_token_fused": eos_fused["host_syncs_per_token"],
        "eos_syncs_per_token_off": eos_base["host_syncs_per_token"],
        "platform": jax.default_backend(),
        "model": MODEL,
        "smoke": SMOKE,
        "max_tokens": MAX_TOKENS,
        "batches": BATCHES,
        "off": base,
        "fused": fused,
        "eos_off": eos_base,
        "eos_fused": eos_fused,
        "wall_s": round(time.monotonic() - t0, 1),
    }
    return out


def main() -> None:
    out = asyncio.run(run())
    write_artifact("BENCH_decode_loop.json", out)
    # acceptance guards: fused batch-1 decode ITL p50 within 1.2x of the
    # raw per-step floor; fused ITL p50 no worse than the per-chunk
    # baseline at EVERY batch size (the dynamic-rung loop must win, not
    # merely amortize); host syncs per token strictly below baseline on
    # the natural-EOS workload (early exit's stale-dispatch savings).
    # The fixed-length sync ratio is recorded but NOT barred: dispatch
    # counts there are equal by arithmetic, so the old <= guard could
    # never fail — vacuous bars are worse than no bars.
    ratios = [out[f"itl_ratio_fused_over_off_b{b}"] for b in BATCHES]
    ok = (
        out["value"] is not None
        and out["value"] <= 1.2
        and all(r is not None and r <= 1.0 for r in ratios)
        and out["eos_syncs_per_token_fused"] is not None
        and out["eos_syncs_per_token_off"] is not None
        and out["eos_syncs_per_token_fused"] < out["eos_syncs_per_token_off"]
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
