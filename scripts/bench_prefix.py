"""Cross-session prefix KV cache benchmark: cold vs warm-prefix TTFT.

A/B for the prefix arena (engine/llm.py): the SAME engine config is driven
twice, once with ``prefix_cache`` off (every session pays full prefill for
the shared system prompt — the pre-arena engine) and once with it on (the
second session FORKS the cached persona prefix on admission and prefills
only its uncached tail). Measures:

  ttft_ms_p50 (warm/base) — TTFT of probe sessions that share a long
                            system-prompt prefix, after the first session
                            populated the arena (vs the off baseline where
                            every probe re-prefills it)
  prefix_tokens_saved     — prefill tokens the forks skipped; must account
                            for the TTFT difference
  itl_ms_steady           — steady-state decode of a long generation (the
                            regression guard: the arena never touches the
                            decode path)
  flattened per-turn      — gemini-style history-flattened turns: per-turn
                            prompt tokens vs tokens actually prefilled
                            (the stable persona+history head forks; only
                            the window tail re-prefills)

The scheduler/copy artifact being measured is host+device-graph behavior
identical on any JAX platform, so a CPU run shows the control flow and the
counts (hits, tokens saved); its times are CPU times and say nothing about
the chip, where this A/B has not been measured yet.

Usage: JAX_PLATFORMS=cpu python scripts/bench_prefix.py
Emits one JSON line on stdout AND writes BENCH_prefix.json at the repo
root (the committed artifact).
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _benchlib import make_engine, p50 as _p50, steady_itl, text_of_tokens, write_artifact

MODEL = os.environ.get("ATPU_PFX_MODEL", "tiny")
PROBES = int(os.environ.get("ATPU_PFX_PROBES", "16"))
MAX_SEQ = int(os.environ.get("ATPU_PFX_MAX_SEQ", "2048"))
# shared system-prompt size in TOKENS (the acceptance bar is ≥256; the
# default exercises the full 1024 bucket so the fork skips ~all prefill)
SYS_TOKENS = int(os.environ.get("ATPU_PFX_SYS_TOKENS", "1040"))
FLAT_TURNS = int(os.environ.get("ATPU_PFX_FLAT_TURNS", "6"))


def _mk_engine(prefix_cache: bool):
    return make_engine(
        MODEL,
        max_batch=4,
        max_seq=MAX_SEQ,
        decode_chunk=8,
        prefill_chunk=256,
        prefix_cache=prefix_cache,
    )


_text_of_tokens = text_of_tokens


async def _probe_ttfts(eng, persona: str) -> list[float]:
    """TTFT of PROBES session-less requests sharing the persona prefix,
    each with a distinct user tail (so only the prefix can be reused)."""
    out = []
    for k in range(PROBES):
        r = await eng.generate(
            f"{persona} user question {k} please answer", max_tokens=8, temperature=0.0
        )
        out.append(r["ttft_ms"])
    return out


async def _steady_itl(eng) -> float:
    """Wall-clock ms per generated token of an uncontended long
    generation, best of two passes (regression guard)."""
    return await steady_itl(eng, passes=2, max_tokens=300)


async def _flattened_turns(eng) -> list[dict]:
    """Per-turn prefill cost for gemini-style flattened-history prompting:
    persona + growing history, one fresh generate per turn. With the arena
    on, turn N forks the longest bucket-prefix of turn N-1's prompt."""
    persona = _text_of_tokens(eng, 300, "You are a terse and careful agent. ")
    history: list[str] = []
    turns = []
    for t in range(FLAT_TURNS):
        prompt = persona + "\n\n" + "\n".join(history) + f"\nUser: question {t}\nAssistant:"
        saved0 = eng.prefix_tokens_saved
        r = await eng.generate(prompt, max_tokens=8, temperature=0.0)
        saved = eng.prefix_tokens_saved - saved0
        turns.append(
            {
                "turn": t,
                "prompt_tokens": r["prompt_tokens"],
                "tokens_saved": saved,
                "tokens_prefilled": r["prompt_tokens"] - saved,
                "ttft_ms": r["ttft_ms"],
            }
        )
        history.append(f"User: question {t}")
        history.append(f"Assistant: {r['text']}")
    return turns


async def _measure(prefix_cache: bool) -> dict:
    eng = _mk_engine(prefix_cache)
    try:
        persona = _text_of_tokens(
            eng, SYS_TOKENS, "You are agent seven of the fleet. Be concise and exact. "
        )
        # first session populates the arena (or just prefills, when off)
        cold = await eng.generate(
            persona + " user question cold start", max_tokens=8, temperature=0.0
        )
        ttfts = await _probe_ttfts(eng, persona)
        itl = await _steady_itl(eng)
        flat = await _flattened_turns(eng)
        m = eng.metrics()
        return {
            "prefix_cache": prefix_cache,
            "sys_prompt_tokens": len(eng.tokenizer.encode(persona)),
            "ttft_ms_cold_first_session": round(cold["ttft_ms"], 3),
            "ttft_ms_p50": _p50(ttfts),
            "ttft_samples": [round(x, 2) for x in ttfts],
            "itl_ms_steady": itl,
            "prefix_hits": m["prefix_hits"],
            "prefix_misses": m["prefix_misses"],
            "prefix_tokens_saved": m["prefix_tokens_saved"],
            "prefix_arena_entries": m["prefix_arena_entries"],
            "prefix_arena_bytes": m["prefix_arena_bytes"],
            "prefix_evictions_total": m["prefix_evictions_total"],
            "flattened_turns": flat,
            "flattened_prefilled_total": sum(t["tokens_prefilled"] for t in flat),
            "flattened_prompt_total": sum(t["prompt_tokens"] for t in flat),
            "worker_errors": m["worker_errors"],
        }
    finally:
        eng.shutdown()


async def run() -> dict:
    t0 = time.monotonic()
    base = await _measure(prefix_cache=False)
    warm = await _measure(prefix_cache=True)
    ratio = None
    if base["ttft_ms_p50"]:
        ratio = round(warm["ttft_ms_p50"] / base["ttft_ms_p50"], 3)
    itl_reg = None
    if base["itl_ms_steady"]:
        itl_reg = round(warm["itl_ms_steady"] / base["itl_ms_steady"] - 1.0, 4)
    # tokens_saved accounting: every warm probe should have forked the
    # largest bucket ≤ the persona length
    saved_per_probe = warm["prefix_tokens_saved"] / max(1, PROBES + FLAT_TURNS)
    import jax

    return {
        "metric": "llm_warm_prefix_ttft_p50_over_no_cache",
        "value": ratio,
        "unit": "ratio",
        "platform": jax.default_backend(),
        "model": MODEL,
        "probes": PROBES,
        "no_cache": base,
        "prefix_cache": warm,
        "itl_steady_regression": itl_reg,
        "tokens_saved_per_probe_avg": round(saved_per_probe, 1),
        "wall_s": round(time.monotonic() - t0, 1),
    }


def main() -> None:
    out = asyncio.run(run())
    write_artifact("BENCH_prefix.json", out)
    # acceptance guard (ISSUE 2): warm-prefix TTFT ≤ 0.5× the no-cache
    # baseline, steady ITL regression < 5%, and the forks actually skipped
    # the shared prefix (saved tokens account for the difference)
    ok = (
        out["value"] is not None
        and out["value"] <= 0.5
        and (out["itl_steady_regression"] is None or out["itl_steady_regression"] < 0.05)
        and out["prefix_cache"]["prefix_tokens_saved"] >= 256 * PROBES
    )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
