"""The general traffic generator: sessions of chat turns, from parameters.

One traffic mix is one JSON file under ``benchmark/traffic/`` that names this
module and gives its parameters; a new mix is a new file, not new code. The
same ``(params, seed)`` gives the same sessions, texts and lengths.

The sessions are offered by a closed population of ``clients`` callers
(``harness/loadgen.py``): each sends its next turn a think time after its last
reply, and opens its next session, after a think time too, when the last one
ended.

Parameters (all optional but ``clients``, ``first_user_tokens`` and
``max_tokens``):

- ``shared_prefix_tokens``: every session's first message starts with the
  same text, this many tokens long with the BOS the tokenizer adds (the
  byte tokenizer makes N tokens of N - 1 ASCII bytes + BOS). 0: none.
- ``turns``, ``think_s``, ``first_user_tokens``, ``later_user_tokens``,
  ``max_tokens``: distributions, ``{"dist": "const" | "uniform" |
  "lognormal" | "geometric", ...}`` with ``min``/``max`` clamps.
- ``context_limit_tokens``: a session ends before its context (every turn's
  BOS + message + completion) would pass this.
- ``clients``, ``warmup_s``, ``drain_s``: the population, and how long it runs
  before the window and is waited for after it (read by the load generator).
- ``base``: another mix's name, whose parameters this file's are laid over
  (a population of its own over a shared mix; resolved by ``run.py``).

Every distribution is sampled through its inverse CDF from stratified uniform
numbers: of each ``STRATA`` consecutive draws of one quantity exactly one falls
in each ``1/STRATA`` slice of the distribution, in seeded random order. The
marginals are exact; a window's total work hardly depends on the seed, which
is what lets two runs of one cell on different seeds agree.
"""

from __future__ import annotations

import math
import random
import statistics
from typing import Iterator

STRATA = 32
_NORMAL = statistics.NormalDist()


class Stratified:
    """Uniform numbers in (0, 1), one per ``1/STRATA`` slice in every block
    of ``STRATA`` draws, the slices in seeded random order."""

    def __init__(self, seed: str):
        self._rng = random.Random(seed)
        self._block: list[int] = []

    def __call__(self) -> float:
        if not self._block:
            self._block = list(range(STRATA))
            self._rng.shuffle(self._block)
        u = (self._block.pop() + self._rng.random()) / STRATA
        return min(max(u, 1e-9), 1.0 - 1e-9)


def draw(u: float, spec: dict) -> float:
    """The distribution's value at quantile ``u``."""
    kind = spec["dist"]
    if kind == "const":
        x = spec["value"]
    elif kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "geometric":  # support 1, 2, ...; mean as given
        p = 1.0 / spec["mean"]
        x = 1 + int(math.log(1.0 - u) / math.log(1.0 - p)) if p < 1.0 else 1
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(spec.get("max", x), max(spec.get("min", x), x))


class Draws:
    """One stratified stream per quantity of the mix."""

    def __init__(self, seed: str):
        self._seed = seed
        self._streams: dict[str, Stratified] = {}

    def __call__(self, name: str, spec: dict) -> float:
        if name not in self._streams:
            self._streams[name] = Stratified(f"{self._seed}-{name}")
        return draw(self._streams[name](), spec)


_SYLLABLES = ["ka", "to", "ri", "mu", "se", "lo", "vi", "na", "pe", "zu", "da", "shi", "en", "or", "ta", "qu"]


def text_of(rng: random.Random, n_bytes: int) -> str:
    """``n_bytes`` ASCII bytes of pronounceable filler: words of 1-4
    syllables, so n-grams repeat about as often as in plain prose."""
    parts: list[str] = []
    size = 0
    while size < n_bytes:
        word = "".join(rng.choice(_SYLLABLES) for _ in range(rng.randint(1, 4)))
        parts.append(word)
        size += len(word) + 1
    return " ".join(parts)[:n_bytes].ljust(n_bytes, ".")


def _lengths(rng: Draws, p: dict) -> list[dict]:
    """One session's turns as token counts: ``user_tokens`` is the user's
    text alone (bytes), ``prompt_tokens`` what the engine will count for
    the turn (BOS + shared prefix + text)."""
    prefix = int(p.get("shared_prefix_tokens", 0))
    limit = int(p.get("context_limit_tokens", 1 << 30))
    n_turns = int(round(rng("turns", p.get("turns", {"dist": "const", "value": 1}))))
    turns, ctx = [], 0
    for k in range(n_turns):
        which = "first_user_tokens" if k == 0 or "later_user_tokens" not in p else "later_user_tokens"
        user = int(round(rng(which, p[which])))
        out = int(round(rng("max_tokens", p["max_tokens"])))
        # a caller also thinks before it opens its next session
        think = float(rng("think_s", p["think_s"])) if "think_s" in p else 0.0
        prompt = (prefix if k == 0 and prefix else 1) + user
        if ctx + prompt + out > limit:
            break
        ctx += prompt + out
        turns.append(
            {"user_tokens": user, "prompt_tokens": prompt, "max_tokens": out, "think_s": think, "context_tokens": ctx}
        )
    return turns


def shared_prefix(p: dict, seed: int) -> str:
    n = int(p.get("shared_prefix_tokens", 0))
    return text_of(random.Random(f"prefix-{seed}"), n - 1) if n else ""


def sessions(p: dict, seed: int, prefix_seed: int, tag: str) -> Iterator[dict]:
    """An endless stream of sessions. ``prefix_seed`` names the shared
    prefix, so that the warm-up stream (another ``seed``) shares it with the
    measured one."""
    lengths = Draws(f"lengths-{seed}")
    texts = random.Random(f"texts-{seed}")
    prefix = shared_prefix(p, prefix_seed)
    n = 0
    while True:
        turns = _lengths(lengths, p)
        if not turns:
            continue
        for k, turn in enumerate(turns):
            turn["message"] = (prefix if k == 0 else "") + text_of(texts, turn["user_tokens"])
        yield {"id": f"{tag}{seed}-{n}", "turns": turns}
        n += 1
