"""scheduler: the share of the window's decode launches that went with a
prefill chunk's launch (``jit_prefill_with_decode``: one launch, one stream of
the weights for both): the mixed launches of the engine's launch ledger
(``/metrics`` ``launches``) over ``decode_steps``, the launches of every
program that steps the decode lanes. 0 where no tick ever has a prompt pending
beside a decoding lane, or where the engine has no mixed step; near 1 where
nearly every tick has.

The other readers of ``launches`` take the ledger's names and rows from here.
A document without ``launches`` (the parent's) gives ``None``."""

from harness import counters

MIXED = "jit_prefill_with_decode"
DECODE = "jit_decode_n"


def ledgers(docs) -> list[dict] | None:
    """The engines' ledgers, or ``None`` where one publishes none."""
    found = [m.get("launches") for m in docs]
    return found if found and all(isinstance(x, dict) for x in found) else None


def total(docs: list[dict], field: str, program: str, key: str | None = None) -> float:
    """``field`` summed over ``program``'s rows (those of ``key`` alone where it is given)."""
    return float(sum(row[field] for ledger in docs for k, row in ledger.get(program, {}).items() if key in (None, k)))


def delta(before, after, field: str, program: str, key: str | None = None) -> float | None:
    b, a = ledgers(before), ledgers(after)
    if b is None or a is None:
        return None
    return total(a, field, program, key) - total(b, field, program, key)


def read(before, after, responses, trace, cell):
    mixed = delta(before, after, "n", MIXED)
    stepped = counters.delta(before, after, "decode_steps")
    return mixed / stepped if mixed is not None and stepped > 0 else None
