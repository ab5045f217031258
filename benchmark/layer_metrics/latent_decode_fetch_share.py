"""kernels: of the latent blocks the arena stores for every lane, the share the
decode steps of the window fetched (``attention.latent_decode_blocks_live`` over
``attention.latent_decode_blocks_stored`` of the engines' ``/metrics``, both
counted at each decode launch from the stepping lanes' positions: what
``mla_decode``'s index map fetches of a lane's row, a block of
``latent_block_positions`` rows, against the blocks a lane's whole row holds).
It is the live context over ``max_seq``, in whole blocks: a read of the arena's
every row would be 1. ``None`` for a program that does not count latent blocks
(no latent leaf, or a program from before the counter)."""

from harness import counters


def latent_counters(docs: list[dict]) -> list[dict]:
    """The ``attention`` blocks that count latent blocks."""
    blocks = [m.get("attention") or {} for m in docs]
    return blocks if blocks and all("latent_decode_blocks_stored" in a for a in blocks) else []


def read(before, after, responses, trace, cell):
    a, b = latent_counters(after), latent_counters(before)
    if not a or not b:
        return None
    stored = counters.delta(b, a, "latent_decode_blocks_stored")
    if stored <= 0:
        return None
    return counters.delta(b, a, "latent_decode_blocks_live") / stored
