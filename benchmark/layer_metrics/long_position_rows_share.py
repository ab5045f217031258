"""model runner: of the rows the window's launches positioned (every real
prefill row and every decode step of a stepping lane), the share at or past
the model's original context (``attention.rows_past_original_max`` over
``attention.rows_positioned`` of the engines' ``/metrics``,
``attention.rope_original_max`` positions): the rows whose YaRN-scaled
frequencies see distances the model was not trained on unscaled and whose
query takes Llama-4's scale. Higher means the cell spends more of its rows
where the configuration's long-context arithmetic differs from plain RoPE.
``None`` for a program that counts no such rows (a model with no original
context, or a program from before the counter)."""

from harness import counters


def position_counters(docs: list[dict]) -> list[dict]:
    """The ``attention`` blocks that count positioned rows."""
    blocks = [m.get("attention") or {} for m in docs]
    return blocks if blocks and all("rows_positioned" in a for a in blocks) else []


def read(before, after, responses, trace, cell):
    a, b = position_counters(after), position_counters(before)
    if not a or not b:
        return None
    rows = counters.delta(b, a, "rows_positioned")
    if rows <= 0:
        return None
    return counters.delta(b, a, "rows_past_original_max") / rows
