"""model runner: of the rows of the sorted grouped FFN's buffer, the share that
are real (token, expert) pairs, in a cell of the ``laguna`` family (``moe`` block
of the engines' ``/metrics``, counted at every launch). ``moe.rows_routed`` is the
buffer's rows as the launches over the cut lay them out: every held expert's rows
padded to whole row tiles; ``moe.assignments`` counts ``rows x top-k`` choices
over ALL the router's experts, of which ``held / experts`` land on an expert this
chip holds (the others' terms are the absent chips'), so the real rows are
``assignments x held / experts``. The launches under the cut (a decode step alone:
the all-held-experts einsum, ``moe.rows_all_experts`` = rows x held) push no rows
through the buffer and their choices are taken out of the count. At 8 rows an
expert a chunk (256 rows x top-8 over 256 experts) a tile of the grouped matmul is
mostly padding: the lower, the more of the experts' time is spent on rows that
are nobody's. ``None`` for a program without the counters, or with no launch over
the cut in the window."""

from harness import counters


def moe_blocks(docs: list[dict]) -> list[dict]:
    blocks = [m.get("moe") or {} for m in docs]
    keys = ("assignments", "rows_routed", "rows_all_experts", "experts", "experts_held", "top_k")
    return blocks if blocks and all(all(k in b for k in keys) for b in blocks) else []


def read(before, after, responses, trace, cell):
    a, b = moe_blocks(after), moe_blocks(before)
    if not a or not b or not a[0]["experts"] or not a[0]["experts_held"]:
        return None
    held, experts, top_k = a[0]["experts_held"], a[0]["experts"], a[0]["top_k"]
    buffer_rows = counters.delta(b, a, "rows_routed")
    under_cut = counters.delta(b, a, "rows_all_experts") / held * top_k  # choices of the einsum's launches
    pairs = (counters.delta(b, a, "assignments") - under_cut) * held / experts
    if buffer_rows <= 0 or pairs <= 0:
        return None
    return pairs / buffer_rows
