"""kernels: of the K/V rows the sparse layers' query rows could see over the
window, the share they read (``attention.sparse.rows_read`` over ``rows_live``
of the engines' ``/metrics``, both counted on the host at each launch from the
rows' positions, a layer counted once: a query at position p under
``dense_len`` reads its p + 1 rows, one past it the rows of its ``topk`` chosen
blocks). 1 while every context is under ``dense_len``; about 4,096 over the
mean context from there on. ``None`` for a program without sparse layers."""


def sparse_counters(docs: list[dict]) -> list[dict]:
    """The ``attention.sparse`` blocks of the engines' documents (all or none)."""
    blocks = [(m.get("attention") or {}).get("sparse") for m in docs]
    return blocks if blocks and all(isinstance(b, dict) and "rows_live" in b for b in blocks) else []


def delta(before: list[dict], after: list[dict], key: str) -> float:
    return float(sum(b[key] for b in sparse_counters(after)) - sum(b[key] for b in sparse_counters(before)))


def read(before, after, responses, trace, cell):
    if not sparse_counters(after) or not sparse_counters(before):
        return None
    live = delta(before, after, "rows_live")
    return delta(before, after, "rows_read") / live if live > 0 else None
