"""cache manager: the mean rows of context a query row of the window had (its
lane's live rows: ``attention.sparse.rows_live`` over the query rows counted,
prefill rows and decode steps alike; a layer counted once). What a sparse
layer would read of a lane if it read every row; ``sala_sparse_rows_read_share``
times this is what it reads. ``None`` for a program without sparse layers."""

from layer_metrics.sala_sparse_rows_read_share import delta, sparse_counters


def read(before, after, responses, trace, cell):
    if not sparse_counters(after) or not sparse_counters(before):
        return None
    rows = delta(before, after, "steps_sparse") + delta(before, after, "steps_dense")
    return delta(before, after, "rows_live") / rows if rows > 0 else None
