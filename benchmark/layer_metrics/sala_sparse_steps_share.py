"""model runner: of the query rows the sparse layers took over the window
(prefill rows and lanes' decode steps alike), the share past ``dense_len``,
which chose their blocks (``attention.sparse.steps_sparse`` over
``steps_sparse + steps_dense``). The cell is built so that this reads 1: a
reading under 0.99 says documents were prefilled inside the window. ``None``
for a program without sparse layers."""

from layer_metrics.sala_sparse_rows_read_share import delta, sparse_counters


def read(before, after, responses, trace, cell):
    if not sparse_counters(after) or not sparse_counters(before):
        return None
    sparse, dense = delta(before, after, "steps_sparse"), delta(before, after, "steps_dense")
    return sparse / (sparse + dense) if sparse + dense > 0 else None
