"""Tokens the window's decode steps selected over the cached tokens they
selected from (the engine's counters `sparse_rows_selected`, the sum of
min(context, topk), over `sparse_rows_context`): what the indexer leaves of
a dense layer's attention. 100 where no row is longer than topk."""
from benchmarks.harness import readers


def read(record):
    if "sparse_rows_context" not in record["closed"]["stats"]:
        return None
    context = readers.stat_delta(record, "sparse_rows_context")
    if not context:
        return None
    return 100.0 * readers.stat_delta(record, "sparse_rows_selected") \
        / context
