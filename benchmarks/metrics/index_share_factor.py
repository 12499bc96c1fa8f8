"""Index pages the decoding rows hold a step counted a row, over the same
pages counted once, over the decode steps between the marks (the `tick`
row's counters `index_pages_rowwise` / `index_pages_distinct`): what a
scoring kernel that copies a shared document's keys once for all the rows on
it would spare. 1.0 where no two rows share a page."""
from benchmarks.harness import readers


def read(record):
    opened = readers.step_row(record["opened"], "tick").get("counters", {})
    closed = readers.step_row(record["closed"], "tick").get("counters", {})
    distinct = closed.get("index_pages_distinct", 0) \
        - opened.get("index_pages_distinct", 0)
    if distinct <= 0:
        return None
    return (closed["index_pages_rowwise"]
            - opened.get("index_pages_rowwise", 0)) / distinct
