"""Of the prefill chunks the engine dispatched between the marks, the share
whose bucket put the routed experts on the sorted form (the `tick` row's
counters `prefill_chunks_sorted` / `prefill_chunks`, counted on the host
with `moe.sorted_form`, the predicate the program's trace takes): whether
the grouped product is on the path the cell's traffic takes. A counter that
stayed 0 is not on the row: 0 then."""
from benchmarks.harness import readers


def read(record):
    opened = readers.step_row(record["opened"], "tick").get("counters", {})
    closed = readers.step_row(record["closed"], "tick").get("counters", {})
    chunks = closed.get("prefill_chunks", 0) \
        - opened.get("prefill_chunks", 0)
    if chunks <= 0:
        return None
    return 100.0 * (closed.get("prefill_chunks_sorted", 0)
                    - opened.get("prefill_chunks_sorted", 0)) / chunks
