"""Device time of one chunk_prefill call, median, from the trace."""
from benchmarks.harness import readers


def read(record):
    row = readers.program(record, "chunk_prefill")
    return row["median_ms"] if row else None
