"""Device time of one compress_window call (a row's full window of every
layer pooled into its summaries), median, from the trace. Its floor by
bytes is costs_evabyte.compress_bytes over the HBM bandwidth: 136 pages of
2 MiB at the published widths and 8 layers, 0.35 ms."""
from benchmarks.harness import readers


def read(record):
    row = readers.program(record, "compress_window")
    return row["median_ms"] if row else None
