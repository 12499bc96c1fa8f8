"""Admissions in the window that found a cached prefix in the radix, over
all admissions that could have (the engine's counters)."""
from benchmarks.harness import readers


def read(record):
    hits = readers.stat_delta(record, "prefix_hits")
    misses = readers.stat_delta(record, "prefix_misses")
    return 100.0 * hits / (hits + misses) if hits + misses else None
