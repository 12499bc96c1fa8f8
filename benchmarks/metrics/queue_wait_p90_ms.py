"""reqtrace QUEUED -> ADMITTED, 90th percentile over requests queued in
the window: the wait for a free row and free pages."""
from benchmarks.harness import arith, readers


def read(record):
    return arith.percentile(
        readers.span_samples(record, "QUEUED", "ADMITTED"), 90)
