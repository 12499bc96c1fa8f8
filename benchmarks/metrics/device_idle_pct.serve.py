"""1 - union of the device's operation intervals over the traced window."""
from benchmarks.harness import readers


def read(record):
    return readers.idle_pct(record)
