"""Collective time during which no compute ran on that device, over the
traced window, mean over the devices."""
from benchmarks.harness import readers


def read(record):
    trace = readers.trace_of(record)
    if not trace:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
