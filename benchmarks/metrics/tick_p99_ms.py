"""99th percentile of a visit's extent (`between` + the visit) from the accel
`tick` row's Δ`extent_hist`: four buckets a doubling, interpolated. In a
traced run only the visits that ended before the trace began
(benchmarks/harness/tickstalls.py); the whole window otherwise."""
from benchmarks.harness import tickstalls


def read(record):
    window = tickstalls.visits(record)
    if window is None:
        return None
    return tickstalls.quantile(
        window["edges"], window["counts"], 0.99) * 1e3
