"""99th percentile of ONE dry gap's length (from the moment the device ran
out of work, the mean of its two bounds, to the dispatch that ended it) from
the `tick` row's `dry_gap_hist` over the span read: four buckets a doubling
from 0.09 ms, interpolated (benchmarks/harness/ticktimeline.py). What a
token's gap feels of a dry chip; 0 with no gap."""
from benchmarks.harness import ticktimeline


def read(record):
    return ticktimeline.gap_quantile_ms(record, 0.99)
