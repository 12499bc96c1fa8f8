"""Pooled gaps between token-bearing chunks at the client, per token; 99th
percentile. The single-gap tail PR 22 tried to bound: a percentile of
jitter, kept visible, deciding nothing."""
from benchmarks.harness import arith


def read(record):
    return arith.percentile(arith.gap_samples(
        record["rows"], record["t0"], record["t1"]), 99)
