"""Closed loop: send to first token, median. Queueing by construction (64
callers on 32 rows), so it decides nothing here."""
from benchmarks.harness import arith


def read(record):
    return arith.percentile(arith.ttft_samples(
        record["rows"], record["t0"], record["t1"]), 50)
