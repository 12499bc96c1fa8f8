"""Client's send to the engine's QUEUED event (reqtrace), median: HTTP
accept and parse, the router's choice, and the RPC into the replica. Both
stamps are CLOCK_MONOTONIC on one host."""
from benchmarks.harness import arith, readers


def read(record):
    return arith.percentile(
        readers.span_samples(record, "sent", "QUEUED"), 50)
