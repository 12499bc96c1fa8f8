"""Tokens per stream_next answer that carried any (reqtrace STREAMED: Σtokens
/ Σpolls); above 1 the poll came later than the tick that made the token."""
from benchmarks.harness import tickphases


def read(record):
    sums = tickphases.streamed(record)
    return sums["tokens"] / sums["polls"] if sums else None
