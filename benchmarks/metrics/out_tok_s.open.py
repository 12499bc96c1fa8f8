"""Open loop: output tokens at the client over the window. Equals the
offered load while the backlog does not grow."""
from benchmarks.harness import arith


def read(record):
    t0, t1 = record["t0"], record["t1"]
    return arith.tokens_in_window(record["rows"], t0, t1) / (t1 - t0)
