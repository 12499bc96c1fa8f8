"""Output tokens that reached the HTTP client inside the window, of
finished and unfinished requests alike, over the window."""
from benchmarks.harness import arith


def read(record):
    t0, t1 = record["t0"], record["t1"]
    return arith.tokens_in_window(record["rows"], t0, t1) / (t1 - t0)
