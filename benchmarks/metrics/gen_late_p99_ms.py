"""How late the load generator sent requests due in the window: a starved
generator must not read as a fast server."""
from benchmarks.harness import arith


def read(record):
    return arith.percentile(arith.lateness_samples(
        record["rows"], record["t0"], record["t1"]), 99)
