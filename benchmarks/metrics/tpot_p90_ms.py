"""Time per output token, averaged inside each request (last chunk minus
first chunk, over the tokens after the first chunk); the 90th percentile
over requests that finished in the window. A failed request is the worst."""
from benchmarks.harness import arith
from benchmarks.harness.cluster import say


def read(record):
    samples = arith.tpot_samples(record["rows"], record["t0"], record["t1"])
    say(f"bench: tpot_p90_ms over {len(samples)} finished requests")
    return arith.percentile(samples, 90)
