"""From the instant a request was due by the arrival schedule to its first
token at the client; 90th percentile over requests due in the window. A
failed request, or one still without a token when the books close, is the
worst. Per-layer for now: over the 65 requests of a window it lies between
two of them and steps by a decode tick (7 %); within a set of six runs of
one schedule it spread 7-10 % (PERF.md section 6)."""
from benchmarks.harness import arith
from benchmarks.harness.cluster import say


def read(record):
    samples = arith.ttft_samples(record["rows"], record["t0"], record["t1"])
    say(f"bench: ttft_p90_ms.open over {len(samples)} requests due in the window")
    return arith.percentile(samples, 90)
