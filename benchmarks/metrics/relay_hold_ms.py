"""Mean ms the oldest token of a stream_next answer had lain in the replica
(reqtrace STREAMED: Σhold_sum_s / Σpolls over streams that ended in the
window): the loop hop from the engine's thread plus the wait for the
proxy's next long-poll — the replica's half of the relay lag."""
from benchmarks.harness import tickphases


def read(record):
    sums = tickphases.streamed(record)
    return sums["hold_sum_s"] / sums["polls"] * 1e3 if sums else None
