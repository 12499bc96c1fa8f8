"""Of the stalled time (tick_stall_pct's numerator, over the visits the
`slow` list still holds), the share under no stamped pause
(`slow[*].pauses`: collector, metrics flush and its encode, compile,
counter fetch, drain). In a traced run only [window's start,
trace.host_began) (benchmarks/harness/tickstalls.py); the whole window
otherwise. 0 with no slow visit: nothing stalled, nothing to explain."""
from benchmarks.harness import tickstalls


def read(record):
    stalls = tickstalls.stalled(record)
    if stalls is None:
        return None
    return 100.0 * stalls["unexplained"]
