"""Share of the window the engine spent in slow visits beyond a usual one:
over the `tick` row's slow visits (over 4 x the running median; counted by
`slow_total` / `slow_seconds`), extent - the window's median extent, over
the seconds read. In a traced run only [window's start, trace.host_began)
(benchmarks/harness/tickstalls.py); the whole window otherwise. 0 with no
slow visit."""
from benchmarks.harness import tickstalls


def read(record):
    stalls = tickstalls.stalled(record)
    if stalls is None:
        return None
    return 100.0 * stalls["stalled_s"] / stalls["seconds"]
