"""Share of visits that dispatched a decode step before reading the last
one's tokens: Δ`lookahead_ticks` / Δ`steps` of the accel `tick` row (PR 33's
counter). 100 where every visit finds rows to decode; lower where the batch
empties (a drain) or a visit only prefills. In a traced run without the slow
visits that ended at or after trace.host_began
(benchmarks/harness/tickstalls.py)."""
from benchmarks.harness import tickstalls


def read(record):
    window = tickstalls.visits(record)
    if window is None:
        return None
    return 100.0 * window["counters"].get("lookahead_ticks", 0.0) \
        / window["steps"]
