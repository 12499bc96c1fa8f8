"""Host wall of one continuous engine tick (accel `tick` row: Δwall_s over
Δsteps between the window's marks), the gap between ticks excluded."""
from benchmarks.harness import tickphases


def read(record):
    delta = tickphases.tick_delta(record)
    return delta["wall_s"] / delta["steps"] * 1e3 if delta else None
