"""Share of `stage` + `dispatch` in which the stepping thread did not run:
(wall − CPU) / wall from the `tick` row's `phases` and `phases_cpu`
(`time.thread_time()` inside each phase). `stage` is numpy and Python only,
so its share is the GIL; `dispatch`'s is the GIL or an upload that blocks.
In a traced run without the slow visits that ended at or after
trace.host_began (benchmarks/harness/tickstalls.py)."""
from benchmarks.harness import tickstalls

PHASES = ("stage", "dispatch")


def read(record):
    window = tickstalls.visits(record)
    if window is None or not window["phases_cpu"]:
        return None
    wall = sum(window["phases"].get(name, 0.0) for name in PHASES)
    cpu = sum(window["phases_cpu"].get(name, 0.0) for name in PHASES)
    return 100.0 * max(0.0, wall - cpu) / wall if wall > 0 else None
