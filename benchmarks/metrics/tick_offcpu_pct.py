"""Share of a tick's host time in which the stepping thread was not running:
(host wall − cpu_s) / host wall, host wall = wall_s − `wait` (`between` is
left out: the thread is parked there by design). Under 20 % the host part
is Python work; over 40 % it is waiting, for the GIL or for I/O."""
from benchmarks.harness import tickphases


def read(record):
    delta = tickphases.tick_delta(record)
    if delta is None:
        return None
    host = delta["wall_s"] - delta["phases"].get("wait", 0.0)
    # cpu_s covers the whole tick, whatever the thread burns inside `wait`
    # included, so it can exceed the host part: the share is then 0
    return 100.0 * max(0.0, host - delta["cpu_s"]) / host if host > 0 \
        else None
