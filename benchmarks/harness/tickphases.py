"""What the tick-phase and relay readers share (benchmarks/metrics/tick_*,
relay_*): the window's part of the accel plane's `tick` row, and the sums
over the replica's STREAMED events.

The engine times every continuous tick with a `StepTimer("tick")` whose
phases tile it (ray_tpu/llm/paged.py `_step_continuous`; README, "Tick
phases"); `step_summary()` rows are cumulative, and the harness marks them
at the window's edges (`replica.py:_mark`), so a reader takes closed −
opened. A program without the row (the parent of the PR that added it, a
killed accel plane) gives None, and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import readers


def tick_delta(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """steps, wall_s, cpu_s and seconds by phase of the ticks that ended
    between the two marks; None if the program reports no `tick` row or
    no tick ended in the window."""
    opened = readers.step_row(record["opened"], "tick")
    closed = readers.step_row(record["closed"], "tick")
    steps = closed["steps"] - opened["steps"]
    if "phases" not in closed or steps <= 0:
        return None
    before = opened.get("phases", {})
    return {"steps": steps,
            "wall_s": closed["wall_s"] - opened["wall_s"],
            "cpu_s": closed["cpu_s"] - opened.get("cpu_s", 0.0),
            "phases": {name: seconds - before.get(name, 0.0)
                       for name, seconds in closed["phases"].items()}}


def phase_ms(record: Dict[str, Any], *names: str) -> Optional[float]:
    """Mean ms a tick spent in the named phases together."""
    delta = tick_delta(record)
    if delta is None:
        return None
    return sum(delta["phases"].get(name, 0.0) for name in names) \
        / delta["steps"] * 1e3


def streamed(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Sums over the STREAMED events stamped in the window (one per
    streamed request, when its stream ended): polls answered with tokens,
    tokens they took, seconds the oldest token of each answer had lain in
    the replica. None without such an event."""
    t0, t1 = readers.window(record)
    out = {"polls": 0.0, "tokens": 0.0, "hold_sum_s": 0.0}
    seen = False
    for _rid, event, ts, args in record["report"]["events"]:
        if event == "STREAMED" and t0 <= ts < t1:
            seen = True
            for key in out:
                out[key] += args.get(key, 0)
    return out if seen and out["polls"] else None
