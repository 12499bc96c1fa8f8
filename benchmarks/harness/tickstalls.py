"""What the readers of the tick's TAIL share (benchmarks/metrics/
tick_p99_ms, tick_stall_pct, tick_stall_unexplained_pct,
tick_stage_offcpu_pct, lookahead_pct, prefill_finish_ms): the window's part
of what the accel plane's `tick` row keeps beside its sums since PR 39 — `extent_hist` (visits
by extent; a visit's extent is `between` + the visit), `slow` (the visits
over four times the running median, whole, each with the stamped pauses
that overlap it), `phases_cpu` and the counters.

`step_summary()` rows are cumulative and the harness marks them at the
window's edges (`replica.py::_mark`), so a reader takes closed − opened. **In
a traced run** the profiler's stop stalls the replica for seconds inside the
window (PERF.md §7), so these readers read only the visits that ended before
the trace began, `[opened, trace.host_began)`: the sums and the histogram
have no clock, so the `slow` visits that ended at or after `host_began` (the
stall is among them) are first taken out of the bucket, the sums and the
counters they are in; the stalled seconds are read over that shorter span.
Without a trace they read the whole window.

A program without the fields (the parent of PR 39, a killed accel plane)
gives None, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional

from . import readers

PHASE_KEYS = ("phases", "phases_cpu", "counters")


def quantile(edges: List[float], counts: List[float], q: float
             ) -> Optional[float]:
    """Seconds under which the share q of the visits lie: interpolated in
    the bucket on the logarithmic scale of the edges (four buckets a
    doubling); in the under- or overflow bucket, the edge beside it."""
    total = sum(counts)
    if total <= 0:
        return None
    rank, seen = q * total, 0.0
    for bucket, count in enumerate(counts):
        if count > 0 and seen + count >= rank:
            if bucket == 0:
                return edges[0]
            if bucket == len(edges):
                return edges[-1]
            lo, hi = edges[bucket - 1], edges[bucket]
            return lo * (hi / lo) ** (max(0.0, rank - seen) / count)
        seen += count
    return edges[-1]


def visits(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The visits these readers read (module docstring): `seconds` of
    the span, `steps`, `edges` and `counts` of their extents, `median`
    extent, seconds by phase (`phases`, `phases_cpu`), `counters`, the
    `slow` visits among them that the list still holds, and the count and
    seconds of all of them (`slow_steps`, `slow_seconds`). None if the
    program has no such row or no visit ended in the span."""
    opened = readers.step_row(record["opened"], "tick")
    closed = readers.step_row(record["closed"], "tick")
    if "extent_hist" not in closed:
        return None
    begin = record["opened"].get("t", record["t0"])
    end = record["closed"].get("t", record["t1"])
    cut = (record.get("trace") or {}).get("host_began")
    in_window = [s for s in closed["slow"] if begin <= s["end"] < end]
    late = [s for s in in_window if cut is not None and s["end"] >= cut]
    kept = [s for s in in_window if cut is None or s["end"] < cut]
    edges = closed["extent_hist"]["edges_s"]
    before = opened.get("extent_hist", {}).get("counts")
    counts = [b - a for a, b in zip(before or [0] * len(edges) + [0],
                                    closed["extent_hist"]["counts"])]
    out: Dict[str, Any] = {
        "seconds": (end if cut is None else cut) - begin,
        "steps": closed["steps"] - opened["steps"] - len(late),
        "edges": edges, "counts": counts}
    for key in PHASE_KEYS:
        was = opened.get(key, {})
        out[key] = {name: value - was.get(name, 0.0)
                    for name, value in closed.get(key, {}).items()}
    for step in late:
        bucket = bisect.bisect_left(edges, step["extent_s"])
        counts[bucket] = max(0, counts[bucket] - 1)
        for key in PHASE_KEYS:
            for name, value in step[key].items():
                out[key][name] = out[key].get(name, 0.0) - value
    if out["steps"] <= 0 or out["seconds"] <= 0:
        return None
    out["median"] = quantile(edges, counts, 0.5)
    # `slow` keeps the newest 64: what a long window dropped are its oldest.
    # The sums count every slow visit, so what stalled is read from them,
    # less the late visits, which the list must then hold in full.
    dropped = closed["slow_total"] - opened.get("slow_total", 0) \
        - len(in_window)
    if dropped > 0 and cut is not None and not kept:
        return None   # the list may have dropped late visits too
    out["slow"] = kept
    out["slow_steps"] = len(kept) + dropped
    out["slow_seconds"] = closed["slow_seconds"] \
        - opened.get("slow_seconds", 0.0) \
        - sum(step["extent_s"] for step in late)
    return out


def stalled(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Over the slow visits read: `stalled_s`, what they took beyond the
    median visit (from the row's `slow_seconds` and `slow_total`, which
    count every slow visit); `unexplained`, of the stalled time of those
    the `slow` list still holds, the share under no stamped pause (the
    union of a visit's overlaps, no more than its stalled time; 0 with
    nothing stalled); `seconds`, the span read."""
    window = visits(record)
    if window is None:
        return None
    total = explained = 0.0
    for step in window["slow"]:
        over = max(0.0, step["extent_s"] - window["median"])
        total += over
        explained += min(over, union_s(
            [(p["t0"], p["t1"]) for p in step["pauses"]]))
    return {"stalled_s": max(0.0, window["slow_seconds"]
                             - window["slow_steps"] * window["median"]),
            "unexplained": 1.0 - explained / total if total > 0 else 0.0,
            "seconds": window["seconds"]}


def union_s(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            total += hi - lo
            reach = hi
        elif hi > reach:
            total += hi - reach
            reach = hi
    return total
