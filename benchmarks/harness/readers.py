"""What several metric readers share: picking the window out of a run's
record. A reader is `read(record) -> number or None`; None (nothing to
read) leaves the metric out of the line."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from . import peaks


def window(record: Dict[str, Any]):
    return record["t0"], record["t1"]


def events_by_request(record: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """request id -> {event: first timestamp} from the replica's reqtrace."""
    out: Dict[str, Dict[str, float]] = {}
    for rid, event, ts, _args in record["report"]["events"]:
        out.setdefault(rid, {}).setdefault(event, ts)
    return out


def span_samples(record: Dict[str, Any], first: str, second: str
                 ) -> List[float]:
    """ms from event `first` to event `second`, for requests whose `first`
    fell in the window. `first` may be "sent": the client's own stamp."""
    t0, t1 = window(record)
    events = events_by_request(record)
    out = []
    for row in record["rows"]:
        stamps = dict(events.get(row["id"], {}))
        stamps["sent"] = row["sent"]
        a, b = stamps.get(first), stamps.get(second)
        if a is not None and b is not None and t0 <= a < t1:
            out.append((b - a) * 1e3)
    return out


def step_row(mark: Dict[str, Any], kind: str) -> Dict[str, float]:
    for row in mark["steps"]:
        if row["kind"] == kind:
            return row
    return {"steps": 0, "wall_s": 0.0, "tokens": 0}


def stat_delta(record: Dict[str, Any], key: str) -> float:
    return record["closed"]["stats"][key] - record["opened"]["stats"][key]


def compiles_in_window(record: Dict[str, Any]) -> float:
    """Compiles the process that holds the chip counted between the
    window's edges (accel plane's compile summary); must be 0."""
    return float(record["compiles_in_window"])


def trace_of(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    trace = record.get("trace")
    return trace if trace and trace.get("window_s") else None


def program(record: Dict[str, Any], name: str) -> Optional[Dict[str, Any]]:
    trace = trace_of(record)
    if not trace:
        return None
    for key, row in trace["programs"].items():
        if key == name or key.endswith(name):
            return row
    return None


def ops_matching(record: Dict[str, Any], *needles: str) -> Dict[str, Any]:
    """Calls and seconds of the device operations whose name holds any of
    the needles."""
    trace = trace_of(record)
    calls, seconds = 0, 0.0
    if trace:
        for key, row in trace["ops"].items():
            if any(n in key for n in needles):
                calls += row["calls"]
                seconds += row["total_s"]
    return {"calls": calls, "total_s": seconds}


def idle_pct(record: Dict[str, Any]) -> Optional[float]:
    trace = trace_of(record)
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def memory_peak_bytes(record: Dict[str, Any]) -> Optional[int]:
    """Peak on the fullest chip, read after the window in the process that
    holds the chips (the CPU backend reports none). `peak_bytes_in_use`
    counts arrays only; what a running program holds for its temporaries
    is `peak_bytes_reserved` (my chip probe, PR 23: a jit with 1.342 GB of
    temporaries by memory_analysis() left peak_bytes_reserved at 1.342 GB
    and peak_bytes_in_use at its 0.138 GB of arguments). The two peaks
    need not fall together, so their sum is an upper bound of the chip's
    peak: exact where the state is live while the largest program runs
    (the train step: 10.94 GB read against 10.81 GB by memory_analysis()),
    high by at most the temporaries (~0.1 GB) where the most arrays are
    live at another moment (serving: dense prefill caches)."""
    memory = record["memory"] if "memory" in record \
        else record["report"]["memory"]
    peaks_ = [m["peak_bytes_in_use"] + m.get("peak_bytes_reserved", 0)
              for m in memory if m and "peak_bytes_in_use" in m]
    return max(peaks_) if peaks_ else None


def hbm_peak_gib(record: Dict[str, Any]) -> Optional[float]:
    peak = memory_peak_bytes(record)
    return peak / 2 ** 30 if peak is not None else None


def device_peaks(record: Dict[str, Any]) -> Dict[str, float]:
    return peaks.peaks(record["device"]["kind"])

