"""What the readers of the engine's DRY-device account share
(benchmarks/metrics/device_dry_pct, dry_dispatch_pct, dry_gap_p99_ms,
dry_prefill_pct, dry_stage_pct, dry_between_pct): a span of the accel
plane's `tick` row's `timeline` (PR 54), the row's flushes of the newest
ten minutes, each stamped on `time.monotonic()` and holding what 16 visits
summed to — seconds by phase, counters, the extents' buckets, the slow
visits whole and the seconds the device had run out of work, by the phase
of the host's visit they lay under (`dry_by_phase`, `dry_gap_hist`; the
counters `dispatches`, `dry_dispatches`, `dry_s_lower`, `dry_s_upper`).

The rows carry their own clock, so ONE mark is read (the closed one), no
sum is subtracted and no list's length matters: a closed mark taken long
after the window (a traced run's waits for the profiler's stop and the
parses) reads like any other. In a traced run the span ends where the
trace began, as `tickstalls.py`'s does: the profiler's stop stalls the
replica inside the window. A program without the ring (the parent of
PR 54, a killed accel plane) gives None, and the metric is left out of the
line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from . import readers, tickstalls

PREFILL = ("prefill",)
STAGE = ("grow", "stage", "dispatch")
BETWEEN = ("between",)


def span(record: Dict[str, Any]) -> Tuple[float, float]:
    """[the opened mark, the closed mark or the trace's start, whichever
    came first), on the replica's `time.monotonic()`."""
    begin = record["opened"].get("t", record["t0"])
    end = record["closed"].get("t", record["t1"])
    cut = (record.get("trace") or {}).get("host_began")
    return begin, end if cut is None else min(end, cut)


def rows(record: Dict[str, Any], begin: Optional[float] = None,
         end: Optional[float] = None) -> Optional[List[Dict[str, Any]]]:
    """The closed mark's `timeline` rows that ended in [begin, end)
    (default: `span`); None if the program keeps no timeline."""
    timeline = readers.step_row(record["closed"], "tick").get("timeline")
    if timeline is None:
        return None
    if begin is None:
        begin, end = span(record)
    return [row for row in timeline if begin <= row["end"] < end]


def dry(record: Dict[str, Any], begin: Optional[float] = None,
        end: Optional[float] = None) -> Optional[Dict[str, Any]]:
    """The account over those rows: `seconds` (what the rows cover: the
    visits' extents, `between` + the visit, summed), `dispatches`,
    `dry_dispatches`, `dry_s_lower`, `dry_s_upper`, `dry_s` (their mean),
    `by_phase` (seconds; they sum to `dry_s`), the gaps' `edges` and
    `counts` and the longest, `gap_max_s`. None without a timeline or
    with no row in the span."""
    found = rows(record, begin, end)
    if not found:
        return None
    edges = readers.step_row(record["closed"], "tick")[
        "dry_gap_hist"]["edges_s"]
    out: Dict[str, Any] = {
        "seconds": 0.0, "dispatches": 0.0, "dry_dispatches": 0.0,
        "dry_s_lower": 0.0, "dry_s_upper": 0.0, "by_phase": {},
        "edges": edges, "counts": [0] * (len(edges) + 1), "gap_max_s": 0.0}
    for row in found:
        out["seconds"] += row["extent_s"]
        for name in ("dispatches", "dry_dispatches", "dry_s_lower",
                     "dry_s_upper"):
            out[name] += row["counters"].get(name, 0.0)
        for phase, seconds in row["dry_by_phase"].items():
            out["by_phase"][phase] = out["by_phase"].get(phase, 0.0) \
                + seconds
        for bucket, count in row["dry_gap_hist"].items():
            out["counts"][int(bucket)] += count   # a JSON key is a string
        out["gap_max_s"] = max(out["gap_max_s"], row["dry_gap_max_s"])
    if out["seconds"] <= 0:
        return None
    out["dry_s"] = 0.5 * (out["dry_s_lower"] + out["dry_s_upper"])
    return out


def dry_pct(record: Dict[str, Any]) -> Optional[float]:
    """Percent of the span's seconds the device was dry."""
    account = dry(record)
    if account is None:
        return None
    return 100.0 * account["dry_s"] / account["seconds"]


def phase_share_pct(record: Dict[str, Any], phases: Tuple[str, ...]
                    ) -> Optional[float]:
    """Percent of the dry seconds that lay under the named phases and
    their parts (`prefill` takes `prefill/chunk` and `prefill/finish`);
    0 with nothing dry."""
    account = dry(record)
    if account is None:
        return None
    if account["dry_s"] <= 0:
        return 0.0
    under = sum(seconds for phase, seconds in account["by_phase"].items()
                if phase.split("/")[0] in phases)
    return 100.0 * under / account["dry_s"]


def gap_quantile_ms(record: Dict[str, Any], q: float) -> Optional[float]:
    """The q-quantile of one gap's length, ms; 0 with no gap."""
    account = dry(record)
    if account is None:
        return None
    seconds = tickstalls.quantile(account["edges"], account["counts"], q)
    return 0.0 if seconds is None else seconds * 1e3


def dry_seconds_in(record: Dict[str, Any], begin: float, end: float
                   ) -> Optional[float]:
    """Dry seconds in [begin, end), finer than a row (16 visits, half a
    second): a row's slow visits keep their own `end`, `extent_s` and
    `dry_s` and are laid where they were; the rest of the row's dry seconds
    are spread evenly over the row's stretch, from the row before it (or
    its own extent back). For the parity with a trace of a few seconds,
    where one long gap at a row's edge is a point of the share."""
    timeline = readers.step_row(record["closed"], "tick").get("timeline")
    if timeline is None:
        return None

    def inside(lo: float, hi: float, seconds: float) -> float:
        both = min(hi, end) - max(lo, begin)
        return seconds * both / (hi - lo) if both > 0 and hi > lo else 0.0

    total, before = 0.0, None
    for row in timeline:
        start = row["end"] - row["extent_s"]
        if before is not None and start < before < row["end"]:
            start = before
        before = row["end"]
        rest = sum(row["dry_by_phase"].values())
        for step in row["slow"]:
            rest -= step.get("dry_s", 0.0)
            total += inside(step["end"] - step["extent_s"], step["end"],
                            step.get("dry_s", 0.0))
        total += inside(start, row["end"], max(0.0, rest))
    return total


def table(account: Dict[str, Any]) -> str:
    """The account in one line for a run's stderr, phases by seconds."""
    doubt = account["dry_s_upper"] - account["dry_s_lower"]
    by_phase = ", ".join(
        f"{phase} {seconds:.4f}" for phase, seconds in sorted(
            account["by_phase"].items(), key=lambda kv: -kv[1]))
    return (f"dry {account['dry_s']:.4f} s of {account['seconds']:.3f} "
            f"({100.0 * account['dry_s'] / account['seconds']:.3f} %), "
            f"bounds {account['dry_s_lower']:.4f}-"
            f"{account['dry_s_upper']:.4f} (doubt "
            f"{100.0 * doubt / account['dry_s'] if account['dry_s'] else 0.0:.1f}"
            f" % of it), {account['dry_dispatches']:.0f} gaps in "
            f"{account['dispatches']:.0f} dispatches, longest "
            f"{account['gap_max_s'] * 1e3:.1f} ms; by phase (s): {by_phase}")


def say(record: Dict[str, Any]) -> None:
    """On stderr: the span's account and, in a traced run, the account
    over the trace's own seconds beside the trace's idle seconds (the two
    clocks' parity; `window_s` runs from the first device operation to
    the last, so it is laid from `host_began`), to a visit
    (`dry_seconds_in`) and by whole rows."""
    from . import cluster
    account = dry(record)
    if account is None:
        return
    cluster.say("bench: dry account, window: " + table(account))
    trace = readers.trace_of(record)
    if trace and "host_began" in trace:
        began, seconds = trace["host_began"], trace["window_s"]
        found = dry_seconds_in(record, began, began + seconds)
        cluster.say(
            f"bench: dry account, the trace's {seconds:.3f} s from "
            f"host_began: dry {found:.4f} s ({100.0 * found / seconds:.3f}"
            f" %) beside the trace's own idle {seconds - trace['busy_s']:.4f}"
            f" s ({100.0 * (1 - trace['busy_s'] / seconds):.3f} %)")
        lead = dry_seconds_in(record, began - 0.25, began)
        cluster.say(f"bench: dry account, the 0.25 s before host_began "
                    f"(the profiler's start): dry {lead:.4f} s")
        traced = dry(record, began, began + seconds)
        if traced is not None:
            cluster.say("bench: dry account, the rows that ended in it: "
                        + table(traced))
