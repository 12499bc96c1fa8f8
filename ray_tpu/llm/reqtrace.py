"""Per-request lifecycle tracing through the serve plane (the
serve-plane request observatory's recording layer — the twin of the
train-plane ``train/steptrace.py`` flight deck).

Every serving process (proxy, replica/engine) stamps bounded
per-request lifecycle events on the host-shared ``time.monotonic()``
clock into a per-process ring:

    QUEUED -> ADMITTED -> PREFILL_CHUNK* -> DECODE
           -> PREEMPTED/PARKED -> RESUMED -> ...
           -> FINISHED | CANCELLED | FAILED

plus ROUTED (proxy-side replica choice), COMPILE (XLA compile stall
attributed to every request whose wall clock contained it, via the
accel-plane compile-seconds tracker delta) and STREAMED (one per
streamed request, when the replica's stream ends: polls, tokens and how
long tokens lay in the replica before a poll took them). Events carry the request id
the proxy accepts or generates (``X-RTPU-Request-Id``, echoed back on
ndjson/SSE streams) and the optional tenant/route labels threaded down
through router -> replica -> engine.

Rings flush piggyback on the metrics flusher into the GCS KV
(ns ``reqtrace``, the steptrace pattern); the driver folds every
process's events into:

- a chrome-trace serve timeline (``state.serve_timeline()`` /
  ``cli timeline --serve`` / the dashboard Serve tab) — one row per
  request, spans for queue/prefill/park/decode with chunk and compile
  spans nested inside;
- ``why_slow(request_id)`` — TTFT and e2e latency decomposed into
  queue / prefill-compute / park / decode / XLA-compile / other
  buckets;
- per-tenant / per-route percentile folds (``cli requests
  --by-tenant``).

Kill switch: ``RTPU_NO_REQTRACE=1`` — ``record()`` degrades to one
flag check, no ring is ever constructed, nothing is flushed;
exact-legacy behavior.

This module is import-light on purpose (stdlib + config only): the
proxy and the dashboard fold requests without pulling jax.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from .._internal.config import CONFIG

logger = logging.getLogger(__name__)

REQTRACE_KV_NS = "reqtrace"

# lifecycle event names (the engine's request state machine)
QUEUED = "QUEUED"
ROUTED = "ROUTED"
ADMITTED = "ADMITTED"
PREFILL_CHUNK = "PREFILL_CHUNK"
DECODE = "DECODE"
PREEMPTED = "PREEMPTED"
PARKED = "PARKED"
RESUMED = "RESUMED"
FINISHED = "FINISHED"
CANCELLED = "CANCELLED"
FAILED = "FAILED"
COMPILE = "COMPILE"
# replica-side account of a streamed request's token hand-off, stamped
# on the replica's loop when its stream ends (around the engine's
# terminal event, on either side); the folds below leave it out of the
# request's extent
STREAMED = "STREAMED"
# a row of a model that generates by diffusion over blocks (paged.py,
# `_blockwise`): the `what` of a request's FIRST block (open, done, commit:
# every block's marks would overrun the ring in seconds) and of its last
# (`blocks`: how many it took); inside the request's decode span, and left
# an instant on the serve timeline
BLOCK = "BLOCK"

TERMINAL = frozenset({FINISHED, CANCELLED, FAILED})

REQUEST_ID_HEADER = "x-rtpu-request-id"
TENANT_HEADER = "x-rtpu-tenant"
ROUTE_HEADER = "x-rtpu-route"


def reqtrace_disabled() -> bool:
    return bool(CONFIG.no_reqtrace)


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------


class _Recorder:
    """Bounded per-process lifecycle-event ring. An event is
    ``(request_id, event, ts, args)`` on the shared monotonic clock;
    overflow drops the oldest — steady-state serving keeps the tail."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: deque = deque(
            maxlen=int(CONFIG.reqtrace_max_events))

    def record(self, request_id: str, event: str, ts: float,
               args: Dict[str, Any]):
        with self._lock:
            self._events.append((request_id, event, float(ts), args))

    def events(self) -> List[tuple]:
        with self._lock:
            return list(self._events)

    def payload(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "pid": os.getpid(),
                "events": [[r, e, t, a] for r, e, t, a in self._events],
            }

    def clear(self):
        with self._lock:
            self._events.clear()


# Lazy singleton: under the kill switch record() returns before ever
# touching this, so a disabled process holds ZERO rings (what the
# kill-switch subprocess test asserts).
_RECORDER: Optional[_Recorder] = None
_recorder_lock = threading.Lock()


def _recorder() -> _Recorder:
    global _RECORDER
    if _RECORDER is None:
        with _recorder_lock:
            if _RECORDER is None:
                _RECORDER = _Recorder()
    return _RECORDER


def record(request_id: Optional[str], event: str, **args) -> None:
    """Stamp one lifecycle event (shared monotonic clock). Args must be
    JSON-serializable scalars; None values are dropped. One flag check
    and nothing else under the kill switch."""
    if reqtrace_disabled() or not request_id:
        return
    _recorder().record(
        str(request_id), event, time.monotonic(),
        {k: v for k, v in args.items() if v is not None})


def events() -> List[tuple]:
    """This process's recorded events (empty if the ring was never
    constructed)."""
    if _RECORDER is None:
        return []
    return _RECORDER.events()


def clear():
    if _RECORDER is not None:
        _RECORDER.clear()


# ---------------------------------------------------------------------------
# flush / collect (the steptrace GCS-KV pattern)
# ---------------------------------------------------------------------------


# events encoded per hold of the GIL by `_payload_json`
_FLUSH_SLICE = 128


def _payload_json(events: List[tuple]) -> bytes:
    """`json.dumps(payload)` of these events, encoded a slice at a time
    with a real sleep between slices. The encoder holds the GIL for the
    whole of one call; a full ring is over a megabyte and tens of
    milliseconds, and this runs on the flusher's thread of a replica
    whose engine thread would stand still for all of it (an engine tick
    is 20-30 ms)."""
    import json
    parts = []
    for at in range(0, len(events), _FLUSH_SLICE):
        parts.append(json.dumps(events[at:at + _FLUSH_SLICE])[1:-1])
        time.sleep(0.0005)  # long enough that a waiting thread takes over
    return ('{"pid": %d, "events": [%s]}'
            % (os.getpid(), ", ".join(parts))).encode()


def flush(gcs=None, key: Optional[str] = None) -> bool:
    """Push this process's event ring into the GCS KV (ns ``reqtrace``)
    under a per-process key. Called piggyback from the metrics flusher
    (util/metrics.flush_now); best-effort, returns False when disabled,
    empty, or no GCS is reachable."""
    if reqtrace_disabled() or _RECORDER is None:
        return False
    try:
        if gcs is None:
            from .._internal.core_worker import try_get_core_worker
            worker = try_get_core_worker()
            if worker is None:
                return False
            gcs = worker.gcs
        if key is None:
            key = str(os.getpid())
        from .._internal import accel
        with accel.pause("reqtrace_encode"):
            payload = _payload_json(_RECORDER.events())
        gcs.put(REQTRACE_KV_NS, key, payload)
        return True
    except Exception:  # noqa: BLE001 — observability is best-effort
        logger.debug("reqtrace flush failed", exc_info=True)
        return False


def collect(gcs) -> List[Dict[str, Any]]:
    """Every process's flushed payload from the GCS KV (driver side)."""
    import json
    out = []
    for key in gcs.keys(REQTRACE_KV_NS, ""):
        raw = gcs.get(REQTRACE_KV_NS, key)
        if raw:
            try:
                out.append(json.loads(raw.decode()))
            except ValueError:
                pass
    return out


# ---------------------------------------------------------------------------
# folds: per-request lifecycle -> spans / buckets / percentiles
# ---------------------------------------------------------------------------


def request_events(payloads: List[Dict[str, Any]]
                   ) -> Dict[str, List[Dict[str, Any]]]:
    """request id -> time-ordered event dicts (cross-process merge: a
    request's ROUTED event comes from the proxy's ring, the rest from
    the engine's)."""
    out: Dict[str, List[Dict[str, Any]]] = {}
    for payload in payloads:
        pid = payload.get("pid")
        for row in payload.get("events", []):
            rid, event, ts, args = row
            out.setdefault(str(rid), []).append(
                {"event": event, "ts": float(ts), "pid": pid,
                 "args": args or {}})
    for rows in out.values():
        rows.sort(key=lambda r: r["ts"])
    return out


def _clip(t0: float, t1: float, hi: Optional[float]) -> float:
    """Length of [t0, t1] clipped to end at hi (None = no clip)."""
    if hi is not None:
        t1 = min(t1, hi)
    return max(0.0, t1 - t0)


def _buckets(rows: List[Dict[str, Any]], end: float,
             hi: Optional[float] = None) -> Dict[str, float]:
    """Decompose one request's wall clock over [QUEUED, min(end, hi)]
    into queue / prefill_compute / park / decode / compile / other.
    ``hi=first_token_ts`` gives the TTFT decomposition; ``hi=None`` the
    e2e one. ``prefill_compute`` sums PREFILL_CHUNK ``dur_s``: host
    wall of the chunk's call, which is its launch (no chunk waits for
    the device; its device time is ``chunk_prefill`` in a profiler
    trace). Invariant: buckets sum to the clipped wall clock (other
    absorbs scheduler gaps between prefill chunks and unmatched
    intervals)."""
    out = {"queue": 0.0, "prefill_compute": 0.0, "park": 0.0,
           "decode": 0.0, "compile": 0.0, "other": 0.0}
    queued_ts = rows[0]["ts"]
    state = "queue"          # queue | park | prefill | decode
    state_t0 = queued_ts
    window_total = 0.0       # prefill-window time (ADMITTED -> DECODE)
    decode_compile = 0.0     # COMPILE stalls: inside the decode spans

    def close(until: float):
        nonlocal window_total
        span = _clip(state_t0, until, hi)
        if state == "queue":
            out["queue"] += span
        elif state == "park":
            out["park"] += span
        elif state == "decode":
            out["decode"] += span
        elif state == "prefill":
            window_total += span

    for row in rows:
        event, ts = row["event"], row["ts"]
        args = row["args"]
        if event in (ADMITTED,):
            close(ts)
            state, state_t0 = "prefill", ts
        elif event == PARKED:
            close(ts)
            state, state_t0 = "park", ts
        elif event == DECODE:
            close(ts)
            state, state_t0 = "decode", ts
        elif event in TERMINAL:
            close(ts)
            state, state_t0 = "done", ts
        elif event == PREFILL_CHUNK:
            dur = float(args.get("dur_s", 0.0))
            comp = float(args.get("compile_s", 0.0))
            # clip chunk work to the window: a chunk straddling hi
            # charges only its pre-hi share
            t0 = ts - dur
            frac = _clip(t0, ts, hi) / dur if dur > 0 else 0.0
            out["prefill_compute"] += max(0.0, (dur - comp)) * frac
            out["compile"] += comp * frac
        elif event == COMPILE:
            dur = float(args.get("compile_s", 0.0))
            t0 = ts - dur
            decode_compile += _clip(t0, ts, hi)
    if state not in ("done",):
        close(end)
    # prefill-window time not spent computing or compiling is scheduler
    # interleave (decode ticks of OTHER requests sharing the engine)
    out["other"] += max(
        0.0, window_total - out["prefill_compute"] - out["compile"])
    # decode-phase compile stalls sit inside the decode span, which is
    # whole only now (the event precedes the span's close)
    decode_compile = min(decode_compile, out["decode"])
    out["decode"] -= decode_compile
    out["compile"] += decode_compile
    for k in out:
        out[k] = round(out[k], 6)
    return out


def lifecycle(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold one request's ordered events into the report both the
    timeline and ``why_slow`` build on."""
    # Anchor at the EARLIEST observed event, not the engine's QUEUED:
    # when the proxy's ROUTED precedes it, the routing gap is real
    # client-perceived latency and must land in the queue bucket —
    # otherwise the bucket sums drift from ttft_s/e2e_s by that gap.
    # STREAMED is stamped when the replica's stream ends, which can be
    # after the terminal event: it is not part of the request's extent
    rows = [r for r in rows if r["event"] != STREAMED] or rows
    queued_ts = rows[0]["ts"]
    labels = {}
    outcome = None
    end_ts = rows[-1]["ts"]
    first_token_ts = None
    preemptions = 0
    prefill_tokens = 0
    shared_pages = 0
    for row in rows:
        event, args = row["event"], row["args"]
        if event == QUEUED:
            for k in ("tenant", "route"):
                if args.get(k):
                    labels[k] = args[k]
        elif event == ROUTED and args.get("route") and \
                "route" not in labels:
            labels["route"] = args["route"]
        elif event == DECODE and first_token_ts is None:
            first_token_ts = row["ts"]
        elif event == PREEMPTED:
            preemptions += 1
        elif event == PREFILL_CHUNK:
            prefill_tokens += int(args.get("tokens", 0))
        elif event == ADMITTED:
            shared_pages = max(shared_pages,
                               int(args.get("shared_pages", 0)))
        if event in TERMINAL:
            outcome = event
            end_ts = row["ts"]
    report: Dict[str, Any] = {
        "queued_ts": queued_ts,
        "end_ts": end_ts,
        "outcome": outcome,
        "tenant": labels.get("tenant"),
        "route": labels.get("route"),
        "preemptions": preemptions,
        "prefill_tokens": prefill_tokens,
        "shared_pages": shared_pages,
        "e2e_s": round(end_ts - queued_ts, 6),
        "e2e_buckets": _buckets(rows, end_ts),
    }
    if first_token_ts is not None:
        report["ttft_s"] = round(first_token_ts - queued_ts, 6)
        report["ttft_buckets"] = _buckets(rows, end_ts,
                                          hi=first_token_ts)
    return report


def to_chrome_trace(payloads: List[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
    """The serve timeline: chrome-trace rows (ph:"X", ts/dur in µs on
    the shared monotonic clock), pid = "serve", one tid per request id
    — queue/park/prefill/decode state spans with prefill-chunk and
    compile spans nested by time containment, PREEMPTED/ROUTED as
    instant events."""
    rows: List[Dict[str, Any]] = []
    for rid, evs in sorted(request_events(payloads).items()):
        state = None
        state_t0 = None
        state_args: Dict[str, Any] = {}

        def emit(name, t0, t1, args=None):
            rows.append({
                "name": name, "cat": "reqtrace", "ph": "X",
                "ts": t0 * 1e6, "dur": max(0.0, t1 - t0) * 1e6,
                "pid": "serve", "tid": rid,
                "args": dict(args or {}, request=rid),
            })

        for row in evs:
            event, ts, args = row["event"], row["ts"], row["args"]
            transition = {QUEUED: "queue", ADMITTED: "prefill",
                          PARKED: "park", DECODE: "decode"}.get(event)
            if transition is not None or event in TERMINAL:
                if state is not None:
                    emit(state, state_t0, ts, state_args)
                state = transition  # None on terminal
                state_t0 = ts
                state_args = args
            if event == PREFILL_CHUNK:
                dur = float(args.get("dur_s", 0.0))
                emit("prefill_chunk", ts - dur, ts, args)
            elif event == COMPILE:
                dur = float(args.get("compile_s", 0.0))
                emit("xla_compile", ts - dur, ts, args)
            elif event in (PREEMPTED, RESUMED, ROUTED, STREAMED, BLOCK) \
                    or event in TERMINAL:
                rows.append({
                    "name": event.lower(), "cat": "reqtrace",
                    "ph": "i", "ts": ts * 1e6, "s": "t",
                    "pid": "serve", "tid": rid,
                    "args": dict(args, request=rid),
                })
    rows.sort(key=lambda r: (str(r["tid"]), r["ts"]))
    return rows


def why_slow(request_id: str,
             payloads: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Latency attribution for one request: TTFT and e2e decomposed
    into queue / prefill-compute / park / decode / compile / other
    seconds, next to the raw lifecycle events. "prefill compute" is
    host wall of the chunk calls: their launch time (see ``_buckets``).
    A request-id PREFIX is accepted when unambiguous."""
    by_rid = request_events(payloads)
    rows = by_rid.get(str(request_id))
    if rows is None:
        matches = [r for r in by_rid if r.startswith(str(request_id))]
        if len(matches) != 1:
            return {"error": f"request {request_id!r} matched "
                             f"{len(matches)} traced requests"}
        request_id = matches[0]
        rows = by_rid[request_id]
    report = lifecycle(rows)
    report["request_id"] = request_id
    report["events"] = [
        {"event": r["event"],
         "t_s": round(r["ts"] - report["queued_ts"], 6),
         **({k: v for k, v in r["args"].items()})}
        for r in rows]
    return report


def _percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    ordered = sorted(values)
    return round(ordered[min(len(ordered) - 1,
                             int(q * len(ordered)))], 6)


def fold_requests(payloads: List[Dict[str, Any]],
                  by: Optional[str] = None) -> Dict[str, Any]:
    """Percentile fold over every traced request, optionally grouped
    ``by`` "tenant" or "route" (unlabeled requests fold under "-").
    The ``cli requests`` / dashboard Serve-tab surface."""
    groups: Dict[str, List[Dict[str, Any]]] = {}
    for rid, rows in request_events(payloads).items():
        report = lifecycle(rows)
        report["request_id"] = rid
        key = "-"
        if by in ("tenant", "route"):
            key = report.get(by) or "-"
        groups.setdefault(key, []).append(report)
    out: Dict[str, Any] = {"by": by or "all", "groups": {}}
    for key, reports in sorted(groups.items()):
        ttfts = [r["ttft_s"] for r in reports if "ttft_s" in r]
        e2es = [r["e2e_s"] for r in reports
                if r["outcome"] == FINISHED]
        park = sum(r["e2e_buckets"]["park"] for r in reports)
        out["groups"][key] = {
            "requests": len(reports),
            "finished": sum(1 for r in reports
                            if r["outcome"] == FINISHED),
            "cancelled": sum(1 for r in reports
                             if r["outcome"] == CANCELLED),
            "failed": sum(1 for r in reports
                          if r["outcome"] == FAILED),
            "in_flight": sum(1 for r in reports
                             if r["outcome"] is None),
            "preemptions": sum(r["preemptions"] for r in reports),
            "park_s_total": round(park, 6),
            "ttft_p50_s": _percentile(ttfts, 0.5),
            "ttft_p95_s": _percentile(ttfts, 0.95),
            "e2e_p50_s": _percentile(e2es, 0.5),
            "e2e_p95_s": _percentile(e2es, 0.95),
        }
    return out
