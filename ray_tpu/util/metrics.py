"""Application + runtime metrics
(reference: python/ray/util/metrics.py Counter/Gauge/Histogram over the
C++ stats layer src/ray/stats/metric.h; export via dashboard agent to
Prometheus).

Design: each process keeps a local registry; a background flusher pushes
snapshots into the GCS KV under a per-worker key; the dashboard head
aggregates all snapshots into one Prometheus text exposition at /metrics.
No OpenCensus/OTel dependency — the exposition format is the interface."""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

_registry_lock = threading.Lock()
_registry: Dict[str, "Metric"] = {}
_flusher_thread: Optional[threading.Thread] = None
_flusher_stop: Optional[threading.Event] = None

DEFAULT_HISTOGRAM_BOUNDARIES = [
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000]


class Metric:
    kind = "untyped"

    def __init__(self, name: str, description: str = "",
                 tag_keys: Optional[Sequence[str]] = None):
        if not name or not name.replace("_", "a").isalnum():
            raise ValueError(f"invalid metric name {name!r}")
        self._name = name
        self._description = description
        self._tag_keys = tuple(tag_keys or ())
        self._default_tags: Dict[str, str] = {}
        self._lock = threading.Lock()
        # tag-tuple -> value (Counter/Gauge) or histogram state
        self._series: Dict[Tuple, Any] = {}
        with _registry_lock:
            _registry[name] = self
        _ensure_flusher()

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _key(self, tags: Optional[Dict[str, str]]) -> Tuple:
        merged = dict(self._default_tags)
        if tags:
            merged.update(tags)
        unknown = set(merged) - set(self._tag_keys)
        if unknown:
            raise ValueError(f"unknown tag keys {sorted(unknown)} for "
                             f"metric {self._name} (declared "
                             f"{self._tag_keys})")
        return tuple(merged.get(k, "") for k in self._tag_keys)

    def snapshot(self) -> Dict[str, Any]:
        # Series are [tag_values, value] PAIRS, not a joined-string dict:
        # ",".join corrupted any tag value containing a comma (the
        # exposition side split it back apart at the wrong places).
        with self._lock:
            series = [
                [list(k),
                 dict(v, buckets=list(v["buckets"]))
                 if isinstance(v, dict) else v]
                for k, v in self._series.items()]
        return {"name": self._name, "kind": self.kind,
                "description": self._description,
                "tag_keys": list(self._tag_keys), "series": series}


class Counter(Metric):
    kind = "counter"

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None):
        if value < 0:
            raise ValueError("counters only increase")
        key = self._key(tags)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + value


class Gauge(Metric):
    kind = "gauge"

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        key = self._key(tags)
        with self._lock:
            self._series[key] = float(value)


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name: str, description: str = "",
                 boundaries: Optional[Sequence[float]] = None,
                 tag_keys: Optional[Sequence[str]] = None):
        super().__init__(name, description, tag_keys)
        self._boundaries = list(boundaries or DEFAULT_HISTOGRAM_BOUNDARIES)
        # Batched observations: observe() is on task-submission hot
        # paths, so it only appends (key, value) — GIL-atomic, no lock —
        # and the bucket/sum/count fold runs once per flush/snapshot
        # under ONE lock acquisition for the whole batch.
        self._pending: list = []

    def observe(self, value: float, tags: Optional[Dict[str, str]] = None):
        self._pending.append((self._key(tags), value))
        if len(self._pending) >= 4096:
            self._fold()  # bound memory between flushes under floods

    def _fold(self):
        if not self._pending:
            return
        with self._lock:
            # Fold a length-snapshot prefix and delete it in place:
            # concurrent lock-free appends land past the snapshot and
            # survive the del — no observation is ever lost to the race.
            pending_list = self._pending
            n = len(pending_list)
            pending = pending_list[:n]
            series = self._series
            boundaries = self._boundaries
            for key, value in pending:
                state = series.get(key)
                if state is None:
                    state = {"buckets": [0] * (len(boundaries) + 1),
                             "sum": 0.0, "count": 0,
                             "boundaries": boundaries}
                    series[key] = state
                for i, bound in enumerate(boundaries):
                    if value <= bound:
                        state["buckets"][i] += 1
                        break
                else:
                    state["buckets"][-1] += 1
                state["sum"] += value
                state["count"] += 1
            del pending_list[:n]

    def snapshot(self) -> Dict[str, Any]:
        self._fold()
        return super().snapshot()


class LazyMetrics:
    """Lazy, thread-safe metric-namespace singleton: `LazyMetrics(build)`
    calls `build()` exactly once, on first use. Rationale: importing an
    instrumented module must not register series (or start the flusher
    thread) in processes that never observe anything — and a racing
    double construction would re-register the metrics, evicting the
    first objects from the registry and silently dropping whatever they
    had already recorded."""

    def __init__(self, build):
        self._build = build
        self._lock = threading.Lock()
        self._ns = None

    def __call__(self):
        if self._ns is None:
            with self._lock:
                if self._ns is None:
                    self._ns = self._build()
        return self._ns


# ---------------------------------------------------------------------------
# export plumbing
# ---------------------------------------------------------------------------

METRICS_KV_NS = "metrics"


def _ensure_flusher():
    global _flusher_thread, _flusher_stop
    with _registry_lock:
        # Liveness-keyed (not a boolean): after node teardown joins the
        # flusher (or signals it), the next metric construction spawns a
        # fresh one — and a signaled-but-not-yet-exited thread counts as
        # stopped, so the restart cannot be lost to that window. An
        # ident of None means constructed-but-not-yet-started (start()
        # happens after the lock is released): counts as alive, or two
        # racing first-metric constructions would both spawn flushers.
        if _flusher_thread is not None \
                and (_flusher_thread.ident is None
                     or _flusher_thread.is_alive()) \
                and not _flusher_stop.is_set():
            return
        stop = threading.Event()
        thread = threading.Thread(target=_flush_loop, args=(stop,),
                                  daemon=True, name="rtpu-metrics-flush")
        _flusher_thread, _flusher_stop = thread, stop
    # Registered with a stop hook so node teardown joins the flusher
    # (bounded) instead of abandoning it.
    from .._internal.threads import register_daemon_thread
    register_daemon_thread(thread, stop=stop.set)
    thread.start()


def snapshot_all() -> List[Dict[str, Any]]:
    """Snapshots of every metric registered in THIS process."""
    with _registry_lock:
        metrics = list(_registry.values())
    return [m.snapshot() for m in metrics]


def snapshot_all_json() -> bytes:
    import json
    return json.dumps(snapshot_all()).encode()


def flush_now(gcs=None, key: Optional[str] = None) -> bool:
    """Synchronously push this process's snapshots into the GCS KV
    (what the background flusher does every metrics_report_interval_s).
    Must be called from a user thread, not the io loop. Returns False
    when no GCS is reachable — observability is best-effort."""
    try:
        if gcs is None or key is None:
            from .._internal.core_worker import try_get_core_worker
            worker = try_get_core_worker()
            if worker is None:
                return False
            gcs = gcs or worker.gcs
            if key is None:
                key = worker.worker_id.hex() if isinstance(
                    worker.worker_id, bytes) else str(worker.worker_id)
        import sys
        from .._internal import accel
        # the encodes below hold the GIL on this thread: stamped, so that
        # a step of this process which a flush slowed says so (accel
        # plane, `slow[*].pauses`)
        with accel.pause("metrics_flush"):
            # transport-observatory piggyback: fold the hot-path
            # accumulators (wire bytes, in-flight) and the native-ring
            # stats into the registry BEFORE snapshotting so this flush
            # carries them. sys.modules-guarded like the reqtrace hook
            # below — processes that never imported the RPC metrics
            # module pay nothing.
            rpcm = sys.modules.get("ray_tpu._internal.rpc_metrics")
            if rpcm is not None:
                rpcm.export_transport()
            gcs.put(METRICS_KV_NS, key, snapshot_all_json())
            # request-observatory piggyback (steptrace pattern): the
            # serve plane's lifecycle rings ride the same flush cadence.
            # Guarded via sys.modules so processes that never imported
            # the serve plane pay nothing (and never import it from here).
            mod = sys.modules.get("ray_tpu.llm.reqtrace")
            if mod is not None:
                mod.flush(gcs=gcs, key=key)
        return True
    except Exception:  # noqa: BLE001
        return False


def _flush_loop(stop: threading.Event):
    from .._internal.config import CONFIG
    while not stop.wait(CONFIG.metrics_report_interval_s):
        flush_now()


def collect_cluster_metrics(gcs) -> List[Dict[str, Any]]:
    """All processes' snapshots from the GCS KV (dashboard side)."""
    import json
    out = []
    for key in gcs.keys(METRICS_KV_NS, ""):
        raw = gcs.get(METRICS_KV_NS, key)
        if raw:
            try:
                out.extend(json.loads(raw.decode()))
            except ValueError:
                pass
    return out


def _escape_label_value(value: Any) -> str:
    """Prometheus exposition escaping for label values: backslash,
    double-quote, and newline must be escaped or the series line is
    corrupt/unparseable."""
    return (str(value).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _iter_series(snap: Dict[str, Any]):
    """Yield (tag_values_tuple, value) from a snapshot. Supports the
    current pair-list form and the legacy joined-string dict form (old
    KV payloads may outlive a process upgrade within a session)."""
    series = snap.get("series") or []
    if isinstance(series, dict):  # legacy ",".join keys
        keys = snap.get("tag_keys") or []
        for tag_str, value in series.items():
            yield (tuple(tag_str.split(",")) if keys else (), value)
    else:
        for tags, value in series:
            yield tuple(tags), value


def _merge_series(snaps: List[Dict[str, Any]], kind: str):
    """Fold one metric's series from every process into one value per
    tag tuple: counters SUM (each process counts its own events), gauges
    last-write-wins, histograms merge bucket/sum/count when boundaries
    agree. Without this, two processes emitting the same series produce
    duplicate sample lines — invalid exposition that scrapers reject."""
    merged: Dict[Tuple, Any] = {}
    for snap in snaps:
        for tags, value in _iter_series(snap):
            have = merged.get(tags)
            if have is None:
                merged[tags] = value
            elif kind == "counter":
                merged[tags] = have + value
            elif kind == "histogram":
                # mismatched boundaries (mixed process versions): keep
                # the first series rather than merging incompatibly
                if have.get("boundaries") == value.get("boundaries"):
                    merged[tags] = {
                        "boundaries": have["boundaries"],
                        "buckets": [a + b for a, b in
                                    zip(have["buckets"], value["buckets"])],
                        "sum": have["sum"] + value["sum"],
                        "count": have["count"] + value["count"],
                    }
            else:  # gauge/untyped: last snapshot wins
                merged[tags] = value
    return merged


def prometheus_text(snapshots: List[Dict[str, Any]]) -> str:
    """Merge per-process snapshots into one Prometheus text exposition:
    stable # HELP/# TYPE per metric, escaped label values, cross-process
    series merging, and empty metrics (e.g. a histogram declared but
    never observed) rendered as their metadata lines alone."""
    by_name: Dict[str, List[Dict[str, Any]]] = {}
    for snap in snapshots:
        by_name.setdefault(snap["name"], []).append(snap)
    lines = []
    for name, snaps in sorted(by_name.items()):
        first = snaps[0]
        kind = first["kind"]
        if first["description"]:
            desc = first["description"].replace("\\", "\\\\") \
                .replace("\n", "\\n")
            lines.append(f"# HELP {name} {desc}")
        lines.append(f"# TYPE {name} {kind}")
        keys = first["tag_keys"]
        merged = _merge_series(snaps, kind)
        for tags in sorted(merged):
            value = merged[tags]
            label = ",".join(
                f'{k}="{_escape_label_value(v)}"'
                for k, v in zip(keys, tags))
            label = "{" + label + "}" if label else ""
            if kind == "histogram":
                cum = 0
                bounds = value.get("boundaries", []) + ["+Inf"]
                for b, n in zip(bounds, value.get("buckets", [])):
                    cum += n
                    extra = (label[:-1] + "," if label else "{") + \
                        f'le="{b}"' + "}"
                    lines.append(f"{name}_bucket{extra} {cum}")
                lines.append(f"{name}_sum{label} {value.get('sum', 0.0)}")
                lines.append(f"{name}_count{label} "
                             f"{value.get('count', 0)}")
            else:
                lines.append(f"{name}{label} {value}")
    return "\n".join(lines) + "\n"
