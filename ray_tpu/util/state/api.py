"""State API implementation
(reference: python/ray/util/state/api.py — list_* functions backed by the
GCS's tables via StateApiClient; state_cli.py renders them as `ray list`).

Every listing is a list of plain dicts (the reference returns dataclass
rows; dicts keep the surface serialization-free). `timeline()` exports the
task-event buffer as a chrome://tracing JSON trace (reference:
_private/state.py:1013 chrome_tracing_dump)."""

from __future__ import annotations

import concurrent.futures
import json
import os
from typing import Any, Dict, List, Optional, Tuple


def _gcs():
    from ..._internal.core_worker import get_core_worker
    return get_core_worker().gcs


def _live_nodes() -> List[Dict[str, Any]]:
    return [n for n in _gcs().call_sync("get_all_nodes")
            if n.get("state") != "DEAD" and n.get("address")]


def _fanout(nodes: List[Dict[str, Any]], fn
            ) -> List[Tuple[Dict[str, Any], Any, Optional[str]]]:
    """Call `fn(node)` for every node CONCURRENTLY; yields (node,
    result, error) triples — an unreachable node becomes an error row
    instead of being silently dropped (and a single slow node no longer
    serializes the whole sweep behind its timeout)."""
    if not nodes:
        return []
    out = []
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(16, len(nodes))) as pool:
        futs = [(node, pool.submit(fn, node)) for node in nodes]
        for node, fut in futs:
            try:
                out.append((node, fut.result(), None))
            except Exception as e:  # noqa: BLE001 — surfaced as a row
                out.append((node, None, str(e)))
    return out


def list_nodes(limit: int = 1000) -> List[Dict[str, Any]]:
    nodes = _gcs().call_sync("get_all_nodes")
    view = _gcs().call_sync("get_cluster_view")
    out = []
    for node in nodes[:limit]:
        live = view.get(node["node_id"], {})
        out.append({
            "node_id": node["node_id"],
            "state": node.get("state", "ALIVE"),
            "address": node.get("address"),
            "node_index": node.get("node_index"),
            "resources_total": node.get("resources", {}),
            "resources_available": live.get("available", {}),
            "labels": node.get("labels", {}),
            "is_head": node.get("is_head", False),
            "draining": live.get("draining", False),
        })
    return out


def get_node(node_id: str) -> Optional[Dict[str, Any]]:
    for node in list_nodes():
        if node["node_id"] == node_id:
            return node
    return None


def list_actors(limit: int = 1000) -> List[Dict[str, Any]]:
    actors = _gcs().call_sync("get_all_actors")
    out = []
    for a in actors[:limit]:
        aid = a["actor_id"]
        out.append({
            "actor_id": aid.hex() if hasattr(aid, "hex") else str(aid),
            "class_name": a.get("class_name", ""),
            "state": a["state"],
            "name": a.get("name", ""),
            "namespace": a.get("namespace", ""),
            "node_id": a.get("node_id"),
            "address": a.get("address"),
            "is_detached": a.get("is_detached", False),
            "num_restarts": a.get("num_restarts", 0),
            "death_cause": a.get("death_cause"),
        })
    return out


def get_actor(actor_id_hex: str) -> Optional[Dict[str, Any]]:
    for a in list_actors():
        if a["actor_id"].startswith(actor_id_hex):
            return a
    return None


def list_placement_groups(limit: int = 1000) -> List[Dict[str, Any]]:
    pgs = _gcs().call_sync("get_all_placement_groups")
    out = []
    for pg in pgs[:limit]:
        pg_id = pg.get("pg_id")
        out.append({
            "placement_group_id": pg_id.hex() if hasattr(pg_id, "hex")
            else str(pg_id),
            "name": pg.get("name", ""),
            "state": pg.get("state"),
            "strategy": pg.get("strategy"),
            "bundles": pg.get("bundles"),
            "bundle_nodes": pg.get("bundle_nodes"),
        })
    return out


def list_jobs(limit: int = 1000) -> List[Dict[str, Any]]:
    return _gcs().call_sync("get_all_jobs")[:limit]


def shard_summary() -> List[Dict[str, Any]]:
    """Owner-shard stats across the cluster's fan-in processes: every
    RUNNING job's driver (where shards>1 lives — the submit side) plus
    this process's own shards. One row per (process, shard) with queue
    depth, submit count, and loop lag, so shard imbalance is visible
    from the dashboard and `cli status`."""
    from ..._internal.core_worker import get_core_worker
    cw = get_core_worker()
    rows: List[Dict[str, Any]] = []

    def _rows(report, node_id=None):
        if not report:
            return
        for shard in report.get("shards", ()):
            rows.append({
                "pid": report.get("pid"), "mode": report.get("mode"),
                "worker_id": report.get("worker_id"),
                "num_shards": report.get("num_shards"),
                "node_id": node_id, **shard})

    local_addr = tuple(cw.rpc_address) if cw.rpc_address else None
    seen = set()
    drivers = [rec for rec in _gcs().call_sync("get_all_jobs")
               if rec.get("state") == "RUNNING"
               and rec.get("driver_address")]

    def _stats(rec):
        # Tight timeout: the dashboard Nodes tab blocks on this sweep,
        # and a kill -9'd driver stays RUNNING until the liveness sweep
        # notices — don't stall the UI 10 s per dead driver.
        return cw.clients.get(tuple(rec["driver_address"])).call_sync(
            "get_shard_stats", timeout=2)

    for rec, report, error in _fanout(drivers, _stats):
        addr = tuple(rec["driver_address"])
        if addr in seen:
            continue
        seen.add(addr)
        if error is not None:
            rows.append({"pid": None, "mode": "driver",
                         "error": error,
                         "job_id": rec.get("job_id")})
        else:
            _rows(report)
    if local_addr is not None and local_addr not in seen:
        _rows({"pid": os.getpid(), "mode": cw.mode,
               "worker_id": cw.worker_id.hex()
               if isinstance(cw.worker_id, bytes) else str(cw.worker_id),
               "num_shards": len(cw.shards),
               "shards": cw.shards.stats()})
    return rows


def rpc_summary() -> Dict[str, Any]:
    """Transport-observatory fold (`cli rpc` / `/api/rpc`): per-method
    client-latency percentiles and error/retry rates from the flushed
    cluster metric snapshots, plus one row per live process (raylets +
    RUNNING drivers + the caller) with its native-ring stats and
    slow-RPC ring — unreachable processes become error rows.

    Percentiles come from the 1/64-sampled `rtpu_rpc_client_seconds`
    histograms, so they describe the sampled population (slow calls are
    always observed — the tail is exact, the body approximate)."""
    from ..._internal.alerts import _hist_quantile
    from ..._internal.core_worker import get_core_worker
    from ..metrics import _iter_series, collect_cluster_metrics
    cw = get_core_worker()
    snapshots = collect_cluster_metrics(_gcs())

    def _fold_by_tag(name: str, tag: str):
        """Merge every process's series of `name` keyed by one tag."""
        out: Dict[str, Any] = {}
        for snap in snapshots:
            if snap.get("name") != name:
                continue
            keys = snap.get("tag_keys") or []
            for tagvals, value in _iter_series(snap):
                label = dict(zip(keys, tagvals)).get(tag, "?")
                if isinstance(value, dict):       # histogram state
                    acc = out.setdefault(label, {
                        "count": 0, "sum": 0.0,
                        "buckets": [0] * len(value.get("buckets", ())),
                        "boundaries": value.get("boundaries", [])})
                    if len(acc["buckets"]) == len(value.get(
                            "buckets", ())):
                        for i, n in enumerate(value["buckets"]):
                            acc["buckets"][i] += n
                    acc["count"] += value.get("count", 0)
                    acc["sum"] += value.get("sum", 0.0)
                else:
                    out[label] = out.get(label, 0.0) + value
        return out

    errors_by_method = _fold_by_tag(
        "rtpu_rpc_transport_errors_total", "method")
    methods = []
    for method, acc in sorted(_fold_by_tag(
            "rtpu_rpc_client_seconds", "method").items()):
        methods.append({
            "method": method,
            "sampled": acc["count"],
            "mean_s": acc["sum"] / acc["count"] if acc["count"] else None,
            "p50_s": _hist_quantile(acc, 0.50),
            "p95_s": _hist_quantile(acc, 0.95),
            "p99_s": _hist_quantile(acc, 0.99),
            "transport_errors": errors_by_method.get(method, 0.0),
        })

    # Per-ring depth table from the flushed gauges: one row per
    # (pid, ring), depth last-write-wins per process flush.
    rings: Dict[tuple, Dict[str, Any]] = {}
    for name, field in (("rtpu_ring_queue_depth", "queue_depth"),
                        ("rtpu_ring_depth_hwm", "depth_hwm"),
                        ("rtpu_ring_frames_total", None),
                        ("rtpu_ring_bytes_total", None)):
        for snap in snapshots:
            if snap.get("name") != name:
                continue
            keys = snap.get("tag_keys") or []
            for tagvals, value in _iter_series(snap):
                tags = dict(zip(keys, tagvals))
                key = (tags.get("pid", "?"), tags.get("ring", "?"))
                row = rings.setdefault(key, {"pid": key[0],
                                             "ring": key[1]})
                if field is not None:
                    row[field] = value
                else:
                    col = name.rsplit("_", 1)[0].replace(
                        "rtpu_ring_", "") + "_" + tags.get("dir", "?")
                    row[col] = row.get(col, 0.0) + value

    # Per-process rows: every live raylet + every RUNNING driver,
    # fetched concurrently; the calling process reports in-process.
    from ..._internal import rpc_metrics
    processes: List[Dict[str, Any]] = []
    own = rpc_metrics.local_stats()
    own.update(mode=cw.mode, node_id=cw.node_id)
    processes.append(own)

    def _node_stats(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "get_rpc_stats", timeout=2)

    for node, stats, error in _fanout(_live_nodes(), _node_stats):
        if error is not None:
            processes.append({"node_id": node["node_id"],
                              "mode": "raylet", "error": error})
        else:
            processes.append(stats)
    own_addr = tuple(cw.rpc_address) if cw.rpc_address else None
    drivers = [j for j in _gcs().call_sync("get_all_jobs")
               if j.get("state") == "RUNNING" and j.get("driver_address")
               and tuple(j["driver_address"]) != own_addr]

    def _driver_stats(job):
        return cw.clients.get(tuple(job["driver_address"])).call_sync(
            "get_rpc_stats", timeout=2)

    for job, stats, error in _fanout(drivers, _driver_stats):
        if error is not None:
            processes.append({"job_id": job.get("job_id"),
                              "mode": "driver", "error": error})
        else:
            processes.append(stats)

    return {
        "methods": methods,
        "rings": sorted(rings.values(),
                        key=lambda r: (r["pid"], r["ring"])),
        "retries_by_site": _fold_by_tag(
            "rtpu_rpc_retries_total", "site"),
        "chaos_hits": _fold_by_tag("rtpu_chaos_hits_total", "method"),
        "processes": processes,
    }


def list_workers(limit: int = 1000) -> List[Dict[str, Any]]:
    """Per-node worker processes, from each raylet's node stats. Nodes
    are queried concurrently; an unreachable node contributes a
    `{"node_id", "error"}` row instead of vanishing from the listing."""
    from ..._internal.core_worker import get_core_worker
    cw = get_core_worker()

    def _stats(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "get_node_stats", timeout=10)

    out = []
    for node, stats, error in _fanout(_live_nodes(), _stats):
        if error is not None:
            out.append({"node_id": node["node_id"], "error": error})
            continue
        for worker in stats.get("workers", []):
            out.append(dict(worker, node_id=node["node_id"]))
    return out[:limit]


def _fetch_events(job_id: Optional[str] = None,
                  limit: int = 100_000,
                  since: Optional[float] = None) -> List[Dict[str, Any]]:
    return _gcs().call_sync("get_task_events", job_id=job_id,
                            limit=limit, since=since)


def list_tasks(job_id: Optional[str] = None, limit: int = 1000,
               detail: bool = False, since: Optional[float] = None,
               _events: Optional[List[Dict[str, Any]]] = None
               ) -> List[Dict[str, Any]]:
    """Task rows folded from the task-event stream: one row per
    (task_id, attempt) with its latest state + phase timings
    (SUBMITTED→LEASED→RUNNING→FINISHED/FAILED). `since` restricts the
    fold to events newer than that timestamp (incremental pollers merge
    the partial rows client-side instead of refetching 100k events)."""
    events = _events if _events is not None \
        else _fetch_events(job_id, since=since)
    rows: Dict[tuple, Dict[str, Any]] = {}
    for ev in events:
        if ev.get("task_id") is None:
            continue  # SPAN events share the stream; see get_trace()
        key = (ev["task_id"], ev.get("attempt", 0))
        row = rows.setdefault(key, {
            "task_id": ev["task_id"], "attempt": ev.get("attempt", 0),
            "name": ev.get("name"), "job_id": ev.get("job_id"),
            "type": ev.get("type"), "actor_id": ev.get("actor_id"),
            "state": None, "submitted_at": None, "leased_at": None,
            "started_at": None, "finished_at": None, "error": None,
            "node_index": None, "node_id": None, "pid": None,
            "worker_id": None, "phases": {},
        })
        kind = ev["event"]
        if kind != "SPAN":
            # keyed by kind, ordered later by timestamp: owner- and
            # worker-side buffers flush independently, so arrival order
            # is NOT causal order (FINISHED can land before RUNNING)
            row["phases"][kind] = ev["ts"]
        if kind == "SUBMITTED":
            row["submitted_at"] = ev["ts"]
            row["state"] = row["state"] or "PENDING"
        elif kind == "LEASED":
            row["leased_at"] = ev["ts"]
            row["node_id"] = ev.get("node_id")
            if row["state"] in (None, "PENDING"):
                row["state"] = "LEASED"
        elif kind == "RUNNING":
            row["started_at"] = ev["ts"]
            row["pid"] = ev.get("pid")
            row["node_index"] = ev.get("node_index")
            row["worker_id"] = ev.get("worker_id")
            if row["state"] not in ("FINISHED", "FAILED"):
                row["state"] = "RUNNING"
        elif kind == "FINISHED":
            row["finished_at"] = ev["ts"]
            row["state"] = "FINISHED"
        elif kind == "FAILED":
            row["finished_at"] = ev["ts"]
            row["state"] = "FAILED"
            row["error"] = ev.get("error")
    _phase_rank = {"SUBMITTED": 0, "LEASED": 1, "RUNNING": 2,
                   "FINISHED": 3, "FAILED": 3}
    out = list(rows.values())
    for row in out:
        row["phases"] = [k for k in sorted(
            row["phases"],
            key=lambda k: (row["phases"][k], _phase_rank.get(k, 9)))]
    out.sort(key=lambda r: r.get("submitted_at") or 0)
    return out[-limit:]


def summarize_tasks(job_id: Optional[str] = None) -> Dict[str, Any]:
    """Counts by (name, state) (reference: `ray summary tasks`)."""
    summary: Dict[str, Dict[str, int]] = {}
    for row in list_tasks(job_id=job_id, limit=100_000):
        by_state = summary.setdefault(row["name"] or "?", {})
        state = row["state"] or "?"
        by_state[state] = by_state.get(state, 0) + 1
    return summary


def list_objects(limit: int = 1000) -> List[Dict[str, Any]]:
    """Plasma-resident (location-tracked) objects cluster-wide."""
    rows = _gcs().call_sync("get_all_object_locations")
    return rows[:limit]


def timeline(filename: Optional[str] = None,
             job_id: Optional[str] = None,
             since: Optional[float] = None) -> List[Dict[str, Any]]:
    """Chrome-trace ('catapult') export of the task lifecycle
    (reference: ray.timeline → _private/state.py chrome_tracing_dump).
    Per-worker rows carry the execution slice plus its queue/lease
    phases, and user `trace_span` spans render as their own rows — load
    the output in chrome://tracing or Perfetto."""
    # ONE event fetch serves both the task fold and the span rows (the
    # stream caps at 100k dicts — fetching it twice doubled the
    # dashboard hot path's serialization cost).
    events = _fetch_events(job_id, since=since)
    trace = []
    for row in list_tasks(job_id=job_id, limit=100_000, _events=events):
        args = {"task_id": row["task_id"], "state": row["state"],
                "attempt": row["attempt"], "phases": row["phases"],
                "worker_id": row["worker_id"]}
        submitted = row["submitted_at"]
        leased = row["leased_at"]
        started = row["started_at"]
        # Pre-execution phases live on the owner's lease-queue row (the
        # task has no worker yet).
        if submitted is not None:
            queue_end = leased or started
            if queue_end is not None:
                trace.append({
                    "name": f"{row['name']} [queued]",
                    "cat": "task_phase", "ph": "X",
                    "ts": submitted * 1e6,
                    "dur": max(0.0, (queue_end - submitted) * 1e6),
                    "pid": "owner", "tid": "lease-queue", "args": args,
                })
        if leased is not None and started is not None:
            trace.append({
                "name": f"{row['name']} [leased]",
                "cat": "task_phase", "ph": "X",
                "ts": leased * 1e6,
                "dur": max(0.0, (started - leased) * 1e6),
                "pid": "owner", "tid": "lease-wait", "args": args,
            })
        if started is None:
            continue
        end = row["finished_at"] or started
        trace.append({
            "name": row["name"],
            "cat": "task" if row["type"] != 2 else "actor_task",
            "ph": "X",
            "ts": started * 1e6,
            "dur": max(0.0, (end - started) * 1e6),
            "pid": f"node{row['node_index']}",
            "tid": f"worker-pid-{row['pid']}",
            "args": args,
        })
    for ev in _span_events(events=events):
        trace.append({
            "name": ev.get("name"),
            "cat": "span", "ph": "X",
            "ts": ev["ts"] * 1e6,
            "dur": max(0.0, ev.get("duration_s", 0.0) * 1e6),
            "pid": f"pid-{ev.get('pid')}",
            "tid": f"trace-{(ev.get('trace_id') or '')[:8]}",
            "args": {"trace_id": ev.get("trace_id"),
                     "span_id": ev.get("span_id"),
                     "parent_span_id": ev.get("parent_span_id")},
        })
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


# ---------------------------------------------------------------------------
# trace assembly (cross-process span trees)
# ---------------------------------------------------------------------------

def _span_events(trace_id: Optional[str] = None,
                 job_id: Optional[str] = None,
                 events: Optional[List[Dict[str, Any]]] = None
                 ) -> List[Dict[str, Any]]:
    if events is None:
        events = _fetch_events(job_id)
    out = []
    for ev in events:
        if ev.get("event") != "SPAN":
            continue
        if trace_id is not None and ev.get("trace_id") != trace_id:
            continue
        out.append(ev)
    return out


def list_traces(limit: int = 100) -> List[Dict[str, Any]]:
    """Summaries of recently recorded traces, newest first."""
    by_trace: Dict[str, List[Dict[str, Any]]] = {}
    for ev in _span_events():
        if ev.get("trace_id"):
            by_trace.setdefault(ev["trace_id"], []).append(ev)
    out = []
    for trace_id, spans in by_trace.items():
        spans.sort(key=lambda e: e.get("ts", 0))
        root = next((s for s in spans if not s.get("parent_span_id")),
                    spans[0])
        start = spans[0].get("ts", 0)
        end = max(s.get("ts", 0) + s.get("duration_s", 0) for s in spans)
        out.append({
            "trace_id": trace_id, "name": root.get("name"),
            "num_spans": len(spans),
            "num_processes": len({s.get("pid") for s in spans}),
            "start": start, "duration_s": end - start,
        })
    out.sort(key=lambda t: t["start"], reverse=True)
    return out[:limit]


def get_trace(trace_id: str) -> Dict[str, Any]:
    """Assemble one trace's spans into a parent/child tree. Spans from
    different processes (the submitting driver, the executing workers)
    link through the span context carried on the TaskSpec, so the tree
    crosses process hops."""
    nodes: Dict[str, Dict[str, Any]] = {}
    for ev in _span_events(trace_id=trace_id):
        sid = ev.get("span_id")
        if sid is None:
            continue
        nodes[sid] = {
            "span_id": sid, "name": ev.get("name"),
            "parent_span_id": ev.get("parent_span_id"),
            "start": ev.get("ts"),
            "duration_s": ev.get("duration_s", 0.0),
            "pid": ev.get("pid"),
            # execution spans carry their task id (tracing._record) so
            # `cli trace --logs` can interleave that task's log lines
            "task_id": ev.get("task_id_hex"),
            "children": [],
        }
    roots = []
    for node in nodes.values():
        parent = node["parent_span_id"]
        if parent and parent in nodes:
            nodes[parent]["children"].append(node)
        else:
            roots.append(node)
    for node in nodes.values():
        node["children"].sort(key=lambda n: n.get("start") or 0)
    roots.sort(key=lambda n: n.get("start") or 0)
    return {"trace_id": trace_id, "num_spans": len(nodes),
            "num_processes": len({n["pid"] for n in nodes.values()}),
            "roots": roots}


# ---------------------------------------------------------------------------
# memory observability plane (reference: `ray memory` / memory_summary()
# folding every worker's reference table + the raylet's store accounting)
# ---------------------------------------------------------------------------

def _collect_memory_reports(limit: int = 10_000) -> Dict[str, Any]:
    """Raw material for memory_summary(): every node's raylet report
    (store accounting + that node's worker reference tables, fetched by
    the raylet concurrently), every RUNNING driver's reference table,
    and the calling process's own — with error rows for unreachable
    nodes/drivers instead of silent gaps."""
    import os
    from ..._internal.core_worker import get_core_worker
    cw = get_core_worker()

    def _node_report(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "get_memory_report", limit=limit, timeout=30)

    node_reports, owner_reports, errors = [], [], []
    for node, report, error in _fanout(_live_nodes(), _node_report):
        if error is not None:
            errors.append({"node_id": node["node_id"], "error": error})
            continue
        node_reports.append(report)
        owner_reports.extend(
            w for w in report.get("workers", ()) if "error" not in w)
        errors.extend(
            w for w in report.get("workers", ()) if "error" in w)
    # The calling driver's own table (it owns most of what a leak hunt
    # cares about), rendered in-process — no RPC to ourselves.
    own_rows, own_truncated = \
        cw.reference_counter.memory_report_with_meta(limit=limit)
    owner_reports.append({
        "worker_id": cw.worker_id.hex()
        if isinstance(cw.worker_id, bytes) else str(cw.worker_id),
        "pid": os.getpid(), "mode": cw.mode, "node_id": cw.node_id,
        "node_index": cw.node_index,
        "truncated": own_truncated,
        "objects": own_rows,
    })
    # Other RUNNING drivers, via the job table's driver addresses.
    own_addr = tuple(cw.rpc_address) if cw.rpc_address else None
    drivers = [j for j in _gcs().call_sync("get_all_jobs")
               if j.get("state") == "RUNNING" and j.get("driver_address")
               and tuple(j["driver_address"]) != own_addr]

    def _driver_report(job):
        return cw.clients.get(tuple(job["driver_address"])).call_sync(
            "get_memory_report", limit=limit, timeout=15)

    for job, report, error in _fanout(drivers, _driver_report):
        if error is not None:
            errors.append({"job_id": job.get("job_id"), "error": error})
        else:
            owner_reports.append(report)
    return {"nodes": node_reports, "owners": owner_reports,
            "errors": errors}


def list_object_refs(limit: int = 10_000) -> List[Dict[str, Any]]:
    """Cluster-wide flat listing of every live object reference with
    owner attribution (node, pid, size, kind, callsite, borrowers)."""
    data = _collect_memory_reports(limit=limit)
    rows: List[Dict[str, Any]] = []
    for report in data["owners"]:
        for obj in report.get("objects", ()):
            rows.append(dict(obj, node_id=report.get("node_id"),
                             node_index=report.get("node_index"),
                             pid=report.get("pid"),
                             worker_id=report.get("worker_id")))
    rows.sort(key=lambda r: -(r.get("size") or 0))
    return rows[:limit]


def memory_summary(limit: int = 10_000, top: int = 10) -> Dict[str, Any]:
    """Cluster memory summary (reference: ray memory / memory_summary):
    per-node store accounting, per-object reference rows grouped by node
    and by owner callsite (top-N by bytes), plus a leak heuristic —
    store-resident objects no owner still holds a reference to.

    `limit` trims only the RETURNED object rows; collection always runs
    at the full 10k-per-owner bound — a display limit must never shrink
    the `held` set the leak heuristic checks against (a truncated
    reference table would flag held objects as leaks)."""
    data = _collect_memory_reports(limit=max(limit, 10_000))
    objects = []
    held: set = set()
    for report in data["owners"]:
        for obj in report.get("objects", ()):
            objects.append(dict(obj, node_id=report.get("node_id"),
                                node_index=report.get("node_index"),
                                pid=report.get("pid"),
                                worker_id=report.get("worker_id")))
            if obj.get("is_owner") and (
                    obj.get("local") or obj.get("submitted")
                    or obj.get("borrowers") or obj.get("contained_in")):
                held.add(obj["object_id"])
    objects.sort(key=lambda r: -(r.get("size") or 0))

    by_callsite: Dict[str, Dict[str, Any]] = {}
    for obj in objects:
        if not obj.get("is_owner"):
            continue
        site = obj.get("callsite") or "(callsite disabled)"
        agg = by_callsite.setdefault(
            site, {"callsite": site, "count": 0, "total_bytes": 0})
        agg["count"] += 1
        agg["total_bytes"] += obj.get("size") or 0
    top_callsites = sorted(by_callsite.values(),
                           key=lambda a: -a["total_bytes"])[:top]

    # Leak detection needs EVERY owner's COMPLETE table: a worker that
    # timed out contributes nothing to `held`, and a truncated report
    # (>10k refs) silently drops its smallest held entries — either way
    # absent-from-held stops meaning unreferenced. Skip the heuristic
    # and say so rather than fill the panel with false positives.
    leak_heuristic_ok = not data["errors"] and not any(
        rep.get("truncated") for rep in data["owners"])
    nodes, leaked = [], []
    by_node: Dict[str, Dict[str, Any]] = {}
    for report in data["nodes"]:
        node_id = report["node_id"]
        nodes.append({"node_id": node_id,
                      "node_index": report.get("node_index"),
                      "mem_pressure": report.get("mem_pressure", False),
                      "store": report.get("store", {})})
        agg = by_node.setdefault(node_id, {
            "node_id": node_id, "owned_count": 0, "owned_bytes": 0})
        for obj in report.get("objects", ()):
            # Leak heuristic: a store-resident (pinned) object whose
            # owner holds no reference of any kind is unreachable from
            # user code yet still consuming store memory.
            if leak_heuristic_ok and obj["object_id"] not in held:
                leaked.append(dict(obj, node_id=node_id))
    for obj in objects:
        if not obj.get("is_owner"):
            continue
        agg = by_node.setdefault(obj.get("node_id") or "?", {
            "node_id": obj.get("node_id") or "?",
            "owned_count": 0, "owned_bytes": 0})
        agg["owned_count"] += 1
        agg["owned_bytes"] += obj.get("size") or 0
    leaked.sort(key=lambda r: -(r.get("size") or 0))
    return {
        "nodes": nodes,
        "objects": objects[:limit],
        "by_callsite": top_callsites,
        "by_node": sorted(by_node.values(),
                          key=lambda a: -a["owned_bytes"]),
        "leaked": leaked,
        "leak_heuristic_skipped": not leak_heuristic_ok,
        "total_owned_bytes": sum((o.get("size") or 0) for o in objects
                                 if o.get("is_owner")),
        "errors": data["errors"],
    }


# ---------------------------------------------------------------------------
# continuous profiling plane (reference: `ray stack` + the reporter
# agent's py-spy routing; merged post-hoc like the Parca/conprof line —
# see _internal/profiler.py for the per-process sampler)
# ---------------------------------------------------------------------------

def _dedupe_by_host_pid(rows: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
    """Drop later rows that repeat an earlier row's (host, pid):
    local-mode driver/raylet/GCS share one process and must print once,
    while bare pids collide ACROSS nodes under per-container pid
    namespaces so the host must be part of the key. Rows without a pid
    (pure error rows) always pass through."""
    deduped: List[Dict[str, Any]] = []
    seen: set = set()
    for row in rows:
        key = (row.get("host"), row.get("pid"))
        if row.get("pid") is not None and key in seen:
            continue
        seen.add(key)
        deduped.append(row)
    return deduped


def profile_cluster(duration_s: float = 2.0, hz: Optional[float] = None,
                    node_id: Optional[str] = None,
                    pid: Optional[int] = None,
                    task: Optional[str] = None,
                    top: int = 20) -> Dict[str, Any]:
    """Sample every process in the fleet for `duration_s` and merge the
    reports into one collapsed-stack flamegraph, a speedscope document,
    and top-N CPU attribution tables (by task, actor class, and frame).

    Every raylet fans the capture out to its workers concurrently
    (`profile_node`); the GCS and the calling driver sample themselves
    in the same window. Filters: ``node_id`` (prefix) restricts the
    node sweep, ``pid`` keeps one process's samples, ``task`` keeps
    samples attributed to a task id prefix or exact task name.

    Processes sharing one OS process (local mode) share a sampler whose
    collection DRAINS the ring, so concurrent collectors split samples
    rather than double-count them.
    """
    import os as _os
    import time as _time
    from ..._internal import profiler
    from ..._internal.config import CONFIG
    from ..._internal.core_worker import get_core_worker

    cw = get_core_worker()
    duration_s = min(float(duration_s), 60.0)
    hz = float(hz) if hz else CONFIG.profiler_hz
    nodes = _live_nodes()
    # The node filter scopes the WHOLE capture: the driver only samples
    # itself when its own node matches, and the (node-less) GCS only
    # joins unfiltered captures.
    include_driver = not node_id or (cw.node_id or "").startswith(node_id)
    include_gcs = not node_id
    if node_id:
        nodes = [n for n in nodes if n["node_id"].startswith(node_id)]
    errors: List[Dict[str, Any]] = []

    # Start the driver's and the GCS's samplers before the node sweep so
    # every process covers the same window.
    own_start = {}
    gcs_start: Dict[str, Any] = {}
    if include_driver:
        own_start = profiler.start_profiling(hz=hz)
        if own_start.get("already_running"):
            # continuous-mode sampler: discard the pre-window backlog so
            # the post-window drain holds only this capture's samples
            profiler.get_profile(clear=True)
    if include_gcs:
        try:
            gcs_start = _gcs().call_sync("start_profiling", hz=hz,
                                         timeout=10)
            if gcs_start.get("already_running"):
                _gcs().call_sync("get_profile", clear=True, stop=False,
                                 timeout=10)
        except Exception as e:  # noqa: BLE001 — surfaced as a row
            gcs_start = {"error": str(e)}
            errors.append({"component": "gcs", "error": str(e)})

    def _node_profile(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "profile_node", duration_s=duration_s, hz=hz,
            timeout=duration_s + 60)

    t0 = _time.monotonic()
    all_reports: List[Dict[str, Any]] = []
    for node, result, error in _fanout(nodes, _node_profile):
        host = tuple(node["address"])[0]
        if error is not None:
            errors.append({"node_id": node["node_id"], "error": error})
            continue
        all_reports.extend(dict(r, host=host)
                           for r in result.get("reports", ()))
        errors.extend(result.get("errors", ()))
    # No (reachable) raylet slept for us — hold the window open locally.
    remaining = duration_s - (_time.monotonic() - t0)
    if remaining > 0:
        _time.sleep(remaining)
    own_host = tuple(cw.rpc_address)[0] if cw.rpc_address else "127.0.0.1"
    if own_start.get("running"):
        own = profiler.get_profile(
            clear=True, stop=not own_start.get("already_running"))
        own.update(component=cw.mode, node_id=cw.node_id,
                   node_index=cw.node_index, host=own_host)
        all_reports.append(own)
    elif own_start.get("error"):
        errors.append({"component": "driver", "pid": _os.getpid(),
                       "error": own_start["error"]})
    if gcs_start.get("running"):
        gcs_host, _gcs_port = _gcs().address
        try:
            all_reports.append(dict(_gcs().call_sync(
                "get_profile", clear=True,
                stop=not gcs_start.get("already_running"), timeout=15),
                host=gcs_host))
        except Exception as e:  # noqa: BLE001 — surfaced as a row
            errors.append({"component": "gcs", "error": str(e)})

    merged_rows: List[Dict[str, Any]] = []
    processes: List[Dict[str, Any]] = []
    for rep in all_reports:
        if pid is not None and rep.get("pid") != pid:
            continue
        # A continuous-mode sampler keeps its own rate; tag rows with it
        # so cpu_s/speedscope weights convert at the true rate.
        rep_hz = rep.get("meta", {}).get("hz") or hz
        for row in rep.get("samples", ()):
            if task and not ((row.get("task") or "").startswith(task)
                             or row.get("task_name") == task):
                continue
            if rep_hz != hz:
                row = dict(row, hz=rep_hz)
            merged_rows.append(row)
        meta = rep.get("meta", {})
        processes.append({
            "pid": rep.get("pid"),
            "host": rep.get("host"),
            "component": rep.get("component"),
            "node_id": rep.get("node_id"),
            "node_index": rep.get("node_index"),
            "worker_id": rep.get("worker_id"),
            "samples_total": meta.get("samples_total", 0),
            "dropped": meta.get("dropped", 0),
        })
    # local-mode driver/raylet/GCS share one process whose collections
    # split one ring — keep one meta row per actual OS process
    processes = _dedupe_by_host_pid(processes)
    num_samples = sum(r["count"] for r in merged_rows)
    return {
        "duration_s": duration_s,
        "hz": hz,
        "num_samples": num_samples,
        "num_processes": len(processes),
        "collapsed": profiler.collapse_rows(merged_rows),
        "speedscope": profiler.speedscope_document(
            merged_rows, name=f"rtpu cluster profile "
            f"({duration_s:g}s @ {hz:g}Hz)", hz=hz),
        "top": profiler.top_attribution(merged_rows, hz, top=top),
        "executor": profiler.executor_split(merged_rows),
        "processes": processes,
        "errors": errors,
    }


def stack_cluster(node_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """One-shot stack dump of every process in the fleet (`cli stack`):
    each raylet dumps itself + its workers concurrently; the GCS and the
    calling driver dump themselves. Rows are
    ``{node_id, pid, component, text}`` (or ``{..., error}``), deduped
    by (host, pid) so local-mode shared processes print once."""
    import os as _os
    from ..._internal import profiler
    from ..._internal.core_worker import get_core_worker

    cw = get_core_worker()
    nodes = _live_nodes()
    if node_id:
        nodes = [n for n in nodes if n["node_id"].startswith(node_id)]

    def _node_stacks(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "stack_dump_node", timeout=60)

    rows: List[Dict[str, Any]] = []
    for node, result, error in _fanout(nodes, _node_stacks):
        host = tuple(node["address"])[0]
        if error is not None:
            rows.append({"node_id": node["node_id"], "error": error})
            continue
        for row in result:
            rows.append(dict(row, host=host))
    # The node filter scopes the whole dump: the (node-less) GCS only
    # joins unfiltered sweeps, the driver only when its node matches.
    if not node_id:
        gcs_host, _gcs_port = _gcs().address
        try:
            gcs_dump = _gcs().call_sync("dump_stacks", timeout=30)
            rows.append({"component": "gcs", "host": gcs_host,
                         "pid": gcs_dump.get("pid"),
                         "text": gcs_dump.get("text", "")})
        except Exception as e:  # noqa: BLE001 — surfaced as a row
            rows.append({"component": "gcs", "error": str(e)})
    if not node_id or (cw.node_id or "").startswith(node_id):
        own_host = tuple(cw.rpc_address)[0] if cw.rpc_address \
            else "127.0.0.1"
        rows.append({"component": "driver", "host": own_host,
                     "node_id": cw.node_id, "pid": _os.getpid(),
                     "text": profiler.stack_dump_text()})
    return _dedupe_by_host_pid(rows)


def profiling_status() -> List[Dict[str, Any]]:
    """Per-process sampler status fleet-wide (`/api/profile/status`).
    Rows dedupe by (host, pid) — bare pids collide across nodes under
    per-container pid namespaces, while local-mode driver/raylet/GCS
    share one process and must still print once."""
    from ..._internal import profiler
    from ..._internal.core_worker import get_core_worker

    cw = get_core_worker()

    def _node_status(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "profiling_status", timeout=15)

    rows: List[Dict[str, Any]] = []
    for node, result, error in _fanout(_live_nodes(), _node_status):
        host = tuple(node["address"])[0]
        if error is not None:
            rows.append({"node_id": node["node_id"], "error": error})
            continue
        rows.extend(dict(r, host=host)
                    for r in result.get("processes", ()))
    gcs_host, _gcs_port = _gcs().address
    try:
        rows.append(dict(_gcs().call_sync("profiling_status", timeout=10),
                         host=gcs_host))
    except Exception as e:  # noqa: BLE001 — surfaced as a row
        rows.append({"component": "gcs", "error": str(e)})
    own_host = tuple(cw.rpc_address)[0] if cw.rpc_address else "127.0.0.1"
    rows.append(dict(profiler.profiling_status(), component="driver",
                     node_id=cw.node_id, host=own_host))
    return _dedupe_by_host_pid(rows)


# ---------------------------------------------------------------------------
# accelerator observability plane (reference: would be `ray status -v`
# accelerator rows + the reporter agent's GPU/TPU utilization feed; here
# each raylet fans get_accel_report out to its workers — see
# _internal/accel.py for the per-process snapshot/compile/step plumbing)
# ---------------------------------------------------------------------------


def accel_summary(force_local_jax: bool = True,
                  node_timeout_s: float = 30.0) -> Dict[str, Any]:
    """Cluster accelerator summary: per-process device HBM rows, XLA
    compile tracking, and step/MFU telemetry, grouped by node.

    Every node's raylet report (its workers fetched concurrently by the
    raylet), every RUNNING driver's report, and the calling process's
    own (with ``force_jax=True`` — the caller is asking about devices,
    so importing jax locally is expected). Unreachable nodes/drivers
    become error rows, not gaps. Pressure rows the local snapshot
    surfaces are published to the GCS event log from here (user
    thread, sync bridge)."""
    from ..._internal import accel
    from ..._internal.core_worker import get_core_worker
    cw = get_core_worker()

    def _node_report(node):
        # node_timeout_s: 30 for the dedicated `cli devices` sweep;
        # status/dashboard callers pass a short bound — one hung raylet
        # must not stall the whole status output (the PR-6
        # shard_summary lesson).
        return cw.clients.get(tuple(node["address"])).call_sync(
            "get_accel_report", timeout=node_timeout_s)

    processes: List[Dict[str, Any]] = []
    errors: List[Dict[str, Any]] = []
    by_node: Dict[str, Dict[str, Any]] = {}

    def _fold(report, node_id):
        node = by_node.setdefault(node_id or "?", {
            "node_id": node_id or "?", "num_devices": 0,
            "hbm_used_bytes": 0, "hbm_limit_bytes": 0,
            "compiles": 0, "compile_seconds": 0.0})
        comp = report.get("compile") or {}
        node["compiles"] += comp.get("compiles", 0)
        node["compile_seconds"] += comp.get("compile_seconds", 0.0)
        for dev in report.get("devices", ()):
            node["num_devices"] += 1
            node["hbm_used_bytes"] += dev.get("hbm_used_bytes", 0)
            node["hbm_limit_bytes"] += dev.get("hbm_limit_bytes", 0)
        processes.append(dict(report, node_id=node_id))

    for node, report, error in _fanout(_live_nodes(), _node_report):
        if error is not None:
            errors.append({"node_id": node["node_id"], "error": error})
            continue
        for wrep in report.get("workers", ()):
            if "error" in wrep:
                errors.append(wrep)
            else:
                _fold(wrep, node["node_id"])
    # The calling driver's own report, rendered in-process — no RPC to
    # ourselves, and the only report allowed to force-import jax
    # (``force_local_jax=False`` keeps lightweight callers like
    # `cli status` from paying the jax import for a status line).
    own = accel.accel_report(force_jax=force_local_jax)
    own.update(mode=cw.mode, worker_id=cw.worker_id.hex()
               if isinstance(cw.worker_id, bytes) else str(cw.worker_id),
               node_index=cw.node_index)
    for pressed in own.get("pressure", ()):
        accel.emit_pressure_event(
            f"device {pressed['device']} ({pressed['device_kind']}) HBM "
            f"at {pressed['used_ratio']:.0%} of limit",
            fields=dict(pressed, node_id=cw.node_id))
    _fold(own, cw.node_id)
    # Other RUNNING drivers, via the job table's driver addresses.
    own_addr = tuple(cw.rpc_address) if cw.rpc_address else None
    drivers = [j for j in _gcs().call_sync("get_all_jobs")
               if j.get("state") == "RUNNING" and j.get("driver_address")
               and tuple(j["driver_address"]) != own_addr]

    def _driver_report(job):
        return cw.clients.get(tuple(job["driver_address"])).call_sync(
            "get_accel_report", timeout=5)

    for job, report, error in _fanout(drivers, _driver_report):
        if error is not None:
            errors.append({"job_id": job.get("job_id"), "error": error})
        else:
            _fold(report, report.get("node_id"))

    devices: List[Dict[str, Any]] = []
    steps: List[Dict[str, Any]] = []
    compiles = compile_seconds = cache_hits = cache_misses = 0
    for report in processes:
        for dev in report.get("devices", ()):
            devices.append(dict(
                dev, node_id=report.get("node_id"),
                pid=report.get("pid"),
                worker_id=report.get("worker_id")))
        for row in report.get("steps", ()):
            # `now`: the process's monotonic clock when it reported, for
            # the ages of its `slow` steps
            steps.append(dict(row, node_id=report.get("node_id"),
                              pid=report.get("pid"),
                              now=report.get("now")))
        comp = report.get("compile") or {}
        compiles += comp.get("compiles", 0)
        compile_seconds += comp.get("compile_seconds", 0.0)
        cache_hits += comp.get("cache_hits", 0)
        cache_misses += comp.get("cache_misses", 0)
    devices.sort(key=lambda r: -(r.get("hbm_used_bytes") or 0))
    steps.sort(key=lambda r: -(r.get("wall_s") or 0))
    return {
        "nodes": sorted(by_node.values(), key=lambda n: n["node_id"]),
        "devices": devices,
        "steps": steps,
        "compile": {
            "compiles": compiles,
            "compile_seconds": round(compile_seconds, 6),
            "cache_hits": cache_hits,
            "cache_misses": cache_misses,
        },
        "processes": [{k: v for k, v in rep.items()
                       if k not in ("devices", "steps")}
                      for rep in processes],
        "errors": errors,
    }


# ---------------------------------------------------------------------------
# log & forensics plane (reference: state API list_logs/get_log + the
# dashboard log view; here every raylet serves its workers' bounded
# rings — see _internal/logplane.py for capture/attribution/postmortems)
# ---------------------------------------------------------------------------


def list_logs(node_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Ring inventory cluster-wide: one row per worker log ring (live
    and retained-dead) with line/drop/byte counts — no line payloads.
    Unreachable nodes become error rows."""
    from ..._internal.core_worker import get_core_worker
    cw = get_core_worker()
    nodes = _live_nodes()
    if node_id:
        nodes = [n for n in nodes if n["node_id"].startswith(node_id)]

    def _node_rings(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "list_logs", timeout=10)

    rows: List[Dict[str, Any]] = []
    for node, result, error in _fanout(nodes, _node_rings):
        if error is not None:
            rows.append({"node_id": node["node_id"], "error": error})
            continue
        rows.extend(result.get("rings", ()))
    return rows


def get_logs(task: Optional[str] = None, actor: Optional[str] = None,
             job: Optional[str] = None, node_id: Optional[str] = None,
             level: Optional[str] = None, grep: Optional[str] = None,
             tail: Optional[int] = None, limit: int = 1000,
             since: Optional[Dict[str, Dict[str, int]]] = None
             ) -> Dict[str, Any]:
    """Attributed log lines cluster-wide, merged across every node's
    worker rings and sorted by timestamp. Filters: ``task``/``actor``
    hex prefix, ``job`` hex, ``node_id`` prefix, min ``level``,
    ``grep`` regex, ``tail``-N after the merge. ``since`` is the
    cursor this function returned last time ({node_id: {worker: seq}})
    — pass it back to receive only newer lines (the follow loop
    `tail_logs` wraps)."""
    from ..._internal.core_worker import get_core_worker
    cw = get_core_worker()
    since = since or {}
    nodes = _live_nodes()
    if node_id:
        nodes = [n for n in nodes if n["node_id"].startswith(node_id)]

    def _node_logs(node):
        return cw.clients.get(tuple(node["address"])).call_sync(
            "get_logs", task=task, actor=actor, job=job, level=level,
            grep=grep, tail=tail, limit=limit,
            since=since.get(node["node_id"]), timeout=15)

    lines: List[Dict[str, Any]] = []
    cursors: Dict[str, Dict[str, int]] = {}
    errors: List[Dict[str, Any]] = []
    dropped = 0
    disabled = False
    for node, result, error in _fanout(nodes, _node_logs):
        if error is not None:
            errors.append({"node_id": node["node_id"], "error": error})
            # keep the previous cursor: a transiently unreachable node
            # must not make the next follow poll replay its whole rings
            if node["node_id"] in since:
                cursors[node["node_id"]] = since[node["node_id"]]
            continue
        lines.extend(result.get("lines", ()))
        cursors[node["node_id"]] = result.get("cursors", {})
        dropped += result.get("dropped", 0)
        disabled = disabled or result.get("disabled", False)
    lines.sort(key=lambda e: (e.get("ts") or 0, e.get("seq") or 0))
    if tail:
        # dropping the OLDEST merged lines is what tail asks for — the
        # per-node cursors legitimately skip them
        lines = lines[-max(1, int(tail)):]
    cut, lines = lines[limit:], lines[:limit]
    # The global cap cuts the NEWEST merged lines, but each raylet's
    # reply already advanced its cursors past everything it returned —
    # clamp the affected (node, worker) cursors back to the newest line
    # actually kept, or a follower would skip the cut lines forever.
    if cut:
        kept_max: Dict[tuple, int] = {}
        for line in lines:
            key = (line.get("node_id"), line.get("worker_id"))
            if (line.get("seq") or 0) > kept_max.get(key, 0):
                kept_max[key] = line["seq"]
        for line in cut:
            node, worker = line.get("node_id"), line.get("worker_id")
            node_cursors = cursors.get(node)
            if node_cursors is None or worker not in node_cursors:
                continue
            prev = int((since.get(node) or {}).get(worker, 0))
            node_cursors[worker] = max(
                prev, kept_max.get((node, worker), prev))
    return {"lines": lines, "cursors": cursors,
            "dropped": dropped, "errors": errors, "disabled": disabled}


def tail_logs(task: Optional[str] = None, actor: Optional[str] = None,
              job: Optional[str] = None, node_id: Optional[str] = None,
              level: Optional[str] = None, grep: Optional[str] = None,
              poll_s: float = 0.5):
    """Generator for `cli logs --follow`: yields one `get_logs` result
    per poll, threading the cursor through so each batch holds only
    lines the previous batch has not seen. The first batch tails the
    recent past (last 100 lines) instead of replaying whole rings."""
    batch = get_logs(task=task, actor=actor, job=job, node_id=node_id,
                     level=level, grep=grep, tail=100)
    while True:
        yield batch
        import time as _time
        _time.sleep(poll_s)
        batch = get_logs(task=task, actor=actor, job=job,
                         node_id=node_id, level=level, grep=grep,
                         since=batch["cursors"])


def list_events(event_type: Optional[str] = None,
                since: Optional[float] = None,
                severity: Optional[str] = None,
                limit: int = 1000) -> List[Dict[str, Any]]:
    """The GCS's persistent cluster event log (node ALIVE/DEAD, actor
    transitions, job state, SPILL/RESTORE, MEMORY_PRESSURE...)."""
    return _gcs().call_sync("get_events", event_type=event_type,
                            since=since, severity=severity, limit=limit)


def train_timeline(filename: Optional[str] = None
                   ) -> List[Dict[str, Any]]:
    """Cross-rank train-step timeline: every rank's (and every MPMD
    pipeline stage's) flushed phase spans folded into one chrome-trace
    JSON on the shared monotonic clock — pid = rank/stage track, spans
    nest by time containment (step > data/forward/collective/optimizer).
    Load the output in chrome://tracing or Perfetto; the train-plane
    companion to `timeline()`'s task view."""
    from ...train import steptrace
    trace = steptrace.to_chrome_trace(steptrace.collect(_gcs()))
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


def serve_timeline(filename: Optional[str] = None
                   ) -> List[Dict[str, Any]]:
    """Serve-plane request timeline: every process's flushed
    request-lifecycle events (llm/reqtrace.py) folded into one
    chrome-trace JSON on the shared monotonic clock — one row per
    request id, queue/park/prefill/decode state spans with
    prefill-chunk and XLA-compile spans nested, PREEMPTED/RESUMED/
    ROUTED as instants. The serve twin of `train_timeline()`."""
    from ...llm import reqtrace
    trace = reqtrace.to_chrome_trace(reqtrace.collect(_gcs()))
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


def why_slow(request_id: str) -> Dict[str, Any]:
    """Latency attribution for one served request: TTFT and e2e
    decomposed into queue / prefill-compute / park / decode /
    XLA-compile / other buckets from its flushed lifecycle events,
    plus the raw event list. Accepts a unique request-id prefix."""
    from ...llm import reqtrace
    return reqtrace.why_slow(request_id, reqtrace.collect(_gcs()))


def serve_requests(by: Optional[str] = None) -> Dict[str, Any]:
    """Percentile fold over every traced serve request — TTFT/e2e
    p50/p95, outcomes, preemptions, total park time — grouped by
    "tenant" or "route" when `by` is given (`cli requests`)."""
    from ...llm import reqtrace
    return reqtrace.fold_requests(reqtrace.collect(_gcs()), by=by)


def stragglers(limit: int = 100) -> Dict[str, Any]:
    """The straggler/skew view: STRAGGLER_DETECTED events (which rank,
    which phase, how far above the peer median) next to the per-track
    rolling step-time fold from the flushed steptrace payloads."""
    from ...train import steptrace
    return {
        "events": list_events(event_type="STRAGGLER_DETECTED",
                              limit=limit),
        "step_stats": steptrace.step_stats(steptrace.collect(_gcs())),
    }


def alerts(rule: Optional[str] = None, since: Optional[float] = None,
           severity: Optional[str] = None,
           limit: int = 100) -> List[Dict[str, Any]]:
    """The GCS's bounded SLO alert table (what the alert engine fired),
    newest last — `cli alerts` / `/api/alerts`."""
    return _gcs().call_sync("get_alerts", rule=rule, since=since,
                            severity=severity, limit=limit)


def gcs_info() -> Dict[str, Any]:
    """GCS identity + durability status: incarnation, persist mode, WAL
    size, failover count (the `cli chaos` / dashboard failover surface)."""
    return _gcs().call_sync("gcs_info")


def drain_node(node_id: str, timeout_s: Optional[float] = None,
               exit_process: bool = False,
               cancel: bool = False) -> Dict[str, Any]:
    """GCS-coordinated graceful drain of one node (`cli drain` / the
    elastic autoscaler's scale-in path): fence new lease grants,
    migrate its actors (restart budget untouched), wait for in-flight
    leases up to ``timeout_s``, postmortem-tag stragglers. A node-id
    PREFIX is accepted (resolved against the alive node table);
    ``exit_process`` additionally makes a standalone raylet exit clean
    (the rolling-restart primitive); ``cancel`` lowers the fence."""
    from ..._internal.config import CONFIG
    matches = [n for n in _live_nodes()
               if n["node_id"].startswith(node_id)]
    if len(matches) != 1:
        return {"error": f"node prefix {node_id!r} matched "
                         f"{len(matches)} alive nodes"}
    budget = timeout_s if timeout_s is not None else CONFIG.drain_timeout_s
    return _gcs().call_sync(
        "drain_node", node_id=matches[0]["node_id"], timeout_s=budget,
        exit_process=exit_process, cancel=cancel, timeout=budget + 60)


def autoscaler_state() -> Dict[str, Any]:
    """The GCS autoscaler state manager's view: per-node capacity /
    pending-lease queue depth + age / drain flag, plus aggregate unmet
    demand (the elastic reconciler's input, also on `/api/autoscaler`)."""
    return _gcs().call_sync("get_autoscaler_state")


def set_chaos(spec: str = "", seed: int = 0,
              schedule: Optional[str] = None) -> List[Dict[str, Any]]:
    """Arm (or, with an empty spec+schedule, disarm) the fault-injection
    registry on the GCS and every live raylet — static rules and/or a
    time-scheduled script. Returns one row per process. Workers pick
    rules up through their own CONFIG env; this call covers the control
    plane, which is where the chaos harness aims."""
    rows = []
    reply = _gcs().call_sync("set_chaos", spec=spec, seed=seed,
                             schedule=schedule)
    rows.append(dict(reply, component="gcs"))
    from ..._internal.core_worker import get_core_worker
    worker = get_core_worker()

    def _one(node):
        return worker.run_sync(
            worker.clients.get(tuple(node["address"])).call(
                "set_chaos", spec=spec, seed=seed, schedule=schedule,
                timeout=10), timeout=15)

    for node, result, error in _fanout(_live_nodes(), _one):
        row = {"component": "raylet", "node_id": node["node_id"]}
        if error is not None:
            row["error"] = error
        else:
            row.update(result)
        rows.append(row)
    return rows
