"""Command-line interface
(reference: python/ray/scripts/scripts.py — `ray start` :679, stop,
status, job submit/logs/stop, `ray list ...` via util/state/state_cli.py,
`ray timeline`).

Usage: python -m ray_tpu.cli <command> ...

  start --head [--num-cpus N] [--port P] [--dashboard]   run a head node
  start --address HOST:PORT [--num-cpus N]               join as a worker
  stop                                                   stop local nodes
  status   [--address ...]                               cluster resources
  list     {nodes,actors,tasks,placement_groups,objects,workers,jobs}
  memory   [--json] [--limit N]                          cluster memory report
  events   [--type T] [--json] [--limit N]               cluster event log
  timeline [--output FILE] [--train|--serve]             chrome trace
  requests [--by-tenant|--by-route] [--why ID] [--json]  serve request folds
  stragglers [--json] [--limit N]                        skew/straggler view
  alerts   [--rule R] [--severity S] [--json]            SLO alert table
  trace    [TRACE_ID] [--json] [--logs]                  span tree / list
  logs     [--task|--actor|--job|--node|--level|--grep]  cluster log search
           [--tail N] [--follow] [--json]                (worker ring query)
  profile  [--duration S] [--hz N] [--format F]          cluster CPU profile
           [--node ID] [--pid P] [--task T] [-o FILE]    (merged flamegraph)
  stack    [--node ID] [--json]                          fleet stack dump
  devices  [--json]                                      per-device HBM /
                                                         compile / step+MFU
  dashboard                                              start + print URL
  submit   [--wait] -- ENTRYPOINT...                     submit a job
  job      {logs,stop,list} [ID]
  chaos    {show,set,clear,kill-gcs,kill-worker}         fault injection
           [--spec S] [--seed N] [--pid P]               drills / failover
  perf     [--quick]                                     microbenchmarks

The head address is written to /tmp/rtpu/head_address; commands default
to it so `--address` is rarely needed (reference: ray's address file in
the session dir)."""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

ADDRESS_FILE = "/tmp/rtpu/head_address"


def _write_address(address: str):
    os.makedirs(os.path.dirname(ADDRESS_FILE), exist_ok=True)
    with open(ADDRESS_FILE, "w") as f:
        f.write(address)


def _resolve_address(arg) -> str:
    if arg:
        return arg
    try:
        with open(ADDRESS_FILE) as f:
            return f.read().strip()
    except FileNotFoundError:
        raise SystemExit(
            "no --address given and no head found "
            f"({ADDRESS_FILE} missing); run `python -m ray_tpu.cli "
            "start --head` first")


def _connect(args):
    import ray_tpu
    if ray_tpu.is_initialized():
        return
    ray_tpu.init(address=_resolve_address(getattr(args, "address", None)),
                 ignore_reinit_error=True)


# -- commands ---------------------------------------------------------------

def cmd_start(args):
    from ray_tpu._internal.node import Node, default_resources

    resources = default_resources(args.num_cpus, None)
    if args.head:
        node = Node(head=True, resources=resources)
        node.start()
        address = f"{node.gcs_address[0]}:{node.gcs_address[1]}"
        _write_address(address)
        print(f"head started; GCS at {address}", flush=True)
        if args.dashboard:
            import ray_tpu
            from ray_tpu.dashboard import start_dashboard
            ray_tpu.init(address=address, ignore_reinit_error=True)
            print(f"dashboard at {start_dashboard()}", flush=True)
        print("press Ctrl-C to stop", flush=True)
        _block_until_signal()
        node.stop()
        return
    address = _resolve_address(args.address)
    host, port = address.rsplit(":", 1)
    from ray_tpu._internal.gcs_client import GcsClient
    probe = GcsClient((host, int(port)))
    nodes = probe.call_sync("get_all_nodes")
    session = next((n.get("session_name") for n in nodes
                    if n.get("is_head")), "connected")
    index = max((n.get("node_index", 0) for n in nodes), default=0) + 1
    from ray_tpu._internal import raylet_main
    sys.argv = ["raylet"]
    raylet_main.main([
        "--gcs-address", address, "--session", session or "connected",
        "--node-index", str(index),
        "--resources", json.dumps(resources),
    ])


def _block_until_signal():
    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    while not stop:
        time.sleep(0.5)


def cmd_up(args):
    """`ray up cluster.yaml` analog (reference:
    autoscaler/_private/commands.py create_or_update_cluster): start a
    head + autoscaler from a declarative YAML and reconcile until
    interrupted."""
    from ray_tpu.autoscaler.cluster_config import load_cluster_config, up

    config = load_cluster_config(args.config)
    workers = {name: (spec.get("min_workers", 0),
                      spec.get("max_workers", 0))
               for name, spec in config["available_node_types"].items()
               if name != config["head_node_type"]}
    print(f"cluster {config['cluster_name']!r}: provider "
          f"{config['provider']['type']}, head "
          f"{config['head_node_type']}, workers {workers}", flush=True)
    if args.validate_only:
        print("config valid", flush=True)
        return
    handle = up(config)
    address = handle.cluster.address
    _write_address(address)
    print(f"head started; GCS at {address}; autoscaler reconciling "
          "(Ctrl-C to tear down)", flush=True)
    _block_until_signal()
    handle.down()


def cmd_down(args):
    """`ray down` analog (reference: commands.py teardown_cluster):
    terminate every provider instance named by the YAML."""
    from ray_tpu.autoscaler.cluster_config import (_build_provider,
                                                   load_cluster_config)

    config = load_cluster_config(args.config)
    if config["provider"]["type"] == "fake":
        print("fake provider is in-process; nothing to tear down "
              "(Ctrl-C the `up` process instead)", flush=True)
        return
    provider = _build_provider(config, cluster=None)
    instances = provider.non_terminated_instances()
    for instance_id in instances:
        provider.terminate(instance_id)
    print(f"terminated {len(instances)} instances of cluster "
          f"{config['cluster_name']!r}", flush=True)


def cmd_stop(_args):
    import subprocess
    patterns = ["ray_tpu._internal.raylet_main",
                "ray_tpu._internal.worker_main",
                "ray_tpu.cli start"]
    for pattern in patterns:
        subprocess.run(["pkill", "-f", pattern], check=False)
    try:
        os.unlink(ADDRESS_FILE)
    except FileNotFoundError:
        pass
    print("stopped")


def cmd_status(args):
    _connect(args)
    from ray_tpu.util import state as st
    from ray_tpu._internal.core_worker import get_core_worker
    nodes = st.list_nodes()
    demand = get_core_worker().gcs.call_sync("get_cluster_demand")
    print(f"nodes: {len(nodes)}")
    total, avail = {}, {}
    for node in nodes:
        mark = " (head)" if node["is_head"] else ""
        print(f"  {node['node_id'][:12]}{mark}  "
              f"{node['resources_available']} / "
              f"{node['resources_total']}")
        for k, v in node["resources_total"].items():
            total[k] = total.get(k, 0) + v
        for k, v in node["resources_available"].items():
            avail[k] = avail.get(k, 0) + v
    print(f"resources: {avail} available of {total}")
    # Owner shards of THIS driver (the submit fan-in side): queue depth
    # and loop lag per shard make imbalance visible from the terminal.
    cw = get_core_worker()
    if len(cw.shards) > 1:
        print(f"owner shards (driver pid {os.getpid()}): "
              f"{len(cw.shards)}")
        for row in cw.shards.stats():
            lag = row["loop_lag_s"]
            lag_txt = f"{lag * 1000:.2f}ms" if lag is not None else "-"
            print(f"  shard {row['shard']}: queue_depth="
                  f"{row['queue_depth']} submits={row['submits']} "
                  f"loop_lag={lag_txt}")
    # Per-node accelerator rows from the device plane (chip count, HBM
    # used/limit, compile seconds since start) — best-effort: a cluster
    # with no accel reports (or the plane killed) just omits the block.
    try:
        accel = st.accel_summary(force_local_jax=False, node_timeout_s=3)
        accel_nodes = [n for n in accel["nodes"]
                       if n["num_devices"] or n["compiles"]]
        if accel_nodes:
            print("accelerators:")
            for row in accel_nodes:
                limit = _fmt_bytes(row["hbm_limit_bytes"]) \
                    if row["hbm_limit_bytes"] else "?"
                print(f"  {row['node_id'][:12]}  "
                      f"{row['num_devices']} chips  HBM "
                      f"{_fmt_bytes(row['hbm_used_bytes'])} / {limit}  "
                      f"compile {row['compile_seconds']:.2f}s "
                      f"({row['compiles']} compiles)")
    except Exception as e:  # noqa: BLE001 — status must render anyway
        print(f"accelerators: unavailable ({e})")
    # Transport plane: per-process rpc error/retry/slow totals from the
    # observatory fan-out — best-effort like the accel block (and empty
    # under RTPU_NO_RPC_METRICS, where the counters don't exist).
    try:
        rows = [p for p in st.rpc_summary()["processes"]
                if "error" not in p]
        if any(p.get("transport_errors") or p.get("retries")
               or p.get("slow_total") for p in rows):
            print("rpc transport:")
            for p in rows:
                node = (p.get("node_id") or "")[:12] or "-"
                print(f"  {p.get('mode', '?'):8s} pid={p.get('pid')} "
                      f"node={node}  "
                      f"errors={p.get('transport_errors', 0):g}  "
                      f"retries={p.get('retries', 0):g}  "
                      f"slow={p.get('slow_total', 0)}")
    except Exception as e:  # noqa: BLE001 — status must render anyway
        print(f"rpc transport: unavailable ({e})")
    # Per-shape pending demand with a feasibility check, so "why is my
    # task pending" is answerable from here: a shape no amount of
    # waiting can satisfy is flagged INFEASIBLE. A shape must fit on
    # ONE node (tasks/bundles don't split), so the test is whether any
    # single node's totals satisfy every resource at once — not the
    # cluster-wide sum ({CPU: 12} pends forever on 2x8-CPU nodes).
    shapes = {}
    for kind, shape_list in (("task", demand["task_demand"]),
                             ("pg bundle", demand["pg_demand"])):
        for shape in shape_list:
            key = (kind, tuple(sorted(shape.items())))
            shapes[key] = shapes.get(key, 0) + 1
    if not shapes:
        print("pending demand: none")
        return
    print(f"pending demand: {sum(shapes.values())} requests, "
          f"{len(shapes)} shapes")
    node_totals = [n["resources_total"] for n in nodes]
    for (kind, shape), count in sorted(shapes.items(),
                                       key=lambda kv: -kv[1]):
        demand_dict = dict(shape)
        line = f"  {count}x {kind} {demand_dict}"
        fits_somewhere = any(
            all(nt.get(k, 0) >= v for k, v in shape)
            for nt in node_totals)
        if not fits_somewhere:
            best = {k: max((nt.get(k, 0) for nt in node_totals),
                           default=0) for k, _v in shape}
            why = [f"{k} {v:g} > best node {best[k]:g}"
                   for k, v in shape if v > best[k]]
            line += (f"  [INFEASIBLE: no single node fits: "
                     f"{'; '.join(why) or 'combined shape'}]")
        print(line)


def cmd_list(args):
    _connect(args)
    from ray_tpu.util import state as st
    listing = {
        "nodes": st.list_nodes, "actors": st.list_actors,
        "tasks": st.list_tasks,
        "placement_groups": st.list_placement_groups,
        "objects": st.list_objects, "workers": st.list_workers,
    }
    if args.what == "jobs":
        from ray_tpu.job_submission import JobManager
        rows = JobManager().list_jobs()
    else:
        rows = listing[args.what](limit=args.limit)
    print(json.dumps(rows, indent=1, default=str))


def _fmt_bytes(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TiB"


def cmd_memory(args):
    """Cluster memory report (reference: `ray memory` — per-object rows
    with owner, reference kind, and callsite, plus store accounting and
    the pinned-but-unreferenced leak heuristic)."""
    _connect(args)
    from ray_tpu.util import state as st
    summary = st.memory_summary(limit=args.limit)
    if args.json:
        print(json.dumps(summary, indent=1, default=str))
        return
    for node in summary["nodes"]:
        store = node["store"]
        pressure = "  [MEMORY PRESSURE]" if node.get("mem_pressure") else ""
        print(f"node {node['node_id'][:12]}  store "
              f"{_fmt_bytes(store.get('used_bytes'))} / "
              f"{_fmt_bytes(store.get('capacity'))} used, "
              f"{_fmt_bytes(store.get('pinned_bytes'))} pinned, "
              f"{_fmt_bytes(store.get('spilled_bytes'))} spilled "
              f"({store.get('spill_count', 0)} spills, "
              f"{store.get('restore_count', 0)} restores)"
              f"{pressure}")
    print(f"\n{len(summary['objects'])} object refs, "
          f"{_fmt_bytes(summary['total_owned_bytes'])} owned")
    header = (f"{'OBJECT ID':<18} {'NODE':<14} {'PID':<7} {'SIZE':>10} "
              f"{'KIND':<24} {'BORROWERS':>9}  CALLSITE")
    print(header)
    print("-" * len(header))
    for obj in summary["objects"][:args.limit]:
        site = obj.get("callsite") or "-"
        if len(site) > 60:
            site = "..." + site[-57:]
        print(f"{obj['object_id'][:16]:<18} "
              f"{(obj.get('node_id') or '?')[:12]:<14} "
              f"{obj.get('pid') or '?':<7} "
              f"{_fmt_bytes(obj.get('size')):>10} "
              f"{obj.get('kind', '?'):<24} "
              f"{obj.get('borrowers', 0):>9}  {site}")
    if summary["by_callsite"]:
        print("\ntop owner callsites by bytes:")
        for agg in summary["by_callsite"]:
            print(f"  {_fmt_bytes(agg['total_bytes']):>10}  "
                  f"x{agg['count']:<5} {agg['callsite']}")
    if summary.get("leak_heuristic_skipped"):
        print("\nleak heuristic skipped: some owner reports were "
              "unreachable or truncated")
    if summary["leaked"]:
        print(f"\nPOSSIBLE LEAKS ({len(summary['leaked'])} store objects "
              "with no owner reference):")
        for obj in summary["leaked"][:20]:
            print(f"  {obj['object_id'][:16]}  "
                  f"{_fmt_bytes(obj.get('size'))}  "
                  f"node {(obj.get('node_id') or '?')[:12]}"
                  f"{'  (spilled)' if obj.get('spilled') else ''}")
    if summary["errors"]:
        errs = json.dumps(summary["errors"], default=str)
        print(f"\nunreachable: {errs}")


def cmd_events(args):
    """Render the GCS cluster event log (node/actor/job transitions,
    SPILL/RESTORE, MEMORY_PRESSURE)."""
    _connect(args)
    from ray_tpu.util import state as st
    events = st.list_events(event_type=args.type, limit=args.limit)
    if args.json:
        print(json.dumps(events, indent=1, default=str))
        return
    if not events:
        print("no events recorded")
        return
    for ev in events:
        stamp = time.strftime("%H:%M:%S", time.localtime(ev["ts"]))
        print(f"{stamp}  {ev['severity']:<7} {ev['type']:<18} "
              f"{ev.get('message', '')}")


def cmd_timeline(args):
    _connect(args)
    from ray_tpu.util import state as st
    if getattr(args, "train", False):
        trace = st.train_timeline(args.output)
        tracks = sorted({row["pid"] for row in trace})
        print(f"wrote {len(trace)} train spans across "
              f"{len(tracks)} tracks ({', '.join(map(str, tracks))}) "
              f"to {args.output}")
        return
    if getattr(args, "serve", False):
        trace = st.serve_timeline(args.output)
        tracks = sorted({row["tid"] for row in trace if "tid" in row})
        print(f"wrote {len(trace)} serve spans across "
              f"{len(tracks)} requests to {args.output}")
        return
    trace = st.timeline(args.output)
    print(f"wrote {len(trace)} spans to {args.output}")


def cmd_requests(args):
    """Render the serve-plane request observatory: percentile folds over
    every traced request (optionally grouped by tenant/route), or one
    request's `why_slow` latency-attribution report with --why."""
    _connect(args)
    from ray_tpu.util import state as st
    if args.why:
        report = st.why_slow(args.why)
        if args.json:
            print(json.dumps(report, indent=1, default=str))
            return
        if "error" in report:
            print(report["error"])
            return
        print(f"request {report['request_id']}  "
              f"outcome={report.get('outcome') or 'in-flight'}"
              + (f"  tenant={report['tenant']}"
                 if report.get("tenant") else "")
              + (f"  route={report['route']}"
                 if report.get("route") else ""))
        for horizon in ("ttft", "e2e"):
            total = report.get(f"{horizon}_s")
            buckets = report.get(f"{horizon}_buckets")
            if total is None or not buckets:
                continue
            print(f"  {horizon}: {total:.4f}s")
            for name, sec in sorted(buckets.items(),
                                    key=lambda kv: -kv[1]):
                if sec <= 0:
                    continue
                print(f"    {name:<16} {sec:>9.4f}s "
                      f"({100.0 * sec / total if total else 0:5.1f}%)")
        if report.get("preemptions"):
            print(f"  preemptions: {report['preemptions']}")
        for ev in report.get("events", []):
            args_s = " ".join(
                f"{k}={v}" for k, v in sorted(ev.items())
                if k not in ("event", "t_s"))
            print(f"  +{ev['t_s']:>8.4f}s  {ev['event']:<14} {args_s}")
        return
    by = "tenant" if args.by_tenant else (
        "route" if args.by_route else None)
    fold = st.serve_requests(by=by)
    if args.json:
        print(json.dumps(fold, indent=1, default=str))
        return
    groups = fold["groups"]
    if not groups:
        print("no requests traced")
        return
    label = fold.get("by") or "all"
    print(f"{label:<18} reqs  done fail  preempt   park_s "
          f"ttft_p50  ttft_p95   e2e_p50   e2e_p95")
    for key in sorted(groups):
        g = groups[key]

        def _f(v):
            return f"{v:>8.4f}" if v is not None else "       -"
        print(f"{key:<18} {g['requests']:>4} {g['finished']:>5} "
              f"{g['failed']:>4} {g['preemptions']:>8} "
              f"{g['park_s_total']:>8.3f} "
              f"{_f(g['ttft_p50_s'])}  {_f(g['ttft_p95_s'])}  "
              f"{_f(g['e2e_p50_s'])}  {_f(g['e2e_p95_s'])}")


def cmd_stragglers(args):
    """Render the straggler/skew view: STRAGGLER_DETECTED events plus
    the per-track (rank/stage) rolling step-time fold."""
    _connect(args)
    from ray_tpu.util import state as st
    view = st.stragglers(limit=args.limit)
    if args.json:
        print(json.dumps(view, indent=1, default=str))
        return
    stats = view["step_stats"]
    if stats:
        print("track       steps  mean_step_s  last_step_s")
        for track in sorted(stats):
            row = stats[track]
            print(f"{track:<10} {row['steps']:>6} "
                  f"{row['mean_step_s']:>12.4f} {row['last_s']:>12.4f}")
    if not view["events"]:
        print("no stragglers detected")
        return
    print()
    for ev in view["events"]:
        stamp = time.strftime("%H:%M:%S", time.localtime(ev["ts"]))
        print(f"{stamp}  rank {ev.get('rank')}  "
              f"phase={ev.get('phase', '?')}  "
              f"wait={ev.get('wait_s', 0):.3f}s "
              f"(median of peers {ev.get('median_others_s', 0):.3f}s, "
              f"seen by rank {ev.get('observer_rank')} "
              f"x{ev.get('consecutive_ops')} ops)")


def cmd_alerts(args):
    """Render the GCS SLO alert table (what the alert engine fired)."""
    _connect(args)
    from ray_tpu.util import state as st
    rows = st.alerts(rule=args.rule, severity=args.severity,
                     limit=args.limit)
    if args.json:
        print(json.dumps(rows, indent=1, default=str))
        return
    if not rows:
        print("no alerts fired")
        return
    for row in rows:
        stamp = time.strftime("%H:%M:%S", time.localtime(row["ts"]))
        print(f"{stamp}  {row['severity']:<8} {row['rule']:<22} "
              f"{row.get('message', '')}")


def cmd_trace(args):
    """Print one trace's span tree (or list recent traces with no id).
    --logs interleaves each execution span's captured log lines (by the
    task id the span carries) under its node."""
    _connect(args)
    from ray_tpu.util import state as st
    if not args.trace_id:
        rows = st.list_traces(limit=args.limit)
        if args.json:
            print(json.dumps(rows, indent=1, default=str))
            return
        for row in rows:
            print(f"{row['trace_id']}  {row['name'] or '?':24s} "
                  f"spans={row['num_spans']} "
                  f"procs={row['num_processes']} "
                  f"dur={row['duration_s']:.3f}s")
        if not rows:
            print("no traces recorded")
        return
    tree = st.get_trace(args.trace_id)
    if args.json:
        print(json.dumps(tree, indent=1, default=str))
        return
    if not tree["num_spans"]:
        print(f"no spans recorded for trace {args.trace_id}")
        raise SystemExit(1)
    print(f"trace {tree['trace_id']}: {tree['num_spans']} spans across "
          f"{tree['num_processes']} processes")

    lines_by_task = {}
    if getattr(args, "logs", False):
        # ONE cluster sweep serves the whole tree; lines group by the
        # task id each execution span carries.
        for line in st.get_logs(limit=10_000)["lines"]:
            if line.get("task"):
                lines_by_task.setdefault(line["task"], []).append(line)

    def _render(node, depth):
        print(f"{'  ' * depth}- {node['name']}  "
              f"[{node['duration_s'] * 1e3:.1f}ms pid={node['pid']} "
              f"span={node['span_id'][:8]}]")
        for line in lines_by_task.get(node.get("task_id") or "", ()):
            stamp = time.strftime("%H:%M:%S",
                                  time.localtime(line["ts"]))
            print(f"{'  ' * (depth + 1)}| {stamp} "
                  f"[{line.get('level') or '?'}] {line['line']}")
        for child in node["children"]:
            _render(child, depth + 1)
    for root in tree["roots"]:
        _render(root, 0)


def cmd_logs(args):
    """Cluster log search/tail over the per-worker rings (reference:
    `ray logs` + the dashboard log view): works with log_to_driver OFF
    — retention lives at the raylets, not in driver stdout."""
    _connect(args)
    from ray_tpu.util import state as st

    def _print_batch(batch):
        for line in batch["lines"]:
            stamp = time.strftime("%H:%M:%S",
                                  time.localtime(line["ts"]))
            who = f"node{line.get('node_index', '?')} " \
                  f"pid={line.get('pid', '?')}"
            task = f" task={line['task'][:12]}" if line.get("task") else ""
            actor = f" actor={line['actor'][:12]}" \
                if line.get("actor") else ""
            print(f"{stamp} [{who}{task}{actor} "
                  f"{line.get('level') or '?'}] {line['line']}")

    if args.follow:
        try:
            for batch in st.tail_logs(task=args.task, actor=args.actor,
                                      job=args.job, node_id=args.node,
                                      level=args.level, grep=args.grep):
                _print_batch(batch)
        except KeyboardInterrupt:
            return
        return
    result = st.get_logs(task=args.task, actor=args.actor, job=args.job,
                         node_id=args.node, level=args.level,
                         grep=args.grep, tail=args.tail,
                         limit=args.limit)
    if args.json:
        print(json.dumps(result, indent=1, default=str))
        return
    if result.get("disabled"):
        print("log plane disabled (RTPU_NO_LOG_PLANE) on some nodes")
    _print_batch(result)
    extras = []
    if result["dropped"]:
        extras.append(f"{result['dropped']} lines dropped (ring "
                      "overflow)")
    if result["errors"]:
        extras.append(f"unreachable: "
                      f"{json.dumps(result['errors'], default=str)}")
    if extras:
        print("-- " + "; ".join(extras))


def cmd_profile(args):
    """Cluster-wide CPU profile (reference: the reporter agent's py-spy
    routing, fleet-merged): sample every process for --duration at
    --hz, print top-N task/actor/frame attribution, and emit the merged
    flamegraph as collapsed stacks or speedscope JSON."""
    _connect(args)
    from ray_tpu.util import state as st
    report = st.profile_cluster(
        duration_s=args.duration, hz=args.hz, node_id=args.node,
        pid=args.pid, task=args.task, top=args.top)

    def _emit(text: str):
        if args.output:
            with open(args.output, "w") as f:
                f.write(text)
            print(f"wrote {len(text)} bytes to {args.output}")
        else:
            print(text)

    if args.format == "json":
        _emit(json.dumps(report, indent=1, default=str))
        return
    if args.format == "speedscope":
        _emit(json.dumps(report["speedscope"], default=str))
        return
    if args.format == "collapsed":
        _emit(report["collapsed"])
        return
    # table (default): capture summary + attribution tables
    print(f"sampled {report['num_samples']} stacks across "
          f"{report['num_processes']} processes "
          f"({report['duration_s']:g}s @ {report['hz']:g}Hz)")
    ex = report["executor"]
    if ex["running"] or ex["idle"]:
        busy = ex["running"] / (ex["running"] + ex["idle"]) * 100
        print(f"executor threads: {ex['running']} running / "
              f"{ex['idle']} idle samples ({busy:.0f}% busy)")
    for title, key, label in (("top tasks by sampled CPU", "by_task",
                               "name"),
                              ("top actor classes", "by_actor", "actor"),
                              ("top frames (self)", "by_frame", "frame")):
        rows = report["top"][key]
        if not rows:
            continue
        print(f"\n{title}:")
        for agg in rows:
            extra = f"  task={agg['task'][:12]}" if key == "by_task" \
                else ""
            print(f"  {agg['cpu_s']:>8.3f}s  x{agg['samples']:<6} "
                  f"{agg.get(label) or '?'}{extra}")
    if report["errors"]:
        print(f"\nunreachable/refused: "
              f"{json.dumps(report['errors'], default=str)}")
    if args.output:
        with open(args.output, "w") as f:
            f.write(report["collapsed"])
        print(f"\ncollapsed flamegraph written to {args.output}")


def cmd_stack(args):
    """One-shot stack dump of every worker/raylet/GCS/driver in the
    fleet (reference: `ray stack`, fleet-scoped)."""
    _connect(args)
    from ray_tpu.util import state as st
    rows = st.stack_cluster(node_id=args.node)
    if args.json:
        print(json.dumps(rows, indent=1, default=str))
        return
    dumped = 0
    for row in rows:
        where = f"node {(row.get('node_id') or '?')[:12]} " \
            f"pid {row.get('pid') or '?'} ({row.get('component', '?')})"
        if row.get("error"):
            print(f"==== {where}: UNREACHABLE: {row['error']}")
            continue
        dumped += 1
        print(f"==== {where} " + "=" * 20)
        print(row.get("text", ""))
    print(f"dumped {dumped} processes "
          f"({sum(1 for r in rows if r.get('error'))} unreachable)")


def _print_extents(row):
    """A step kind's distribution of extents, its dry-device account and
    its newest slow steps (accel plane: `extent_hist`, `dry_by_phase`,
    `slow`), each with its largest phase and the parts of it that were
    timed apart."""
    from ray_tpu._internal import accel
    hist = row["extent_hist"]
    p50, p99, top = (accel.extent_quantile(hist, q) * 1e3
                     for q in (0.5, 0.99, 1.0))
    print(f"    extent p50={p50:.1f}ms p99={p99:.1f}ms max<={top:.0f}ms "
          f"· slow {row['slow_total']} ({row['slow_seconds']:.2f}s)")
    dry = row.get("dry_by_phase")
    if dry:
        # the device out of work while the owner had some (`DryWatch`)
        seconds = sum(dry.values())
        extents = row["wall_s"] + row["phases"].get("between", 0.0)
        worst = ", ".join(f"{phase} {value:.2f}" for phase, value in sorted(
            dry.items(), key=lambda kv: -kv[1])[:3])
        print(f"    dry {seconds:.2f}s ({100.0 * seconds / extents:.1f}%) in "
              f"{row['counters'].get('dry_dispatches', 0):.0f} of "
              f"{row['counters'].get('dispatches', 0):.0f} dispatches, "
              f"longest {row['dry_gap_max_s'] * 1e3:.0f}ms · {worst}")
    now = row.get("now") or time.monotonic()
    for step in row["slow"][-5:]:
        phase, seconds = max(step["phases"].items(), key=lambda kv: kv[1],
                             default=("-", 0.0))
        # the pieces of that phase timed apart (`StepTimer.part`)
        parts = ", ".join(
            f"{name[len(phase) + 1:-2]} {value * 1e3:.1f}"
            for name, value in step["counters"].items()
            if name.startswith(phase + "_") and name.endswith("_s"))
        pauses = ", ".join(f"{p['what']} {p['seconds'] * 1e3:.0f}ms"
                           for p in step["pauses"]) or "none stamped"
        print(f"      {now - step['end']:>8.1f}s ago "
              f"{step['extent_s'] * 1e3:>7.1f}ms "
              f"(usual {step['typical_s'] * 1e3:.1f}) "
              f"{phase}={seconds * 1e3:.1f}ms"
              + (f" ({parts})" if parts else "") + f"  pauses: {pauses}")


def cmd_devices(args):
    """Cluster accelerator report (the device leg of memory/profile):
    per-device HBM used/peak/limit, XLA compile totals + top compiled
    functions, and step/MFU telemetry per process."""
    _connect(args)
    from ray_tpu.util import state as st
    summary = st.accel_summary()
    if args.json:
        print(json.dumps(summary, indent=1, default=str))
        return
    comp = summary["compile"]
    print(f"devices: {len(summary['devices'])} across "
          f"{len(summary['nodes'])} nodes · compiles {comp['compiles']} "
          f"({comp['compile_seconds']:.2f}s, "
          f"cache {comp['cache_hits']} hit / "
          f"{comp['cache_misses']} miss)")
    header = (f"{'NODE':<14} {'PID':<7} {'DEV':<4} {'KIND':<14} "
              f"{'HBM USED':>10} {'PEAK':>10} {'LIMIT':>10}  SOURCE")
    print(header)
    print("-" * len(header))
    for dev in summary["devices"]:
        print(f"{(dev.get('node_id') or '?')[:12]:<14} "
              f"{dev.get('pid') or '?':<7} "
              f"{dev['index']:<4} {dev['device_kind'][:14]:<14} "
              f"{_fmt_bytes(dev['hbm_used_bytes']):>10} "
              f"{_fmt_bytes(dev['hbm_peak_bytes']):>10} "
              f"{_fmt_bytes(dev['hbm_limit_bytes']):>10}  "
              f"{dev['source']}")
    if summary["steps"]:
        print("\nstep telemetry (per process, per kind):")
        for row in summary["steps"]:
            print(f"  {row['kind']:<14} pid {row.get('pid') or '?':<7} "
                  f"steps={int(row['steps'])} "
                  f"mean={row['mean_step_s'] * 1e3:.2f}ms "
                  f"tok/s={row['tokens_per_s']:.1f} "
                  f"mfu={row['mfu'] * 100:.1f}% "
                  f"goodput compile/device/host="
                  f"{row['compile_s']:.2f}/{row['device_s']:.2f}/"
                  f"{row['host_s']:.2f}s")
            if row.get("extent_hist"):
                _print_extents(row)
    top_fns = []
    for proc in summary["processes"]:
        top_fns.extend((proc.get("compile") or {}).get("per_function", ()))
    top_fns.sort(key=lambda r: -r["seconds"])
    if top_fns:
        print("\ntop compiled functions by backend-compile seconds:")
        for fn in top_fns[:10]:
            print(f"  {fn['seconds']:>8.3f}s  x{fn['count']:<4} "
                  f"{fn['function']}")
    if summary["errors"]:
        print(f"\nunreachable: "
              f"{json.dumps(summary['errors'], default=str)}")


def cmd_dashboard(args):
    _connect(args)
    from ray_tpu.dashboard import start_dashboard
    print(start_dashboard())


def cmd_submit(args):
    _connect(args)
    from ray_tpu.job_submission import JobManager, JobStatus
    import shlex
    manager = JobManager()
    entrypoint = shlex.join(args.entrypoint)
    submission_id = manager.submit_job(entrypoint=entrypoint)
    print(f"submitted {submission_id}")
    if args.wait:
        status = manager.wait_until_finished(submission_id,
                                             timeout_s=args.timeout)
        print(manager.get_job_logs(submission_id), end="")
        print(f"job {submission_id}: {status}")
        if status != JobStatus.SUCCEEDED:
            raise SystemExit(1)


def cmd_job(args):
    _connect(args)
    from ray_tpu.job_submission import JobManager
    manager = JobManager()
    if args.action == "list":
        print(json.dumps(manager.list_jobs(), indent=1, default=str))
    elif args.action == "logs":
        print(manager.get_job_logs(args.id), end="")
    elif args.action == "stop":
        print("stopped" if manager.stop_job(args.id) else "not running")


def cmd_drain(args):
    """Graceful node drain (`cli drain <node-prefix>`): fence new lease
    grants, migrate actors, wait for in-flight work up to the deadline
    — the rolling-upgrade / scale-in primitive."""
    _connect(args)
    from ray_tpu.util.state import api as state_api
    report = state_api.drain_node(
        args.node, timeout_s=args.timeout, exit_process=args.exit,
        cancel=args.cancel)
    print(json.dumps(report, indent=1, default=str))
    if report.get("error"):
        raise SystemExit(1)


def cmd_rollout(args):
    """Rolling restart (`cli rollout`): drain every non-head node one
    by one (each with exit_process so a supervised raylet restarts
    clean) and wait for a replacement to register before moving on —
    the cluster keeps serving throughout. The head restart itself rides
    the PR-10 incarnation reconnect-and-replay path (restart the GCS
    process out-of-band; clients re-register automatically)."""
    _connect(args)
    import time as _time
    from ray_tpu.util.state import api as state_api
    targets = [n for n in state_api.list_nodes()
               if n["state"] == "ALIVE" and not n["is_head"]]
    if not targets:
        print("no non-head nodes to roll")
        return
    for i, node in enumerate(targets):
        nid = node["node_id"]
        print(f"[{i + 1}/{len(targets)}] draining node {nid[:12]} "
              f"(index {node['node_index']})...")
        report = state_api.drain_node(nid, timeout_s=args.timeout,
                                      exit_process=True)
        print(f"  drained in {report.get('elapsed_s', 0):.2f}s, "
              f"migrated {len(report.get('migrated_actors', ()))} "
              f"actor(s), "
              f"{len(report.get('stragglers_killed', ()))} straggler(s)"
              + (f"; ERROR {report['error']}"
                 if report.get("error") else ""))
        if report.get("error"):
            raise SystemExit(1)
        if args.no_wait:
            continue
        # Wait for the replacement (a supervisor restarting the raylet)
        # to re-register before rolling the next node, so capacity never
        # dips by more than one node.
        before = {n["node_id"] for n in targets} | \
            {n["node_id"] for n in state_api.list_nodes()}
        deadline = _time.monotonic() + args.rejoin_timeout
        while _time.monotonic() < deadline:
            fresh = [n for n in state_api.list_nodes()
                     if n["state"] == "ALIVE"
                     and n["node_id"] not in before]
            if fresh:
                print(f"  replacement node {fresh[0]['node_id'][:12]} "
                      "registered")
                break
            _time.sleep(0.5)
        else:
            print("  (no replacement registered within "
                  f"{args.rejoin_timeout:.0f}s — is a supervisor "
                  "restarting the raylet? continuing)")
    print("rollout complete")


def cmd_chaos(args):
    """Fault-injection drills (the deterministic chaos harness,
    _internal/chaos.py): arm/disarm RPC fault rules cluster-wide, show
    the GCS's failover status, and kill processes for failover tests."""
    _connect(args)
    from ray_tpu.util.state import api as state_api
    if args.action == "show":
        info = state_api.gcs_info()
        from ray_tpu._internal.chaos import REGISTRY
        out = {"gcs": info, "local_rules": [vars(r) for r in
                                           REGISTRY.active_rules()],
               "local_schedule": REGISTRY.schedule_status(),
               "local_hits": REGISTRY.hit_counts()}
        if args.json:
            print(json.dumps(out, indent=2, default=str))
        else:
            print(f"gcs incarnation {info['incarnation']} "
                  f"(pid {info['pid']}, persist={info['persist_mode']}, "
                  f"wal={info['wal_bytes']}B, "
                  f"failovers={info['failovers']})")
            for r in out["local_rules"]:
                print(f"  rule {r['pattern']}:{r['action']}"
                      f":{r['prob']}" + (f":{r['param']}"
                                         if r["param"] else ""))
            for s in out["local_schedule"]:
                state = "ACTIVE" if s["active"] else "armed"
                print(f"  sched t+{s['at_s']:g}s {s['pattern']}:"
                      f"{s['action']}:{s['prob']:g}"
                      + (f":{s['param']:g}" if s["param"] else "")
                      + f"  [{state}, t={s['elapsed_s']:g}s]")
            for site, n in out["local_hits"].items():
                print(f"  hits {site}: {n}")
    elif args.action == "set":
        if not args.spec and not args.schedule:
            raise SystemExit("chaos set requires --spec "
                             "(method:action:prob[:param],...) and/or "
                             "--schedule (at_s:method:action:prob"
                             "[:param],...)")
        rows = state_api.set_chaos(spec=args.spec, seed=args.seed,
                                   schedule=args.schedule or None)
        for row in rows:
            print(row)
    elif args.action == "clear":
        for row in state_api.set_chaos(spec="", seed=0, schedule=""):
            print(row)
    elif args.action == "kill-gcs":
        info = state_api.gcs_info()
        print(f"SIGKILLing gcs incarnation {info['incarnation']} "
              f"(pid {info['pid']})...")
        try:
            state_api._gcs().call_sync("chaos_kill_self", timeout=10)
        except Exception as e:  # noqa: BLE001 — death races the reply
            print(f"(kill call returned {e!r})")
    elif args.action == "kill-worker":
        import ray_tpu
        from ray_tpu._internal.core_worker import get_core_worker
        cw = get_core_worker()
        for node in ray_tpu.nodes():
            if args.node and not node["node_id"].startswith(args.node):
                continue
            ok = cw.run_sync(cw.clients.get(tuple(node["address"])).call(
                "chaos_kill_worker", worker_hex=args.worker or "",
                pid=args.pid, timeout=10), timeout=15)
            print(f"node {node['node_id'][:12]}: {ok}")
            if ok:
                break
    else:
        raise SystemExit(f"unknown chaos action {args.action!r}")


def cmd_rpc(args):
    """Transport observatory (`state.rpc_summary()`): per-method client
    latency percentiles + error rates, retry/chaos counters, per-ring
    native stats, and every process's slow-RPC ring."""
    _connect(args)
    from ray_tpu.util import state as st
    summary = st.rpc_summary()
    if args.json:
        print(json.dumps(summary, indent=1, default=str))
        return

    def _ms(v):
        return f"{v * 1000:.2f}ms" if v is not None else "-"

    methods = summary["methods"]
    if args.method:
        methods = [m for m in methods if args.method in m["method"]]
    print(f"methods: {len(methods)} "
          f"(client latency, 1/64-sampled + every slow call)")
    for m in methods:
        print(f"  {m['method']:<24s} n={m['sampled']:<6d} "
              f"p50={_ms(m['p50_s'])} p95={_ms(m['p95_s'])} "
              f"p99={_ms(m['p99_s'])} errors={m['transport_errors']:g}")
    if summary["retries_by_site"]:
        print("retries:")
        for site, n in sorted(summary["retries_by_site"].items()):
            print(f"  {site}: {n:g}")
    if summary["chaos_hits"]:
        print("chaos hits:")
        for pattern, n in sorted(summary["chaos_hits"].items()):
            print(f"  {pattern}: {n:g}")
    if summary["rings"]:
        print("native rings:")
        for r in summary["rings"]:
            print(f"  pid={r['pid']} ring={r['ring']}  "
                  f"depth={r.get('queue_depth', 0):g} "
                  f"hwm={r.get('depth_hwm', 0):g}  "
                  f"frames in/out={r.get('frames_in', 0):g}/"
                  f"{r.get('frames_out', 0):g}")
    processes = summary["processes"]
    if args.node:
        processes = [p for p in processes
                     if (p.get("node_id") or "").startswith(args.node)]
    for p in processes:
        if "error" in p:
            print(f"process {p.get('node_id') or p.get('job_id')}: "
                  f"unreachable ({p['error']})")
            continue
        print(f"process pid={p.get('pid')} mode={p.get('mode', '?')} "
              f"errors={p.get('transport_errors', 0):g} "
              f"retries={p.get('retries', 0):g} "
              f"slow={p.get('slow_total', 0)}")
        if args.slow:
            for row in p.get("slow", ()):
                print(f"    {row['method']:<20s} "
                      f"{row['duration_s'] * 1000:8.1f}ms  "
                      f"peer={row['peer']}  site={row['site']}")


def cmd_perf(args):
    from ray_tpu import perf
    perf.main(quick=args.quick)


def cmd_lint(args):
    """rtpulint: project-specific static analysis (per-file rules
    L001-L010 plus cross-module A001-A003/J001-J003, burn-down
    allowlist). Exit codes: 0 clean, 1 violations or a stale/malformed
    allowlist entry, 2 usage/environment error (--changed without a
    usable git checkout)."""
    from ray_tpu._internal import lint
    raise SystemExit(lint.main(
        (["--json"] if args.json else [])
        + (["--no-allowlist"] if args.no_allowlist else [])
        + (["--changed"] if args.changed else [])))


def cmd_serve(args):
    """`serve deploy/status/shutdown` (reference: serve/scripts.py —
    the config-file production deploy path)."""
    import ray_tpu
    ray_tpu.init(address=_resolve_address(getattr(args, "address", None)))
    from ray_tpu import serve as serve_api
    if args.action == "deploy":
        if not args.config:
            raise SystemExit("serve deploy requires a config file path")
        from ray_tpu.serve.config_file import deploy_config
        names = deploy_config(args.config)
        print(f"deployed applications: {', '.join(names)}")
        print(f"http: {serve_api.get_http_address()}")
    elif args.action == "status":
        import json as _json
        print(_json.dumps(serve_api.status(), indent=2, default=str))
    elif args.action == "shutdown":
        serve_api.shutdown()
        print("serve shut down")


def main(argv=None):
    parser = argparse.ArgumentParser(prog="ray_tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("start")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--dashboard", action="store_true")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("up")
    p.add_argument("config")
    p.add_argument("--validate-only", action="store_true")
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("down")
    p.add_argument("config")
    p.set_defaults(fn=cmd_down)

    p = sub.add_parser("stop")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("status")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("list")
    p.add_argument("what", choices=["nodes", "actors", "tasks",
                                    "placement_groups", "objects",
                                    "workers", "jobs"])
    p.add_argument("--limit", type=int, default=100)
    p.add_argument("--address")
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("memory")
    p.add_argument("--json", action="store_true")
    p.add_argument("--limit", type=int, default=50)
    p.add_argument("--address")
    p.set_defaults(fn=cmd_memory)

    p = sub.add_parser("events")
    p.add_argument("--type", default=None)
    p.add_argument("--json", action="store_true")
    p.add_argument("--limit", type=int, default=100)
    p.add_argument("--address")
    p.set_defaults(fn=cmd_events)

    p = sub.add_parser("timeline")
    p.add_argument("--output", default="timeline.json")
    p.add_argument("--train", action="store_true",
                   help="cross-rank train-step timeline (steptrace) "
                        "instead of the task timeline")
    p.add_argument("--serve", action="store_true",
                   help="serve-plane per-request lifecycle timeline "
                        "(reqtrace) instead of the task timeline")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser("requests")
    p.add_argument("--by-tenant", action="store_true",
                   help="group percentile folds by tenant label")
    p.add_argument("--by-route", action="store_true",
                   help="group percentile folds by serve route")
    p.add_argument("--why", default=None, metavar="REQUEST_ID",
                   help="latency-attribution report for one request "
                        "(unique id prefix ok)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_requests)

    p = sub.add_parser("stragglers")
    p.add_argument("--json", action="store_true")
    p.add_argument("--limit", type=int, default=100)
    p.add_argument("--address")
    p.set_defaults(fn=cmd_stragglers)

    p = sub.add_parser("alerts")
    p.add_argument("--rule", default=None)
    p.add_argument("--severity", default=None)
    p.add_argument("--json", action="store_true")
    p.add_argument("--limit", type=int, default=100)
    p.add_argument("--address")
    p.set_defaults(fn=cmd_alerts)

    p = sub.add_parser("trace")
    p.add_argument("trace_id", nargs="?")
    p.add_argument("--json", action="store_true")
    p.add_argument("--logs", action="store_true",
                   help="interleave captured log lines under each "
                        "execution span (by task id)")
    p.add_argument("--limit", type=int, default=20)
    p.add_argument("--address")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser("logs")
    p.add_argument("--task", default=None,
                   help="task id (hex prefix)")
    p.add_argument("--actor", default=None,
                   help="actor id (hex prefix)")
    p.add_argument("--job", default=None, help="job id (hex)")
    p.add_argument("--node", default=None,
                   help="restrict to one node (id prefix)")
    p.add_argument("--level", default=None,
                   help="minimum level (DEBUG/INFO/WARNING/ERROR)")
    p.add_argument("--grep", default=None, help="regex over messages")
    p.add_argument("--tail", type=int, default=None,
                   help="last N lines after the merge")
    p.add_argument("--follow", "-f", action="store_true",
                   help="poll for new lines (cursor-based)")
    p.add_argument("--limit", type=int, default=1000)
    p.add_argument("--json", action="store_true")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser("profile")
    p.add_argument("--duration", type=float, default=5.0)
    p.add_argument("--hz", type=float, default=None,
                   help="sampling rate (default: CONFIG.profiler_hz)")
    p.add_argument("--format", choices=["table", "collapsed",
                                        "speedscope", "json"],
                   default="table")
    p.add_argument("--output", "-o", default=None)
    p.add_argument("--node", default=None,
                   help="restrict to one node (id prefix)")
    p.add_argument("--pid", type=int, default=None,
                   help="restrict to one process")
    p.add_argument("--task", default=None,
                   help="restrict to one task (id prefix or exact name)")
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--address")
    p.set_defaults(fn=cmd_profile)

    p = sub.add_parser("stack")
    p.add_argument("--node", default=None,
                   help="restrict to one node (id prefix)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_stack)

    p = sub.add_parser("devices")
    p.add_argument("--json", action="store_true")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_devices)

    p = sub.add_parser("dashboard")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser("submit")
    p.add_argument("--wait", action="store_true")
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("--address")
    p.add_argument("entrypoint", nargs="+")
    p.set_defaults(fn=cmd_submit)

    p = sub.add_parser("job")
    p.add_argument("action", choices=["list", "logs", "stop"])
    p.add_argument("id", nargs="?")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_job)

    p = sub.add_parser(
        "drain",
        help="gracefully drain one node: fence leases, migrate actors, "
             "wait for in-flight work")
    p.add_argument("node", help="node id (hex prefix)")
    p.add_argument("--timeout", type=float, default=None,
                   help="drain deadline seconds (default: "
                        "CONFIG.drain_timeout_s); stragglers past it "
                        "are postmortem-tag killed")
    p.add_argument("--exit", action="store_true",
                   help="ask a standalone raylet to exit clean after "
                        "the drain (rolling-restart primitive)")
    p.add_argument("--cancel", action="store_true",
                   help="lower the fence instead (abort a drain)")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_drain)

    p = sub.add_parser(
        "rollout",
        help="rolling restart: drain+exit every non-head node one by "
             "one, waiting for replacements between nodes")
    p.add_argument("--timeout", type=float, default=None,
                   help="per-node drain deadline seconds")
    p.add_argument("--rejoin-timeout", type=float, default=60.0,
                   help="how long to wait for a replacement node "
                        "before rolling the next one")
    p.add_argument("--no-wait", action="store_true",
                   help="do not wait for replacements (drain-only "
                        "sweep)")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_rollout)

    p = sub.add_parser(
        "chaos",
        help="fault-injection drills: arm/disarm rpc chaos rules, "
             "show failover status, kill the GCS or a worker")
    p.add_argument("action",
                   choices=["show", "set", "clear", "kill-gcs",
                            "kill-worker"])
    p.add_argument("--address")
    p.add_argument("--spec", default="",
                   help="method:action:prob[:param],... with actions "
                        "drop_req|drop_resp|delay|dup")
    p.add_argument("--schedule", default="",
                   help="time-scheduled script at_s:method:action:prob"
                        "[:param],... — each entry arms at_s seconds "
                        "after set; a later entry for the same "
                        "method:action replaces the earlier one")
    p.add_argument("--seed", type=int, default=0,
                   help="chaos RNG seed (0 = process-random)")
    p.add_argument("--pid", type=int, default=0,
                   help="kill-worker: worker pid")
    p.add_argument("--worker", default="",
                   help="kill-worker: worker id hex prefix")
    p.add_argument("--node", default="",
                   help="kill-worker: restrict to one node id prefix")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_chaos)

    p = sub.add_parser(
        "rpc",
        help="transport observatory: per-method latency percentiles, "
             "retry/error rates, native-ring stats, slow-RPC ring")
    p.add_argument("--address")
    p.add_argument("--method", default="",
                   help="filter the method table by substring")
    p.add_argument("--node", default="",
                   help="restrict process rows to one node id prefix")
    p.add_argument("--slow", action="store_true",
                   help="print each process's slow-RPC ring (method, "
                        "duration, peer, creation site)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_rpc)

    p = sub.add_parser("perf")
    p.add_argument("--quick", action="store_true")
    p.set_defaults(fn=cmd_perf)

    p = sub.add_parser("lint")
    p.add_argument("--json", action="store_true")
    p.add_argument("--no-allowlist", action="store_true")
    p.add_argument("--changed", action="store_true",
                   help="only report violations in files changed vs HEAD")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("serve")
    p.add_argument("action", choices=["deploy", "status", "shutdown"])
    p.add_argument("config", nargs="?")
    p.add_argument("--address")
    p.set_defaults(fn=cmd_serve)

    args = parser.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
