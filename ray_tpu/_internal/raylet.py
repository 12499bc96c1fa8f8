"""Raylet: the per-node scheduling and data plane.

Equivalent of the reference raylet (src/ray/raylet/): worker-lease
scheduling with spillback, a worker pool of language workers, placement-group
bundle accounting with two-phase prepare/commit, the local object manager
(eviction, spill/restore, remote pulls via chunked transfer — the role of
plasma's PullManager/PushManager over object_manager.proto), node heartbeats
carrying the resource view, and worker liveness supervision.

One raylet per node. In local mode it runs inside the driver process on the
shared io loop; `cluster_utils.Cluster.add_node` runs additional raylets as
subprocesses for multi-node semantics on one machine (reference:
python/ray/cluster_utils.py).
"""

from __future__ import annotations

import asyncio
import collections
import logging
import os
import random
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from . import aio
from .backoff import Backoff
from .config import CONFIG
from .ids import NodeID, ObjectID, PlacementGroupID, WorkerID
from . import logplane
from .memory_store import MemoryStore
from .plasma import PlasmaDir
from .resources import NodeResources, ResourceSet
from .rpc import Address, ClientPool, RpcServer
from .scheduling_policy import NodeView
from . import scheduling_policy

logger = logging.getLogger(__name__)

HEARTBEAT_INTERVAL_S = 0.2


@dataclass
class WorkerHandle:
    worker_id: bytes
    address: Optional[Address] = None
    pid: int = 0
    proc: Optional[subprocess.Popen] = None
    state: str = "STARTING"         # STARTING | IDLE | LEASED | DEAD
    env_key: Tuple = ()
    lease_id: Optional[int] = None
    registered: Optional[asyncio.Future] = None
    last_idle: float = 0.0
    is_actor_worker: bool = False
    job_hex: Optional[str] = None  # last-leased job (log-stream routing)
    # Set when the RAYLET delivered the kill (memory watchdog): the
    # postmortem taxonomy then reports OOM_KILLED with certainty
    # instead of guessing at a foreign SIGKILL.
    kill_reason: Optional[str] = None


@dataclass
class LeaseRequest:
    lease_id: int
    demand: ResourceSet
    spec_meta: Dict[str, Any]
    future: asyncio.Future = None
    pg: Optional[Tuple[PlacementGroupID, int]] = None
    # Queue-age accounting (autoscaler scale-up signal + the
    # rtpu_lease_queue_age_seconds gauge): when this request arrived.
    enqueued_at: float = 0.0


@dataclass
class BundleAccount:
    resources: ResourceSet
    available: ResourceSet
    committed: bool = False


@dataclass
class ObjectEntry:
    size: int
    last_access: float
    pinned: int = 0
    spilled_path: Optional[str] = None


class Raylet:
    def __init__(self, session_name: str, gcs_address: Address,
                 resources: Dict[str, float],
                 labels: Optional[Dict[str, str]] = None,
                 node_index: int = 0, is_head: bool = False,
                 object_store_memory: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        self.session_name = session_name
        self.node_id = NodeID.from_random().hex()
        self.gcs_address = tuple(gcs_address)
        self.is_head = is_head
        self.node_index = node_index
        self.labels = dict(labels or {})
        self.resources = NodeResources(ResourceSet(resources), self.labels)
        self.server = RpcServer(f"raylet-{node_index}")
        self.clients = ClientPool()
        self.address: Optional[Address] = None
        self.plasma = PlasmaDir(session_name, node_index)
        self.capacity = object_store_memory or CONFIG.object_store_memory_bytes
        self.spill_dir = spill_dir or os.path.join(
            "/tmp", f"rtpu-spill-{session_name}-{node_index}")

        self.workers: Dict[bytes, WorkerHandle] = {}
        self.queued: List[LeaseRequest] = []
        self.leases: Dict[int, Tuple[bytes, ResourceSet,
                                     Optional[Tuple[PlacementGroupID, int]]]] = {}
        self.bundles: Dict[Tuple[PlacementGroupID, int], BundleAccount] = {}
        self.objects: Dict[str, ObjectEntry] = {}
        self.store_used = 0
        # Spill/restore accounting (reference: local_object_manager.cc
        # spilled_bytes_total/restored_bytes_total + the pinned-bytes
        # gauge): feeds runtime_metrics and get_memory_report.
        self.spilled_objects: Dict[str, int] = {}  # hex -> size
        self.spilled_bytes = 0
        self.spilled_bytes_total = 0
        self.restored_bytes_total = 0
        self.spill_count = 0
        self.restore_count = 0
        # Memory watchdog state (reference: memory_monitor.h): above the
        # watermark the node is "under pressure" — events are emitted and
        # the lease policy hook may refuse new grants.
        self._mem_pressure = False
        self._last_pressure_event = 0.0
        self.cluster_view: Dict[str, NodeView] = {}
        self._view_ver = -1  # last merged GCS view version (-1 = none)
        self._view_epoch = 0  # GCS incarnation the version belongs to
        # in-progress push-broadcast assemblies: object_hex -> state
        self._push_assembly: Dict[str, Dict[str, Any]] = {}
        from .external_storage import storage_from_config
        self.spill_storage = storage_from_config()
        self.node_addresses: Dict[str, Address] = {}
        self._next_lease_id = 0
        # Actor-lease idempotency (one grant per actor id): a caller
        # whose lease RPC timed out retries while the ORIGINAL request is
        # still queued behind the spawn pipeline — without coalescing,
        # both requests eventually grant and two creation pushes land on
        # two (or worse, one reused) worker(s), cross-wiring actors.
        self._actor_lease_tasks: Dict[str, asyncio.Task] = {}
        self._lease_actor_keys: Dict[int, str] = {}
        self._spawn_sem: Optional[asyncio.Semaphore] = None
        self._tasks: List[asyncio.Task] = []
        self._pulls: Dict[str, asyncio.Future] = {}
        # Log & forensics plane: per-worker line rings (live + a bounded
        # FIFO of dead workers' rings) and the bounded publish window
        # the pump flushes through (see logplane.py).
        self.log_rings = logplane.RingSet()
        self._log_pub_window = logplane.PublishWindow(
            CONFIG.log_pump_inflight_max)
        # GCS failover state: the incarnation we registered with (a
        # changed incarnation in any heartbeat ack means the GCS
        # restarted — re-announce), and reports whose delivery failed
        # while the GCS was down (replayed after re-registration so
        # worker deaths/events that raced the outage aren't lost).
        self._gcs_incarnation: Optional[int] = None
        self._gcs_reconnecting = False
        self._gcs_reports_pending: collections.deque = \
            collections.deque(maxlen=256)
        # Graceful-drain fence (rolling upgrades / elastic scale-in):
        # while draining, NO new lease grants — requests spill back to
        # healthy nodes or are rejected with {"draining": True}, workers
        # whose leases return are disposed instead of re-pooled, and
        # drain_self(phase="wait") blocks until in-flight leases empty
        # (stragglers past the deadline get postmortem-tagged kills).
        self._draining = False
        self._drain_reason = ""
        # Set by drain_self(exit_process=True): standalone raylet mains
        # (raylet_main.py) wait on it and exit clean after the drain.
        self.exit_requested: Optional[asyncio.Event] = None
        # Gauge hygiene: shapes whose queue-age series we exported last
        # tick, so a drained shape's stale age is zeroed, not frozen.
        self._last_age_shapes: Set[str] = set()
        self._stopped = False

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> Address:
        self.exit_requested = asyncio.Event()
        self.server.register_instance(self)
        self.address = await self.server.start(host, port)
        gcs = self.clients.get(self.gcs_address)
        reply = await gcs.call("register_node", node_id=self.node_id,
                               address=self.address,
                               resources=self.resources.total.to_dict(),
                               labels=self.labels, is_head=self.is_head,
                               retries=CONFIG.rpc_max_retries)
        if isinstance(reply, dict):
            self._gcs_incarnation = reply.get("incarnation")
        self._tasks.append(asyncio.ensure_future(self._heartbeat_loop()))
        self._tasks.append(asyncio.ensure_future(self._worker_liveness_loop()))
        if CONFIG.memory_monitor_refresh_ms > 0:
            self._tasks.append(
                asyncio.ensure_future(self._memory_monitor_loop()))
        from . import profiler
        profiler.maybe_autostart()
        return self.address

    async def stop(self):
        self._stopped = True
        for t in self._tasks:
            t.cancel()
        for handle in list(self.workers.values()):
            self._kill_worker(handle)
        await self.server.stop()
        self.plasma.destroy()

    # ------------------------------------------------------------------
    # heartbeats / cluster view
    # ------------------------------------------------------------------

    async def _heartbeat_loop(self):
        gcs = self.clients.get(self.gcs_address)
        next_metrics_flush = 0.0
        hb_failures = 0
        while not self._stopped:
            try:
                self._update_metrics()
                now = time.monotonic()
                if now >= next_metrics_flush:
                    next_metrics_flush = now + \
                        CONFIG.metrics_report_interval_s
                    self._flush_metrics(gcs)
                reply = await gcs.call(
                    "heartbeat", node_id=self.node_id,
                    resources_available=self.resources.available.to_dict(),
                    resources_total=self.resources.total.to_dict(),
                    pending_demand=[req.demand.to_dict()
                                    for req in self.queued[:100]],
                    queue_ages=self._queue_ages(),
                    draining=self._draining,
                    known_ver=self._view_ver,
                    known_epoch=self._view_epoch,
                    gcs_incarnation=self._gcs_incarnation,
                    timeout=CONFIG.health_check_timeout_s)
                if reply.get("stale_gcs"):
                    # A zombie pre-restart GCS answered (we already
                    # follow its successor): not an ack. If EVERY
                    # heartbeat says stale (the successor's state was
                    # lost and it restarted with a lower incarnation),
                    # reconnect — _reannounce stamps the server's own
                    # incarnation, so the re-registration is accepted
                    # and the cluster reforms instead of orbiting a
                    # GCS that refuses us forever.
                    logger.warning("heartbeat answered by a stale GCS "
                                   "incarnation; ignoring")
                    hb_failures += 1
                    if hb_failures >= \
                            CONFIG.gcs_heartbeat_failure_threshold:
                        await self._reconnect_to_gcs(
                            "heartbeats answered by a stale GCS "
                            "incarnation")
                        hb_failures = 0
                elif reply.get("dead"):
                    logger.warning("raylet %s marked dead by gcs; exiting",
                                   self.node_id[:12])
                    return
                elif reply.get("unknown"):
                    # The GCS restarted without our record (persistence
                    # off / lost): re-register instead of exiting.
                    await self._reconnect_to_gcs(
                        "gcs lost our registration")
                    hb_failures = 0
                else:
                    hb_failures = 0
                    inc = reply.get("incarnation")
                    if inc is not None and self._gcs_incarnation is not None \
                            and inc != self._gcs_incarnation:
                        # Restart detected between heartbeats (durable
                        # GCS knows us, so the ack still succeeded):
                        # re-announce workers + replay unacked reports.
                        await self._reconnect_to_gcs(
                            f"gcs incarnation changed "
                            f"{self._gcs_incarnation} -> {inc}")
                    elif inc is not None:
                        self._gcs_incarnation = inc
                    self._update_view(reply.get("view", {}))
                    fj = reply.get("finished_jobs")
                    if fj:
                        self._reap_job_leases(fj)
            except asyncio.CancelledError:
                return
            except Exception:
                hb_failures += 1
                if hb_failures >= CONFIG.gcs_heartbeat_failure_threshold:
                    await self._reconnect_to_gcs(
                        f"{hb_failures} consecutive heartbeat failures")
                    hb_failures = 0
                else:
                    logger.debug("heartbeat to GCS failed; retrying next "
                                 "interval", exc_info=True)
            await asyncio.sleep(HEARTBEAT_INTERVAL_S)

    # -- GCS failover: reconnect-and-replay ----------------------------

    async def _reconnect_to_gcs(self, reason: str):
        """Ride through a GCS restart: jittered-exponential probing
        until a live incarnation answers, then re-register (same
        node_id, address and resources re-announced, live worker
        inventory attached so the GCS can fail over actors whose
        workers died during the outage) and replay reports whose
        delivery was lost. Never gives up — a raylet without a GCS has
        no cluster."""
        if self._gcs_reconnecting:
            return
        self._gcs_reconnecting = True
        t0 = time.monotonic()
        try:
            gcs = self.clients.get(self.gcs_address)
            logger.warning("raylet %s reconnecting to GCS (%s)",
                           self.node_id[:12], reason)
            bo = Backoff(
                base_s=CONFIG.gcs_reconnect_base_delay_ms / 1000.0,
                max_s=CONFIG.gcs_reconnect_max_delay_ms / 1000.0)
            info = None
            while not self._stopped:
                try:
                    info = await gcs.call(
                        "gcs_info", timeout=CONFIG.health_check_timeout_s)
                    break
                except Exception:
                    logger.debug("gcs reconnect probe failed",
                                 exc_info=True)
                    await bo.async_sleep()
            if info is None:  # stopped mid-reconnect
                return
            try:
                accepted = await self._reannounce(info.get("incarnation"))
            except asyncio.CancelledError:
                raise
            except Exception:
                # The GCS died again between the probe and the register
                # (or rejected us): the next failed heartbeat re-enters
                # this loop. Must not raise — one call site is the
                # heartbeat loop's own except handler, and an escape
                # there would kill heartbeating for good.
                logger.warning("gcs re-registration failed; will retry",
                               exc_info=True)
                return
            if not accepted:
                # Fenced or stale-rejected: not a reconnect — the
                # failover dashboards must not count a refused node.
                return
            elapsed = time.monotonic() - t0
            from .runtime_metrics import runtime_metrics
            metrics = runtime_metrics()
            metrics.gcs_reconnects.inc(tags={"component": "raylet"})
            metrics.gcs_reconnect_latency.observe(
                elapsed, tags={"component": "raylet"})
            logger.warning(
                "raylet %s re-registered with GCS incarnation %s after "
                "%.2fs", self.node_id[:12], self._gcs_incarnation,
                elapsed)
        finally:
            self._gcs_reconnecting = False

    async def _reannounce(self, incarnation: Optional[int]) -> bool:
        """Re-register on the (possibly new) GCS incarnation and replay
        in-flight state: resource totals, live worker inventory, and any
        queued reports (worker deaths, events) the outage swallowed.
        Returns False when the GCS refused us (stale/fenced)."""
        gcs = self.clients.get(self.gcs_address)
        worker_ids = [h.worker_id.hex() for h in self.workers.values()
                      if h.state != "DEAD"]
        reply = await gcs.call(
            "register_node", node_id=self.node_id, address=self.address,
            resources=self.resources.total.to_dict(), labels=self.labels,
            is_head=self.is_head, worker_ids=worker_ids,
            gcs_incarnation=incarnation,
            retries=CONFIG.rpc_max_retries)
        if isinstance(reply, dict):
            if reply.get("stale_gcs"):
                logger.warning("re-registration rejected by a stale GCS")
                return False
            if reply.get("dead"):
                # Fenced out: we were declared dead and our actors
                # failed over. The next heartbeat's {"dead": True} makes
                # the heartbeat loop exit this raylet cleanly.
                logger.warning("re-registration refused: this node was "
                               "declared dead; exiting on next heartbeat")
                return False
            self._gcs_incarnation = reply.get("incarnation")
        # The new incarnation numbers its view from scratch.
        self._view_ver = -1
        self._view_epoch = 0
        # Replay unacked reports in arrival order; re-queue on failure
        # (the next reconnect cycle retries).
        pending = list(self._gcs_reports_pending)
        self._gcs_reports_pending.clear()
        for method, kwargs in pending:
            try:
                await gcs.call(method, timeout=10, **kwargs)
            except Exception:
                logger.debug("replay of %s after reconnect failed",
                             method, exc_info=True)
                self._gcs_reports_pending.append((method, kwargs))
        return True

    def _queue_gcs_report(self, method: str, kwargs: Dict[str, Any]):
        """Remember a report whose delivery failed (GCS down) for replay
        after re-registration. Bounded: oldest dropped beyond 256."""
        self._gcs_reports_pending.append((method, kwargs))

    @staticmethod
    def _shape_tag(demand: ResourceSet) -> str:
        """Compact stable tag for one lease shape's resource demand
        (the per-shape queue-age gauge + autoscaler state rows)."""
        d = demand.to_dict()
        if not d:
            return "none"
        return ",".join(f"{k}={v:g}" for k, v in sorted(d.items()))

    def _queue_ages(self) -> Dict[str, float]:
        """Oldest pending lease age per resource shape — the elastic
        autoscaler's primary scale-up signal (a deep-but-fresh queue is
        a burst; an OLD queue is starvation)."""
        now = time.monotonic()
        ages: Dict[str, float] = {}
        for req in self.queued:
            shape = self._shape_tag(req.demand)
            age = now - (req.enqueued_at or now)
            if age > ages.get(shape, -1.0):
                ages[shape] = age
        return ages

    def _update_metrics(self):
        from .runtime_metrics import runtime_metrics
        metrics = runtime_metrics()
        tags = {"node": str(self.node_index)}
        metrics.raylet_lease_queue.set(len(self.queued), tags=tags)
        metrics.node_draining.set(1 if self._draining else 0, tags=tags)
        ages = self._queue_ages()
        for shape, age in ages.items():
            metrics.lease_queue_age.set(
                age, tags={"node": str(self.node_index), "shape": shape})
        for stale in self._last_age_shapes - set(ages):
            metrics.lease_queue_age.set(
                0.0, tags={"node": str(self.node_index), "shape": stale})
        self._last_age_shapes = set(ages)
        metrics.raylet_store_bytes.set(self.store_used, tags=tags)
        metrics.raylet_workers.set(len(self.workers), tags=tags)
        metrics.store_capacity.set(self.capacity, tags=tags)
        metrics.store_pinned_bytes.set(
            sum(e.size for e in self.objects.values() if e.pinned > 0),
            tags=tags)
        metrics.store_spilled_bytes.set(self.spilled_bytes, tags=tags)
        if not CONFIG.no_log_plane:
            metrics.log_ring_bytes.set(self.log_rings.total_bytes(),
                                       tags=tags)

    def _gcs_event(self, event_type: str, message: str,
                   severity: str = "INFO", **fields):
        """Best-effort structured event to the GCS event log; failures
        (GCS down) queue for replay after reconnection."""
        gcs = self.clients.get(self.gcs_address)
        kwargs = dict(event_type=event_type, message=message,
                      severity=severity,
                      fields=dict(fields, node_id=self.node_id))
        fut = asyncio.ensure_future(gcs.call(
            "add_event", timeout=10, **kwargs))

        def _done(f):
            if not f.cancelled() and f.exception() is not None:
                self._queue_gcs_report("add_event", kwargs)
        fut.add_done_callback(_done)

    def _flush_metrics(self, gcs):
        """Push this process's registry into the metrics KV. Standalone
        raylet processes have no CoreWorker (whose flusher would do it);
        in local mode the driver's flusher owns the shared registry, so
        flushing here too would double-count counters after the merge."""
        from .core_worker import try_get_core_worker
        if try_get_core_worker() is not None:
            return
        from ..util.metrics import METRICS_KV_NS, snapshot_all_json
        fut = asyncio.ensure_future(gcs.call(
            "kv_put", ns=METRICS_KV_NS, key=f"raylet-{self.node_id}",
            value=snapshot_all_json(), overwrite=True, timeout=10))
        # best-effort: consume a failed flush (GCS briefly unreachable)
        # instead of spamming "Task exception was never retrieved"
        fut.add_done_callback(
            lambda f: f.cancelled() or f.exception())

    def _update_view(self, vd: Dict[str, Any]):
        """Merge a versioned view delta (stable cluster => empty payload;
        reference: ray_syncer.h eventually-consistent resource views)."""
        delta = vd.get("delta", vd if vd and "ver" not in vd else {})
        changed = bool(delta) or bool(vd.get("removed"))
        if vd.get("full", "ver" not in vd):
            view = {}
        else:
            view = self.cluster_view
            for nid in vd.get("removed", ()):
                view.pop(nid, None)
                self.node_addresses.pop(nid, None)
        for nid, info in delta.items():
            nr = NodeResources(ResourceSet(info["total"]), info["labels"])
            nr.available = ResourceSet(info["available"])
            nv = NodeView(nid, nr)
            # Drain fence propagation: peer raylets must stop spilling
            # lease requests onto a draining node.
            nv.draining = bool(info.get("draining"))
            view[nid] = nv
            self.node_addresses[nid] = tuple(info["address"])
        self.cluster_view = view
        if "ver" in vd:
            self._view_ver = vd["ver"]
            self._view_epoch = vd.get("epoch", 0)
        if not changed:
            return
        # New nodes / freed remote capacity can unblock queued requests via
        # spillback — a request infeasible here would otherwise park forever
        # (reference: cluster_lease_manager re-runs scheduling on every
        # resource-view change, node_manager.cc ScheduleAndGrantLeases).
        self._pump_queue()

    # ------------------------------------------------------------------
    # worker pool (reference: src/ray/raylet/worker_pool.cc)
    # ------------------------------------------------------------------

    def _env_key(self, runtime_env: Dict[str, Any],
                 demand: ResourceSet) -> Tuple:
        """Workers are dedicated per runtime environment: env vars are
        process state, and working_dir/py_modules mutate sys.path/cwd —
        none of these may leak between environments via worker reuse.

        A lease that holds chips is its own environment: unless the
        runtime env names a platform itself, its worker runs with
        JAX_PLATFORMS=tpu, so it opens the TPU backend or dies with the
        backend's error — never a silent CPU run."""
        from .task_spec import runtime_env_key
        key = runtime_env_key(runtime_env)
        if demand.get("TPU") > 0 and \
                not any(k == "JAX_PLATFORMS" for k, _ in key[0]):
            key = (tuple(sorted(key[0] + (("JAX_PLATFORMS", "tpu"),))),) \
                + key[1:]
        return key

    def _spawn_worker(self, env_key: Tuple) -> WorkerHandle:
        worker_id = WorkerID.from_random().binary()
        env = dict(os.environ)
        env.update({k: v for k, v in env_key[0]})  # env_vars component
        # Workers must import ray_tpu even when it isn't installed — put the
        # package's parent dir on their PYTHONPATH.
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        existing = env.get("PYTHONPATH", "")
        if pkg_root not in existing.split(os.pathsep):
            env["PYTHONPATH"] = (pkg_root + os.pathsep + existing
                                 if existing else pkg_root)
        env.update({
            # piped stdout must not sit in an 8KB block buffer — the log
            # stream to the driver needs lines as they are printed
            "PYTHONUNBUFFERED": "1",
            "RTPU_WORKER_ID": worker_id.hex(),
            "RTPU_SESSION": self.session_name,
            "RTPU_NODE_ID": self.node_id,
            "RTPU_NODE_INDEX": str(self.node_index),
            "RTPU_RAYLET_ADDR": f"{self.address[0]}:{self.address[1]}",
            "RTPU_GCS_ADDR": f"{self.gcs_address[0]}:{self.gcs_address[1]}",
        })
        # Only a worker whose lease holds chips (see _env_key) or whose
        # runtime env names a platform may open an accelerator. Every
        # other worker is FORCED onto the CPU — setdefault is not enough:
        # on TPU hosts the ambient environment itself carries
        # JAX_PLATFORMS=tpu, and a worker inheriting it would take the
        # host's chips away from the worker that was leased them.
        if not any(k == "JAX_PLATFORMS" for k, _ in env_key[0]):
            env["JAX_PLATFORMS"] = "cpu"
        if "tpu" in env["JAX_PLATFORMS"]:
            # about to own a chip: share the persistent compile cache
            from ..accelerators.tpu import compile_cache_dir
            compile_cache_dir(env)
        handle = WorkerHandle(
            worker_id=worker_id, proc=None, pid=0, env_key=env_key,
            registered=asyncio.get_running_loop().create_future())
        self.workers[worker_id] = handle
        loop = asyncio.get_running_loop()

        def _popen():
            # fork/exec off the event loop: a spawn burst must not starve
            # lease/heartbeat handling (1-core boxes stall for seconds).
            # With log_to_driver, worker output is piped and streamed to
            # the driver via GCS pubsub (reference: _private/log_monitor.py).
            from .task_spec import (ENV_KEY_CONDA, ENV_KEY_PYTHON_ENV,
                                    ENV_KEY_UV)
            interpreter = sys.executable
            pyenv_reqs = env_key[ENV_KEY_PYTHON_ENV] \
                if len(env_key) > ENV_KEY_PYTHON_ENV else ()
            conda_entry = env_key[ENV_KEY_CONDA] \
                if len(env_key) > ENV_KEY_CONDA else ""
            uv_pkgs = env_key[ENV_KEY_UV] \
                if len(env_key) > ENV_KEY_UV else ""
            if pyenv_reqs or conda_entry or uv_pkgs:
                # isolated interpreter (reference: conda/uv/pip plugins)
                from .errors import RuntimeEnvSetupError
                from .runtime_env import (ensure_conda_env_entry,
                                          ensure_python_env,
                                          ensure_uv_env)
                pyenv_root = os.path.join(
                    "/tmp", "rtpu", f"session_{self.session_name}",
                    "pyenvs")
                try:
                    if conda_entry:
                        interpreter = ensure_conda_env_entry(
                            conda_entry, pyenv_root)
                    elif uv_pkgs:
                        interpreter = ensure_uv_env(
                            list(uv_pkgs), pyenv_root)
                    else:
                        interpreter = ensure_python_env(
                            list(pyenv_reqs), pyenv_root)
                except Exception as e:
                    # Deterministic: the same requirements will fail the
                    # same way on every node — callers must not retry.
                    raise RuntimeEnvSetupError(
                        f"python env setup failed: {e}") from e
            if CONFIG.no_log_plane:
                # exact-legacy wiring (the kill switch's contract)
                if CONFIG.log_to_driver:
                    out_target = err_target = subprocess.PIPE
                else:
                    # stderr stays inherited: crash tracebacks must
                    # surface somewhere even with log streaming disabled
                    out_target, err_target = subprocess.DEVNULL, None
            else:
                # Log plane: ALWAYS pipe — the per-worker ring captures
                # (and postmortems quote) output even when pubsub
                # streaming to drivers is off. The old DEVNULL path
                # becomes ring-only capture.
                out_target = err_target = subprocess.PIPE
            argv = [interpreter, "-m", "ray_tpu._internal.worker_main"]
            from .task_spec import ENV_KEY_IMAGE_URI
            image_uri = env_key[ENV_KEY_IMAGE_URI] \
                if len(env_key) > ENV_KEY_IMAGE_URI else ""
            if image_uri:
                from .runtime_env import build_container_argv
                # the IMAGE's python, not the host interpreter path
                # (host venv paths don't exist inside the container);
                # ray_tpu resolves via the mounted pkg_root + the
                # forwarded PYTHONPATH
                argv = ["python", "-m", "ray_tpu._internal.worker_main"]
                argv = build_container_argv(
                    image_uri, argv, env, pkg_root,
                    extra_env_keys=[k for k, _ in env_key[0]])
            return subprocess.Popen(
                argv, env=env, stdout=out_target, stderr=err_target)

        def _attach(fut):
            try:
                proc = fut.result()
            except Exception as e:
                logger.warning("worker spawn failed: %s", e)
                self.workers.pop(worker_id, None)
                if not handle.registered.done():
                    # Preserve the exception type: RuntimeEnvSetupError is
                    # deterministic (permanent rejection); a Popen/OS error
                    # (ENOMEM/EAGAIN under spawn bursts) is transient and
                    # must stay retryable.
                    from .errors import RuntimeEnvSetupError
                    if isinstance(e, RuntimeEnvSetupError):
                        handle.registered.set_exception(e)
                    else:
                        handle.registered.set_exception(
                            RuntimeError(f"worker spawn failed: {e}"))
                return
            handle.proc = proc
            handle.pid = proc.pid
            if proc.stdout is not None or proc.stderr is not None:
                self._start_log_forwarders(proc, handle)
            if handle.state == "DEAD":
                # killed while the fork was in flight — don't leak it
                try:
                    proc.terminate()
                except Exception:
                    logger.debug("terminate of orphaned spawn failed",
                                 exc_info=True)
        spawn_fut = loop.run_in_executor(None, _popen)
        spawn_fut.add_done_callback(_attach)
        return handle

    def _start_log_forwarders(self, proc: subprocess.Popen,
                              handle: "WorkerHandle" = None):
        """Tail the worker's stdout/stderr pipes: capture lines into the
        per-worker ring (attribution stamps parsed off), and publish
        cleaned batches to the WORKER_LOGS pubsub channel when
        log_to_driver streaming is on (reference:
        _private/log_monitor.py -> driver prints them). Under
        RTPU_NO_LOG_PLANE the pump degrades to the exact-legacy
        publish-only behavior (and only runs when log_to_driver piped
        the streams at all)."""
        from .rpc import EventLoopThread

        gcs = self.clients.get(self.gcs_address)
        capture = not CONFIG.no_log_plane
        forward = CONFIG.log_to_driver
        window = self._log_pub_window
        ring = self.log_rings.get_or_create(
            handle.worker_id.hex(), proc.pid) if capture \
            and handle is not None else None
        limiter = logplane.RateLimiter(
            CONFIG.log_rate_limit_lines_per_s) if capture else None
        from .runtime_metrics import runtime_metrics
        metrics = runtime_metrics()
        node_tag = str(self.node_index)

        def _pump(stream, name):
            batch: List[str] = []
            last_flush = time.monotonic()

            def _ingest(raw: str):
                """One raw pumped line -> ring capture + (maybe) the
                forward batch. Returns with the batch updated; the ring
                always captures, streaming is what rate limits."""
                if not capture or ring is None:
                    batch.append(raw)
                    return
                attribution, msg = logplane.parse_line(raw)
                if ring.job is None and handle is not None:
                    # the lease that binds this worker to a job lands
                    # after spawn; adopt it as soon as it exists
                    ring.job = handle.job_hex
                entry = ring.append(
                    name, attribution["level"], msg,
                    task=attribution["task"], actor=attribution["actor"],
                    job=attribution["job"])
                metrics.log_lines.inc(tags={
                    "node": node_tag, "stream": name,
                    "level": entry["level"]})
                overflow = ring.take_overflow_delta()
                if overflow:
                    metrics.log_dropped.inc(overflow, tags={
                        "node": node_tag, "reason": "ring_overflow"})
                if forward:
                    if limiter is None or limiter.allow(1):
                        batch.append(msg)
                    else:
                        metrics.log_dropped.inc(tags={
                            "node": node_tag, "reason": "rate_limited"})

            def flush():
                nonlocal batch, last_flush
                if not batch:
                    return
                lines, batch = batch, []
                last_flush = time.monotonic()
                if capture and not forward:
                    return  # ring-only mode: nothing streams
                # job read at flush time: the lease that binds this worker
                # to a job lands after spawn; drivers filter on it so one
                # job's output doesn't print on every driver
                job = handle.job_hex if handle is not None else None
                # Bounded in-flight window: with the GCS down/slow,
                # batches DROP (counted, warned once) instead of
                # queueing unboundedly on the EventLoopThread. Applies
                # in kill-switch mode too (the unbounded queue was a
                # bug, not plane behavior) — but only the plane moves
                # rtpu_log_* metrics; off-mode drops are visible via
                # the PublishWindow's own counters + warning.
                if not window.try_acquire(len(lines)):
                    if capture:
                        metrics.log_dropped.inc(
                            len(lines),
                            tags={"node": node_tag,
                                  "reason": "backpressure"})
                    return

                async def _publish(lines=lines, job=job):
                    try:
                        await gcs.call(
                            "publish", channel="WORKER_LOGS",
                            message={"pid": proc.pid,
                                     "node_id": self.node_id,
                                     "stream": name, "job": job,
                                     "lines": lines},
                            timeout=10)
                    except Exception:
                        logger.debug("WORKER_LOGS publish failed",
                                     exc_info=True)
                    finally:
                        window.release()
                EventLoopThread.get().post(_publish())
            # Raw nonblocking fd reads with our own line splitting.
            # select + BufferedReader.readline() is WRONG here: readline
            # slurps a whole chunk into the Python buffer and returns one
            # line — the rest sit buffered while select watches an empty
            # fd, so a burst (a stack dump, a traceback) surfaces one
            # line per future write.
            # selectors (epoll), NOT select(): select() rejects fds
            # >= FD_SETSIZE (1024), which a 1,000-actor fleet exceeds —
            # the pump then dies and that worker's logs vanish.
            import fcntl
            import selectors
            fd = stream.fileno()
            flags = fcntl.fcntl(fd, fcntl.F_GETFL)
            fcntl.fcntl(fd, fcntl.F_SETFL, flags | os.O_NONBLOCK)
            sel = selectors.DefaultSelector()
            sel.register(fd, selectors.EVENT_READ)
            pending = b""
            try:
                while True:
                    ready = sel.select(timeout=0.1)
                    if not ready:
                        flush()
                        continue
                    try:
                        chunk = os.read(fd, 65536)
                    except BlockingIOError:
                        continue
                    if not chunk:
                        break
                    pending += chunk
                    *lines, pending = pending.split(b"\n")
                    for raw in lines:
                        _ingest(raw.decode("utf-8", "replace"))
                    if len(batch) >= 100 or \
                            time.monotonic() - last_flush > 0.1:
                        flush()
            except (ValueError, OSError) as e:
                # fd closed at worker teardown is a clean exit; a read
                # failure while the worker LIVES still deserves a line
                if proc.poll() is None:
                    logger.warning(
                        "worker log pump read failed (pid %s): %s",
                        proc.pid, e)
            except Exception:
                logger.exception("worker log pump failed (pid %s)",
                                 proc.pid)
            finally:
                sel.close()
                if pending:
                    _ingest(pending.decode("utf-8", "replace"))
                flush()
        from .threads import spawn_daemon
        for stream, name in ((proc.stdout, "stdout"),
                             (proc.stderr, "stderr")):
            if stream is not None:
                # Exits on its own when the worker's fd closes; tracked
                # but not joined (the fd outlives raylet teardown).
                spawn_daemon(_pump, args=(stream, name),
                             name=f"rtpu-log-{proc.pid}")

    async def handle_register_worker(self, worker_id: bytes, address: Address,
                                     pid: int):
        handle = self.workers.get(worker_id)
        if handle is None:
            # Worker from a previous epoch; tell it to exit.
            return {"exit": True}
        handle.address = tuple(address)
        handle.pid = pid
        if handle.registered and not handle.registered.done():
            # A spawning lease request is awaiting THIS worker: hold it
            # in STARTING so the idle-pool scans cannot steal it between
            # registration and the spawner's resume — the stolen-worker
            # interleaving leased one process to two actor creations.
            handle.registered.set_result(True)
        else:
            handle.state = "IDLE"
            handle.last_idle = time.monotonic()
        return {"exit": False, "node_id": self.node_id,
                "node_index": self.node_index}

    async def _worker_liveness_loop(self):
        while not self._stopped:
            try:
                await asyncio.sleep(CONFIG.worker_liveness_check_period_s)
                now = time.monotonic()
                dead: List[WorkerHandle] = []
                for handle in list(self.workers.values()):
                    if handle.proc is not None and handle.proc.poll() is not None \
                            and handle.state != "DEAD":
                        dead.append(handle)
                    elif (handle.state == "IDLE" and not handle.is_actor_worker
                          and now - handle.last_idle >
                          CONFIG.worker_idle_timeout_s):
                        self._kill_worker(handle)
                if dead:
                    # concurrent: a mass death (OOM storm, job teardown)
                    # must not serialize at one postmortem grace sleep +
                    # GCS report per worker — callers poll the GCS for
                    # these postmortems on a ~2s budget
                    results = await asyncio.gather(
                        *(self._on_worker_death(h) for h in dead),
                        return_exceptions=True)
                    for handle, res in zip(dead, results):
                        if isinstance(res, Exception):
                            logger.error(
                                "death handling for worker %s failed: "
                                "%r", handle.worker_id.hex()[:12], res)
                # Reap abandoned push assemblies (sender died mid-stream).
                for ohex, assy in list(self._push_assembly.items()):
                    if now - assy["t"] > 120:
                        self._push_assembly.pop(ohex, None)
                        try:
                            assy["buf"].release()
                            self.plasma.abort(ObjectID.from_hex(ohex))
                        except Exception:
                            logger.debug("abort of half-pushed object %s "
                                         "failed", ohex[:12], exc_info=True)
            except asyncio.CancelledError:
                return
            except Exception:
                logger.exception("worker liveness loop error")

    def _reap_job_leases(self, finished_jobs: List[str]):
        """Kill workers leased to finished/dead jobs and refund their
        resources; drop the jobs' queued lease requests (reference:
        node_manager.cc HandleJobFinished). Idempotent — the GCS resends
        recently finished jobs on every heartbeat."""
        jobs = set(finished_jobs)
        for handle in list(self.workers.values()):
            if handle.job_hex in jobs and handle.lease_id is not None \
                    and handle.state != "DEAD":
                logger.info("reaping worker %s leased to finished job %s",
                            handle.worker_id.hex()[:12], handle.job_hex[:8])
                lease_id = handle.lease_id
                self._kill_worker(handle)
                self._release_lease(lease_id)
        for req in list(self.queued):
            if req.spec_meta.get("job") in jobs:
                self.queued.remove(req)
                if not req.future.done():
                    req.future.set_result({"canceled": True})

    async def _on_worker_death(self, handle: WorkerHandle):
        # Single-flight: the liveness sweep and a caller's dispose
        # (handle_return_worker) can both spot the same death. Whoever
        # sets DEAD first (synchronously below — no await before it, so
        # same-loop callers can't interleave) owns the postmortem; the
        # loser must neither re-report nor touch the ring while the
        # owner's grace sleep is still draining it.
        if handle.state == "DEAD":
            return
        # Actor workers routinely die on purpose (ray.kill / job teardown
        # kill_actor goes GCS->worker directly); the GCS owns their
        # restart-or-fail decision, so that's not warning-worthy here.
        log = logger.info if handle.is_actor_worker else logger.warning
        log("worker %s (pid %s) died unexpectedly",
            handle.worker_id.hex()[:12], handle.pid)
        handle.state = "DEAD"
        self.workers.pop(handle.worker_id, None)
        if handle.lease_id is not None:
            self._release_lease(handle.lease_id)
        # Assemble the postmortem BEFORE retiring the ring: exit
        # taxonomy + the ring's last lines + recent task ids + the
        # stuck-task stack dump if the probe sweeper captured one. It
        # rides the death report so the GCS can attach it to the
        # WORKER_DIED event and serve it to crashing callers.
        postmortem = None
        if not CONFIG.no_log_plane:
            # One pump tick of grace so lines still buffered in the dead
            # worker's pipe reach the ring before we quote it (the pump
            # polls every 0.1s; its EOF drain flushes the remainder).
            await asyncio.sleep(0.2)
            whex = handle.worker_id.hex()
            postmortem = logplane.build_postmortem(
                worker_hex=whex, pid=handle.pid, node_id=self.node_id,
                returncode=handle.proc.returncode
                if handle.proc is not None else None,
                ring=self.log_rings.live.get(whex),
                kill_reason=handle.kill_reason,
                cause="worker process died")
            self.log_rings.retire(whex)
        report = dict(node_id=self.node_id, worker_id=handle.worker_id,
                      cause="worker process died", postmortem=postmortem)
        try:
            await self.clients.get(self.gcs_address).call(
                "report_worker_death", timeout=10, **report)
        except Exception:
            # GCS down: queue for replay after re-registration — a death
            # that races the outage must still fail its actor over.
            logger.debug("report_worker_death to GCS failed; queued for "
                         "reconnect replay", exc_info=True)
            self._queue_gcs_report("report_worker_death", report)

    # ------------------------------------------------------------------
    # memory monitor (reference: src/ray/common/memory_monitor.h:52 +
    # raylet/worker_killing_policy.h:39 retriable-FIFO variant)
    # ------------------------------------------------------------------

    @staticmethod
    def _system_memory_usage_fraction() -> float:
        """Used fraction of system memory from /proc/meminfo."""
        try:
            fields = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    name, _, rest = line.partition(":")
                    fields[name] = int(rest.split()[0])
            total = fields.get("MemTotal", 0)
            avail = fields.get("MemAvailable", total)
            if total <= 0:
                return 0.0
            return 1.0 - avail / total
        except OSError:  # pragma: no cover
            return 0.0

    # Overridable for tests / fake pressure injection.
    _memory_usage_fn = None

    async def _memory_monitor_loop(self):
        period = CONFIG.memory_monitor_refresh_ms / 1000.0
        from .runtime_metrics import runtime_metrics
        tags = {"node": str(self.node_index)}
        while not self._stopped:
            try:
                await asyncio.sleep(period)
                usage_fn = (self._memory_usage_fn
                            or self._system_memory_usage_fraction)
                usage = usage_fn()
                runtime_metrics().node_mem_used_ratio.set(usage, tags=tags)
                over_watermark = usage > CONFIG.memory_monitor_watermark
                if over_watermark and not self._mem_pressure:
                    logger.warning(
                        "node memory %.1f%% above watermark %.1f%%",
                        usage * 100, CONFIG.memory_monitor_watermark * 100)
                pressure_cleared = self._mem_pressure and not over_watermark
                self._mem_pressure = over_watermark
                if pressure_cleared:
                    # Requests parked while leases were refused must not
                    # wait for an unrelated release/view change to grant.
                    self._pump_queue()
                now = time.monotonic()
                if over_watermark and \
                        now - self._last_pressure_event > 30.0:
                    # Rate-limited: a node camped above the watermark
                    # must not flood the event log every refresh tick.
                    self._last_pressure_event = now
                    self._gcs_event(
                        "MEMORY_PRESSURE",
                        f"node memory at {usage * 100:.1f}% (watermark "
                        f"{CONFIG.memory_monitor_watermark * 100:.0f}%)",
                        severity="WARNING", used_ratio=usage)
                if usage > CONFIG.memory_usage_threshold:
                    self._kill_for_memory(usage)
            except asyncio.CancelledError:
                return
            except Exception:
                logger.exception("memory monitor loop error")

    def _kill_for_memory(self, usage: float):
        """Retriable-FIFO policy: kill the most recently leased
        task-worker first (its owner retries it), sparing actor workers
        as long as possible; at most one kill per refresh tick."""
        leased = [w for w in self.workers.values()
                  if w.state == "LEASED" and w.proc is not None]
        if not leased:
            return
        leased.sort(key=lambda w: ((0 if not w.is_actor_worker else 1),
                                   -(w.lease_id or 0)))
        victim = leased[0]
        consequence = ("callers see ActorDiedError unless max_restarts "
                       "allows a restart" if victim.is_actor_worker
                       else "the owner will retry retriable tasks")
        logger.warning(
            "memory usage %.1f%% above threshold %.1f%%: killing worker "
            "%s (pid %s, %s) to relieve pressure; %s",
            usage * 100, CONFIG.memory_usage_threshold * 100,
            victim.worker_id.hex()[:12], victim.pid,
            "actor" if victim.is_actor_worker else "task", consequence)
        victim.kill_reason = "memory"  # postmortem taxonomy: OOM_KILLED
        try:
            victim.proc.kill()
        except Exception:
            logger.debug("memory-kill of pid %s failed (already gone?)",
                         victim.pid, exc_info=True)

    def _kill_worker(self, handle: WorkerHandle):
        handle.state = "DEAD"
        self.workers.pop(handle.worker_id, None)
        if not CONFIG.no_log_plane:
            # intentional teardown: no postmortem, but the ring moves to
            # the dead FIFO so `cli logs` still answers for a while
            self.log_rings.retire(handle.worker_id.hex())
        if handle.proc is not None:
            try:
                handle.proc.terminate()
            except Exception:
                logger.debug("terminate of worker pid %s failed",
                             handle.pid, exc_info=True)

    # ------------------------------------------------------------------
    # leases (reference: node_manager.cc HandleRequestWorkerLease +
    # local_lease_manager.cc + cluster_lease_manager spillback)
    # ------------------------------------------------------------------

    async def handle_request_worker_lease(
            self, spec_meta: Optional[Dict[str, Any]] = None,
            meta_blob: Optional[bytes] = None,
            task_hex: Optional[str] = None, job: Optional[str] = None,
            strategy: Optional[str] = None):
        if meta_blob is not None:
            # Flat-wire lease path: the submitter pre-encodes the shape-
            # invariant meta ONCE per shape and ships the same opaque
            # blob on every request (and every spillback hop) — only the
            # tiny per-task overlay travels uncoded. Decode here, once,
            # into the dict the scheduling pipeline already understands.
            from . import serialization
            spec_meta = serialization.loads(meta_blob)
            if task_hex is not None:
                spec_meta["task_hex"] = task_hex  # lease cancellation key
            if job is not None:
                spec_meta["job"] = job            # log-stream routing
            if strategy is not None:
                spec_meta["strategy"] = strategy
        actor_key = spec_meta.get("actor_id") \
            if spec_meta.get("is_actor") else None
        if actor_key is None:
            return await self._lease_request(spec_meta)
        task = self._actor_lease_tasks.get(actor_key)
        if task is None:
            task = asyncio.ensure_future(self._lease_request(spec_meta))
            self._actor_lease_tasks[actor_key] = task
        try:
            # shield: a retry RPC joining late must not cancel the shared
            # in-flight grant when its own transport drops
            reply = await asyncio.shield(task)
        except Exception:
            # Guard the pop: a LATE-waking awaiter of a finished (failed)
            # task must not evict the NEWER in-flight task a fresh retry
            # already installed under this key — popping it would let two
            # concurrent grants coalesce onto nothing and double-lease.
            if self._actor_lease_tasks.get(actor_key) is task:
                self._actor_lease_tasks.pop(actor_key, None)
            raise
        lease_id = reply.get("lease_id")
        if lease_id is None:
            # rejection/spillback: no lease to coalesce on — clear so a
            # later attempt can try fresh (same late-waker guard as above)
            if self._actor_lease_tasks.get(actor_key) is task:
                self._actor_lease_tasks.pop(actor_key, None)
        else:
            # cache the grant until the lease dies (_release_lease), so
            # any further retry of this actor reuses the SAME worker
            self._lease_actor_keys[lease_id] = actor_key
        return reply

    async def _lease_request(self, spec_meta: Dict[str, Any]):
        self._next_lease_id += 1
        req = LeaseRequest(
            lease_id=self._next_lease_id,
            demand=ResourceSet(spec_meta.get("resources", {})),
            spec_meta=spec_meta,
            future=asyncio.get_running_loop().create_future(),
            pg=spec_meta.get("pg"),
            enqueued_at=time.monotonic())
        if self._draining:
            # Drain fence: this node grants nothing new.
            # grant_or_reject callers (the GCS actor scheduler) have a
            # two-outcome contract — grant or {"rejected"} — so they
            # get a transient rejection (their own view skips draining
            # nodes on the re-pick); everyone else is redirected to a
            # healthy node when one fits, else told WHY
            # ({"draining": True}) so the driver's retry loop goes
            # back to its local raylet instead of spinning here.
            if spec_meta.get("grant_or_reject"):
                return {"rejected": True, "draining": True,
                        "error": "node is draining"}
            spill = self._pick_spillback(req)
            if spill is not None:
                return {"spillback_to": spill}
            return {"rejected": True, "draining": True,
                    "error": "node is draining"}
        if spec_meta.get("strategy") == "SPREAD":
            # Round-robin across schedulable nodes BEFORE considering a
            # local grant (reference: spread_scheduling_policy — default
            # hybrid prefers local, SPREAD must not).
            self._spread_clock = getattr(self, "_spread_clock", 0) + 1
            target = scheduling_policy.pick_spread(
                self.cluster_view, req.demand, self._spread_clock,
                spec_meta.get("label_selector") or None)
            if target is not None and target != self.node_id:
                addr = self.node_addresses.get(target)
                if addr is not None:
                    return {"spillback_to": (target, addr)}
        grant = self._try_grant(req)
        if grant is not None:
            try:
                return await grant
            except Exception as e:  # noqa: BLE001 — never hang the caller
                logger.exception("lease grant failed")
                self._refund(req.demand, req.pg)
                return {"rejected": True, "error": f"grant failed: {e!r}"}
        if spec_meta.get("grant_or_reject"):
            reply = {"rejected": True}
            if self._refuse_new_leases():
                reply["error"] = "node under memory pressure"
            return reply
        # Spillback: is some other node better placed right now?
        spill = self._pick_spillback(req)
        if spill is not None:
            return {"spillback_to": spill}
        self.queued.append(req)
        return await req.future

    def _pick_spillback(self, req: LeaseRequest) -> Optional[Tuple[str, Address]]:
        if req.pg is not None:
            return None  # PG leases are node-pinned by the bundle
        selector = req.spec_meta.get("label_selector") or None
        target = scheduling_policy.pick_hybrid(
            self.cluster_view, req.demand, local_node_id=self.node_id,
            label_selector=selector)
        if target is not None and target != self.node_id:
            view = self.cluster_view.get(target)
            if view is not None and view.available(req.demand):
                addr = self.node_addresses.get(target)
                if addr is not None:
                    return (target, addr)
        return None

    def _refuse_new_leases(self) -> bool:
        """Watchdog policy hook: above the memory watermark (with
        memory_pressure_refuse_leases on) NEW leases stop granting —
        requests queue (or spill back) and the monitor pumps the queue
        when pressure clears; existing leases run on."""
        return self._mem_pressure and CONFIG.memory_pressure_refuse_leases

    def _try_grant(self, req: LeaseRequest):
        """Attempt to allocate resources + a worker; returns awaitable reply
        or None if resources unavailable."""
        if self._draining:
            # Drain fence: grants stop the moment the drain begins —
            # including re-grants of just-returned workers to queued
            # requests (the drain-leak the return path used to allow).
            return None
        if self._refuse_new_leases():
            return None
        if req.pg is not None:
            pg_id, index = req.pg
            if index >= 0:
                key = (pg_id, index)
                account = self.bundles.get(key)
            else:
                # wildcard bundle index: any committed bundle of this pg
                key, account = next(
                    ((k, a) for k, a in self.bundles.items()
                     if k[0] == pg_id and a.committed
                     and req.demand.fits(a.available)), (None, None))
            if account is None or not account.committed \
                    or not req.demand.fits(account.available):
                return None
            account.available = account.available - req.demand
            req.pg = key  # resolved bundle; release refunds exactly this one
            charge_node = False
        else:
            if not self.resources.try_allocate(req.demand):
                return None
            charge_node = True
        return self._finish_grant(req, charge_node)

    def _refund(self, demand: ResourceSet,
                pg_key: Optional[Tuple[PlacementGroupID, int]]):
        if pg_key is not None:
            account = self.bundles.get(pg_key)
            if account is not None:
                account.available = account.available + demand
        else:
            self.resources.release(demand)

    async def _finish_grant(self, req: LeaseRequest, charge_node: bool):
        env_key = self._env_key(req.spec_meta.get("runtime_env", {}),
                                req.demand)
        handle = next(
            (w for w in self.workers.values()
             if w.state == "IDLE" and w.env_key == env_key
             and not w.is_actor_worker), None)
        if handle is None:
            # Bounded spawn pipeline (reference: worker_pool.cc
            # maximum_startup_concurrency): a 1,000-actor burst must not
            # fork 1,000 interpreters at once on one box — spawns run
            # `maximum_startup_concurrency` at a time and the start
            # timeout covers only the spawn itself, not the queue wait.
            if self._spawn_sem is None:
                self._spawn_sem = asyncio.Semaphore(
                    max(1, CONFIG.maximum_startup_concurrency))
            async with self._spawn_sem:
                # a worker may have gone idle while we queued
                handle = next(
                    (w for w in self.workers.values()
                     if w.state == "IDLE" and w.env_key == env_key
                     and not w.is_actor_worker), None)
                if handle is None:
                    handle = self._spawn_worker(env_key)
                    try:
                        await asyncio.wait_for(
                            handle.registered,
                            CONFIG.worker_start_timeout_s)
                    except asyncio.TimeoutError:
                        self._kill_worker(handle)
                        self._refund(req.demand,
                                     None if charge_node else req.pg)
                        return {"rejected": True,
                                "error": "worker failed to start in time"}
                    except Exception as e:
                        self._kill_worker(handle)
                        self._refund(req.demand,
                                     None if charge_node else req.pg)
                        # Only deterministic runtime-env failures are
                        # permanent; transient OS errors (fork ENOMEM/
                        # EAGAIN during spawn bursts) stay retryable like
                        # the start-timeout path.
                        from .errors import RuntimeEnvSetupError
                        permanent = isinstance(e, RuntimeEnvSetupError)
                        reply = {"rejected": True, "error": str(e)}
                        if permanent:
                            reply["permanent"] = True
                        return reply
        handle.state = "LEASED"
        handle.lease_id = req.lease_id
        handle.is_actor_worker = bool(req.spec_meta.get("is_actor"))
        handle.job_hex = req.spec_meta.get("job")
        from .runtime_metrics import runtime_metrics
        runtime_metrics().raylet_leases_granted.inc(
            tags={"node": str(self.node_index)})
        self.leases[req.lease_id] = (
            handle.worker_id, req.demand, None if charge_node else req.pg)
        return {"rejected": False, "lease_id": req.lease_id,
                "worker_address": handle.address,
                "worker_id": handle.worker_id, "node_id": self.node_id}

    def _release_lease(self, lease_id: int):
        actor_key = self._lease_actor_keys.pop(lease_id, None)
        if actor_key is not None:
            self._actor_lease_tasks.pop(actor_key, None)
        entry = self.leases.pop(lease_id, None)
        if entry is None:
            return
        worker_id, demand, pg = entry
        if not demand.is_empty() or pg is not None:
            self._refund(demand, pg)
        handle = self.workers.get(worker_id)
        if handle is not None and handle.state == "LEASED":
            if handle.is_actor_worker:
                # Actor workers are SINGLE-USE (reference: dedicated
                # actor workers die with their actor): re-entering the
                # IDLE pool while the instance lives would let a later
                # creation bind a second actor onto this process and
                # cross-wire both handles. Whatever released the lease,
                # the process goes down with it — and the death is
                # REPORTED, so if a live actor was bound here the GCS
                # restarts or fails it instead of leaving its callers
                # hanging on a dead address.
                logger.info("disposing actor worker %s on lease %d "
                            "release", handle.worker_id.hex()[:12],
                            lease_id)
                self._kill_worker(handle)
                report = dict(
                    node_id=self.node_id, worker_id=handle.worker_id,
                    cause="actor worker disposed on lease release")
                fut = asyncio.ensure_future(self.clients.get(
                    self.gcs_address).call(
                        "report_worker_death", timeout=10, **report))
                fut.add_done_callback(
                    lambda f, r=report: (not f.cancelled()
                                         and f.exception() is not None
                                         and self._queue_gcs_report(
                                             "report_worker_death", r)))
            elif self._draining:
                # Drain fence on the return path: a worker returned
                # mid-drain (including via handle_return_worker's
                # grace-poll, which awaits and can resume AFTER the
                # fence went up) must NOT re-enter the idle pool where
                # a queued request from another job could re-lease it —
                # that leak kept drains from ever converging. The
                # process is disposed; its resources were refunded
                # above, so the drain's lease count still converges.
                logger.info("disposing worker %s returned during drain",
                            handle.worker_id.hex()[:12])
                self._kill_worker(handle)
            else:
                handle.state = "IDLE"
                handle.lease_id = None
                handle.last_idle = time.monotonic()
        self._pump_queue()

    def _pump_queue(self):
        still_queued = []
        for req in self.queued:
            grant = self._try_grant(req)
            if grant is not None:
                async def _complete(req=req, grant=grant):
                    try:
                        reply = await grant
                    except Exception as e:  # noqa: BLE001 — a raised
                        # grant must NOT leave the queued request's
                        # future unresolved (the driver would wait on the
                        # lease RPC forever and every task behind that
                        # waiter wedges)
                        logger.exception("queued lease grant failed")
                        self._refund(req.demand, req.pg)
                        reply = {"rejected": True,
                                 "error": f"grant failed: {e!r}"}
                    if not req.future.done():
                        req.future.set_result(reply)
                asyncio.ensure_future(_complete())
                continue
            spill = self._pick_spillback(req)
            if spill is not None and not req.future.done():
                # Debit the snapshot so one freed remote slot doesn't spill
                # the whole queue there in a herd (each bounce burns one of
                # the client's spillback hops).
                target_view = self.cluster_view.get(spill[0])
                if target_view is not None:
                    target_view.resources.available = \
                        target_view.resources.available - req.demand
                req.future.set_result({"spillback_to": spill})
                continue
            still_queued.append(req)
        self.queued = still_queued

    async def handle_agent_stats(self) -> Dict[str, Any]:
        """Per-node agent surface (reference: dashboard/agent.py +
        modules/reporter/reporter_agent.py — each node reports its own
        cpu/mem and per-worker process stats; the dashboard head proxies
        /api/nodes/<id>/stats here instead of running a separate agent
        process — the raylet IS the node agent)."""
        stats: Dict[str, Any] = {"node_id": self.node_id,
                                 "node_index": self.node_index}
        try:
            with open("/proc/loadavg") as f:
                stats["loadavg"] = [float(x)
                                    for x in f.read().split()[:3]]
        except OSError:
            pass
        try:
            mem = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, _, rest = line.partition(":")
                    if k in ("MemTotal", "MemAvailable"):
                        mem[k] = int(rest.split()[0]) * 1024
            stats["mem_total_bytes"] = mem.get("MemTotal")
            stats["mem_available_bytes"] = mem.get("MemAvailable")
        except OSError:
            pass
        workers = []
        for handle in self.workers.values():
            entry = {"worker_id": handle.worker_id.hex(),
                     "pid": handle.pid, "state": handle.state,
                     "job": handle.job_hex}
            try:
                with open(f"/proc/{handle.pid}/statm") as f:
                    pages = int(f.read().split()[1])
                entry["rss_bytes"] = pages * os.sysconf("SC_PAGESIZE")
            except (OSError, ValueError, IndexError):
                pass
            workers.append(entry)
        stats["workers"] = workers
        # Owner-shard rows are NOT fanned out here: workers auto-resolve
        # to 1 shard (the sharded fan-in side is the DRIVER, served by
        # /api/shards -> state.shard_summary), and a per-poll RPC to
        # every worker would tax node-stats for rows nobody renders.
        # Per-worker stats stay one `get_shard_stats` call away.
        stats["num_leases"] = len(self.leases)
        stats["resources_total"] = self.resources.total.to_dict()
        stats["resources_available"] = self.resources.available.to_dict()
        return stats

    async def handle_return_worker(self, lease_id: int,
                                   dispose: bool = False):
        entry = self.leases.get(lease_id)
        if entry and dispose:
            handle = self.workers.get(entry[0])
            if handle is not None:
                died = False
                if not CONFIG.no_log_plane and handle.proc is not None \
                        and handle.state != "DEAD":
                    # The usual dispose reason is a worker that died
                    # underneath its caller (the failed push races our
                    # liveness sweep). Give the kernel a short grace to
                    # reap — poll() flips within ~50ms of a SIGKILL —
                    # so a real death takes the postmortem/report path
                    # (the crashing caller is about to ask the GCS for
                    # this worker's last words); a healthy disposal
                    # falls through to the plain kill.
                    deadline = time.monotonic() + 0.5
                    while True:
                        died = handle.proc.poll() is not None
                        if died or time.monotonic() >= deadline:
                            break
                        await asyncio.sleep(0.05)
                if died and handle.state != "DEAD":
                    await self._on_worker_death(handle)
                elif handle.state != "DEAD":
                    self._kill_worker(handle)
                # state == DEAD: the liveness sweep owns this death —
                # killing/retiring here would yank the ring from under
                # its in-flight postmortem
        self._release_lease(lease_id)
        return True

    async def handle_cancel_lease_by_task(self, task_hex: str):
        """Drop a queued lease request for a cancelled task so it stops
        competing for resources (and never cold-starts a worker)."""
        for req in list(self.queued):
            if req.spec_meta.get("task_hex") == task_hex:
                if not req.future.done():
                    req.future.set_result({"canceled": True})
                self.queued.remove(req)
        return True

    async def handle_cancel_lease(self, lease_id: int):
        for req in list(self.queued):
            if req.lease_id == lease_id and not req.future.done():
                req.future.set_result({"rejected": True, "canceled": True})
                self.queued.remove(req)
        return True

    # ------------------------------------------------------------------
    # graceful drain (rolling upgrades / elastic scale-in; reference:
    # node_manager.cc HandleDrainRaylet + the autoscaler drain protocol)
    # ------------------------------------------------------------------

    def _begin_drain(self, reason: str = ""):
        if self._draining:
            return
        self._draining = True
        self._drain_reason = reason or "drain requested"
        logger.warning("raylet %s draining: %s", self.node_id[:12],
                       self._drain_reason)
        # Resolve every queued request NOW: spill it to a healthy node
        # or reject it with the draining marker — drain convergence
        # must not wait on requests this node will never grant.
        queued, self.queued = self.queued, []
        for req in queued:
            if req.future.done():
                continue
            spill = self._pick_spillback(req)
            if spill is not None:
                req.future.set_result({"spillback_to": spill})
            else:
                req.future.set_result(
                    {"rejected": True, "draining": True,
                     "error": "node is draining"})
        self._update_metrics()

    def _cancel_drain(self):
        if not self._draining:
            return
        logger.warning("raylet %s drain canceled", self.node_id[:12])
        self._draining = False
        self._drain_reason = ""
        self._update_metrics()
        self._pump_queue()

    async def handle_drain_self(self, phase: str = "all",
                                timeout_s: Optional[float] = None,
                                exit_process: bool = False,
                                reason: str = ""):
        """GCS-coordinated graceful drain of this raylet.

        ``phase="fence"`` raises the fence and returns immediately (the
        coordinator then migrates actors off this node);
        ``phase="wait"`` blocks until every in-flight lease is returned
        — idle leases come home via the owners' fairness-rotation /
        idle-cleaner ticks within ~lease_idle_timeout_s — or the
        deadline passes, at which point stragglers get postmortem-
        tagged SIGKILLs (kill_reason="drain_timeout" →
        DRAIN_TIMEOUT_KILLED), never a hang. ``exit_process=True`` asks
        a standalone raylet main to exit clean after replying.
        ``phase="cancel"`` lowers the fence and re-pumps the queue."""
        if phase == "cancel":
            self._cancel_drain()
            return {"draining": False}
        self._begin_drain(reason)
        if phase == "fence":
            return {"draining": True, "leases": len(self.leases),
                    "workers": len(self.workers)}
        budget = timeout_s if timeout_s is not None \
            else CONFIG.drain_timeout_s
        t0 = time.monotonic()
        deadline = t0 + budget
        while self.leases and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        killed: List[str] = []
        if self.leases:
            # Stragglers: killed, tagged so the postmortem taxonomy
            # reports DRAIN_TIMEOUT_KILLED with certainty rather than
            # guessing at a foreign SIGKILL.
            for worker_id, _demand, _pg in list(self.leases.values()):
                handle = self.workers.get(worker_id)
                if handle is None or handle.state == "DEAD":
                    continue
                logger.warning(
                    "drain deadline (%.1fs): killing straggler worker "
                    "%s (pid %s)", budget, handle.worker_id.hex()[:12],
                    handle.pid)
                handle.kill_reason = "drain_timeout"
                killed.append(handle.worker_id.hex())
                if handle.proc is not None:
                    try:
                        handle.proc.kill()
                    except Exception:
                        logger.debug("drain kill of pid %s failed",
                                     handle.pid, exc_info=True)
                else:
                    self._kill_worker(handle)
            # The death path (liveness sweep / dispose) releases their
            # leases and files the postmortems; wait briefly for the
            # fold, then force-release whatever is left.
            grace = time.monotonic() + 5.0
            while self.leases and time.monotonic() < grace:
                await asyncio.sleep(0.05)
            for lease_id in list(self.leases):
                self._release_lease(lease_id)
        # Idle/starting workers are never reused post-drain: reap them.
        for handle in list(self.workers.values()):
            if handle.state in ("IDLE", "STARTING") \
                    and handle.lease_id is None:
                self._kill_worker(handle)
        elapsed = time.monotonic() - t0
        from .runtime_metrics import runtime_metrics
        metrics = runtime_metrics()
        tags = {"node": str(self.node_index)}
        metrics.drain_latency.observe(elapsed, tags=tags)
        metrics.drains_completed.inc(tags=dict(
            tags, outcome="timeout" if killed else "clean"))
        self._gcs_event(
            "NODE_DRAINED",
            f"node {self.node_id[:12]} drained in {elapsed:.2f}s"
            + (f" ({len(killed)} stragglers killed)" if killed else ""),
            severity="WARNING" if killed else "INFO",
            elapsed_s=elapsed, stragglers_killed=killed,
            will_exit=exit_process)
        if exit_process and self.exit_requested is not None:
            # Reply first; a standalone raylet main (raylet_main.py)
            # wakes on the event and exits clean. In-process raylets
            # (local mode / the embedded head) just stay fenced.
            asyncio.get_running_loop().call_later(
                0.2, self.exit_requested.set)
        return {"drained": True, "elapsed_s": elapsed,
                "stragglers_killed": killed,
                "timed_out": bool(killed), "exiting": exit_process}

    # ------------------------------------------------------------------
    # placement group bundles (two-phase commit, raylet side)
    # ------------------------------------------------------------------

    async def handle_prepare_bundle(self, pg_id: PlacementGroupID,
                                    bundle_index: int,
                                    resources: Dict[str, float]):
        demand = ResourceSet(resources)
        key = (pg_id, bundle_index)
        if key in self.bundles:
            return True
        if not self.resources.try_allocate(demand):
            return False
        self.bundles[key] = BundleAccount(resources=demand, available=demand)
        return True

    async def handle_commit_bundle(self, pg_id: PlacementGroupID,
                                   bundle_index: int):
        account = self.bundles.get((pg_id, bundle_index))
        if account is None:
            return False
        account.committed = True
        self._pump_queue()
        return True

    async def handle_cancel_bundle(self, pg_id: PlacementGroupID,
                                   bundle_index: int):
        account = self.bundles.pop((pg_id, bundle_index), None)
        if account is not None:
            self.resources.release(account.resources)
            self._pump_queue()
        return True

    # ------------------------------------------------------------------
    # local object manager (reference: local_object_manager.cc + plasma
    # eviction + pull/push managers)
    # ------------------------------------------------------------------

    async def handle_seal_object(self, object_hex: str, size: int,
                                 owner_address: Optional[Address]):
        self.objects[object_hex] = ObjectEntry(size=size,
                                               last_access=time.monotonic())
        self.store_used += size
        gcs = self.clients.get(self.gcs_address)
        aio.spawn(gcs.call(
            "add_object_location", object_hex=object_hex,
            node_id=self.node_id, size=size, owner_address=owner_address,
            timeout=10), what="add_object_location")
        if self.store_used > self.capacity * CONFIG.object_spilling_threshold:
            aio.spawn(self._evict_until_under(), what="evict_until_under")
        return True

    async def _evict_until_under(self):
        target = self.capacity * CONFIG.object_spilling_threshold * 0.8
        victims = sorted(
            ((h, e) for h, e in self.objects.items() if e.pinned == 0),
            key=lambda kv: kv[1].last_access)
        gcs = self.clients.get(self.gcs_address)
        from .runtime_metrics import runtime_metrics
        metrics = runtime_metrics()
        tags = {"node": str(self.node_index)}
        for object_hex, entry in victims:
            if self.store_used <= target:
                break
            try:
                spill_t = time.monotonic()
                oid = ObjectID.from_hex(object_hex)
                if self.spill_storage is not None:
                    # Cloud spilling (reference: external_storage.py:398):
                    # ship the bytes through fsspec, free the local copy.
                    data = self.plasma.read_bytes(oid)
                    if data is None:
                        raise FileNotFoundError(object_hex)
                    path = await asyncio.get_running_loop().run_in_executor(
                        None, self.spill_storage.put, object_hex, data)
                    self.plasma.delete(oid)
                else:
                    path = self.plasma.spill_to(oid, self.spill_dir)
                entry.spilled_path = path
                self.store_used -= entry.size
                del self.objects[object_hex]
                self.spilled_objects[object_hex] = entry.size
                self.spilled_bytes += entry.size
                self.spilled_bytes_total += entry.size
                self.spill_count += 1
                metrics.store_spilled_total.inc(entry.size, tags=tags)
                metrics.store_spill_latency.observe(
                    time.monotonic() - spill_t, tags=tags)
                self._gcs_event(
                    "SPILL",
                    f"spilled {object_hex[:12]} ({entry.size} bytes)",
                    object_id=object_hex, size=entry.size, path=path)
                await gcs.call("add_spilled_location",
                               object_hex=object_hex, path=path, timeout=10)
                await gcs.call("remove_object_location",
                               object_hex=object_hex, node_id=self.node_id,
                               timeout=10)
            except FileNotFoundError:
                self.objects.pop(object_hex, None)
            except Exception:
                logger.exception("spill of %s failed", object_hex[:12])

    async def handle_pull_object(self, object_hex: str):
        """Ensure the object is locally readable; used by workers on get()."""
        oid = ObjectID.from_hex(object_hex)
        entry = self.objects.get(object_hex)
        if entry is not None:
            entry.last_access = time.monotonic()
            return {"ok": True}
        # Deduplicate concurrent pulls.
        pending = self._pulls.get(object_hex)
        if pending is not None:
            return await pending
        fut = asyncio.get_running_loop().create_future()
        self._pulls[object_hex] = fut
        try:
            result = await self._pull_object(oid, object_hex)
            if not fut.done():
                fut.set_result(result)
            return result
        except Exception as e:
            if not fut.done():
                fut.set_exception(e)
            raise
        finally:
            self._pulls.pop(object_hex, None)

    async def _pull_object(self, oid: ObjectID, object_hex: str):
        # A push of this object may be assembling right now — it owns the
        # store's tmp file, so wait for it rather than racing the create.
        if object_hex in self._push_assembly:
            deadline = time.monotonic() + 120
            while object_hex in self._push_assembly:
                if time.monotonic() > deadline:
                    break
                await asyncio.sleep(0.05)
            if self.plasma.contains(oid):
                size = self.plasma.size_of(oid)
                self.objects.setdefault(object_hex, ObjectEntry(
                    size=size, last_access=time.monotonic()))
                return {"ok": True}
        gcs = self.clients.get(self.gcs_address)
        info = await gcs.call("get_object_locations", object_hex=object_hex,
                              timeout=10)
        spilled = info.get("spilled")
        if spilled and "://" in spilled and self.spill_storage is not None:
            restore_t = time.monotonic()
            data = await asyncio.get_running_loop().run_in_executor(
                None, self.spill_storage.get, spilled)
            if data is not None:
                self.plasma.write_bytes(oid, data)
                size = len(data)
                self.objects[object_hex] = ObjectEntry(
                    size=size, last_access=time.monotonic())
                self.store_used += size
                self._record_restore(object_hex, size,
                                     time.monotonic() - restore_t)
                await gcs.call("add_object_location",
                               object_hex=object_hex,
                               node_id=self.node_id,
                               size=info.get("size", size),
                               owner_address=info.get("owner"), timeout=10)
                return {"ok": True}
        if spilled and "://" not in spilled and os.path.exists(spilled):
            restore_t = time.monotonic()
            self.plasma.restore_from(oid, spilled)
            size = self.plasma.size_of(oid)
            self.objects[object_hex] = ObjectEntry(
                size=size, last_access=time.monotonic())
            self.store_used += size
            self._record_restore(object_hex, size,
                                 time.monotonic() - restore_t)
            await gcs.call("add_object_location", object_hex=object_hex,
                           node_id=self.node_id, size=info.get("size", size),
                           owner_address=info.get("owner"), timeout=10)
            return {"ok": True}
        # Randomize replica choice so a broadcast storm spreads across the
        # nodes that already hold a copy instead of funnelling into the
        # first-listed (usually the origin) node.
        candidates = list(info.get("nodes", []))
        random.shuffle(candidates)
        if self.node_id in info.get("nodes", []):
            candidates.insert(0, self.node_id)
        for node_id in candidates:
            if node_id == self.node_id:
                if self.plasma.contains(oid):
                    size = self.plasma.size_of(oid)
                    self.objects[object_hex] = ObjectEntry(
                        size=size, last_access=time.monotonic())
                    self.store_used += size
                    return {"ok": True}
                continue
            addr = self.node_addresses.get(node_id)
            if addr is None:
                nodes = await gcs.call("get_all_nodes", timeout=10)
                for n in nodes:
                    self.node_addresses[n["node_id"]] = tuple(n["address"])
                addr = self.node_addresses.get(node_id)
            if addr is None:
                continue
            try:
                await self._fetch_from(addr, oid, object_hex)
                return {"ok": True}
            except Exception as e:
                logger.warning("pull of %s from %s failed: %s",
                               object_hex[:12], node_id[:12], e)
        return {"ok": False, "error": "no reachable copy"}

    async def _fetch_from(self, addr: Address, oid: ObjectID,
                          object_hex: str):
        peer = self.clients.get(addr)
        meta = await peer.call("object_info", object_hex=object_hex,
                               timeout=30)
        size = meta["size"]
        chunk = CONFIG.object_store_chunk_bytes
        buf = self.plasma.create(oid, size)
        try:
            # Windowed parallel chunk fetch (reference: pull_manager.cc
            # keeps several chunk requests in flight): overlaps the
            # peer's read+serialize with our write.
            sem = asyncio.Semaphore(4)

            async def _one(offset: int, n: int):
                async with sem:
                    data = await peer.call(
                        "fetch_chunk", object_hex=object_hex,
                        offset=offset, length=n, timeout=60)
                    buf[offset:offset + len(data)] = data
            tasks = [asyncio.ensure_future(
                _one(off, min(chunk, size - off)))
                for off in range(0, size, chunk)]
            try:
                await asyncio.gather(*tasks)
            except BaseException:
                for t in tasks:  # stop siblings before releasing buf
                    t.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
                raise
        except Exception:
            buf.release()
            self.plasma.abort(oid)
            raise
        buf.release()
        self.plasma.seal(oid)
        self.objects[object_hex] = ObjectEntry(size=size,
                                               last_access=time.monotonic())
        self.store_used += size
        gcs = self.clients.get(self.gcs_address)
        await gcs.call("add_object_location", object_hex=object_hex,
                       node_id=self.node_id, size=size,
                       owner_address=None, timeout=10)

    def _record_restore(self, object_hex: str, size: int, latency_s: float):
        """Fold one spill-restore into the accounting + metrics + event
        log (both the cloud and the local-disk restore paths land here)."""
        self.restored_bytes_total += size
        self.restore_count += 1
        spilled_size = self.spilled_objects.pop(object_hex, None)
        if spilled_size is not None:
            self.spilled_bytes -= spilled_size
        from .runtime_metrics import runtime_metrics
        tags = {"node": str(self.node_index)}
        runtime_metrics().store_restored_total.inc(size, tags=tags)
        runtime_metrics().store_restore_latency.observe(latency_s,
                                                        tags=tags)
        self._gcs_event("RESTORE",
                        f"restored {object_hex[:12]} ({size} bytes)",
                        object_id=object_hex, size=size)

    async def handle_object_info(self, object_hex: str):
        oid = ObjectID.from_hex(object_hex)
        entry = self.objects.get(object_hex)
        if entry is None or not self.plasma.contains(oid):
            raise KeyError(f"object {object_hex[:12]} not local")
        return {"size": self.plasma.size_of(oid)}

    async def handle_fetch_chunk(self, object_hex: str, offset: int,
                                 length: int):
        oid = ObjectID.from_hex(object_hex)
        view = self.plasma.map_read(oid)
        if view is None:
            raise KeyError(f"object {object_hex[:12]} not local")
        try:
            return bytes(view[offset:offset + length])
        finally:
            view.release()

    # ------------------------------------------------------------------
    # push-based broadcast (reference: src/ray/object_manager/
    # push_manager.cc — owner-initiated chunked pushes; here arranged as
    # a binary forwarding tree so source egress is O(2N) regardless of
    # the receiver count, and every tree level streams in parallel)
    # ------------------------------------------------------------------

    @staticmethod
    def _tree_split(nodes: List) -> List[List]:
        """Binary forwarding-tree split: two contiguous halves, each led
        by its first element."""
        mid = (len(nodes) + 1) // 2
        return [g for g in (nodes[:mid], nodes[mid:]) if g]

    async def handle_profile_worker(self, pid: int, kind: str = "pystack",
                                    duration_s: float = 1.0):
        """Forward a profile capture to the worker with `pid` on this
        node (reference: reporter agent routing profile requests)."""
        for handle in self.workers.values():
            if handle.pid == pid and handle.address is not None:
                client = self.clients.get(handle.address)
                return await client.call(
                    "capture_profile", kind=kind, duration_s=duration_s,
                    timeout=duration_s + 60)
        return {"error": f"no worker with pid {pid} on this node"}

    # ------------------------------------------------------------------
    # continuous profiling plane (the get_memory_report fan-out pattern:
    # the raylet IS the node agent — one RPC profiles the whole node)
    # ------------------------------------------------------------------

    def _profiling_targets(self) -> List[WorkerHandle]:
        return [h for h in self.workers.values()
                if h.address is not None and h.state != "DEAD"]

    async def handle_start_profiling(self, hz: Optional[float] = None,
                                     ring_size: Optional[int] = None):
        from . import profiler
        return profiler.start_profiling(hz=hz, ring_size=ring_size)

    async def handle_stop_profiling(self):
        from . import profiler
        return profiler.stop_profiling()

    async def handle_get_profile(self, clear: bool = True,
                                 stop: bool = False):
        from . import profiler
        report = profiler.get_profile(clear=clear, stop=stop)
        report["node_id"] = self.node_id
        report["node_index"] = self.node_index
        report["component"] = "raylet"
        return report

    async def handle_profile_node(self, duration_s: float = 2.0,
                                  hz: Optional[float] = None):
        """Sample every process on this node for `duration_s`: the
        raylet's own process plus all live workers, started and
        collected CONCURRENTLY. A worker that refuses (kill switch) or
        dies mid-capture contributes an error row, not a gap. Samplers
        this call started are stopped after collection; an
        already-running (continuous-mode) sampler is left running."""
        from . import profiler
        duration_s = min(float(duration_s), 60.0)
        hz = hz or CONFIG.profiler_hz
        own_start = profiler.start_profiling(hz=hz)
        targets = self._profiling_targets()

        async def _start(handle):
            try:
                return await self.clients.get(handle.address).call(
                    "start_profiling", hz=hz, timeout=10)
            except Exception as e:  # noqa: BLE001 — surfaced as a row
                return {"error": str(e)}

        starts = list(await asyncio.gather(
            *(_start(h) for h in targets))) if targets else []

        # A continuous-mode sampler that was already running has a ring
        # full of pre-window backlog — drain (discard) it now so the
        # post-window collection holds only this capture's samples.
        async def _predrain(handle):
            try:
                await self.clients.get(handle.address).call(
                    "get_profile", clear=True, stop=False, timeout=10)
            except Exception:  # noqa: BLE001 — collect will surface it
                logger.debug("profiler pre-drain failed", exc_info=True)

        stale = [h for h, s in zip(targets, starts)
                 if s.get("already_running")]
        if own_start.get("already_running"):
            profiler.get_profile(clear=True)
        if stale:
            await asyncio.gather(*(_predrain(h) for h in stale))
        await asyncio.sleep(duration_s)
        reports: List[Dict[str, Any]] = []
        errors: List[Dict[str, Any]] = []

        async def _collect(handle, started):
            if started.get("error") or not started.get("running"):
                errors.append({
                    "node_id": self.node_id, "pid": handle.pid,
                    "worker_id": handle.worker_id.hex(),
                    "error": started.get("error", "sampler not running")})
                return
            try:
                reports.append(await asyncio.wait_for(
                    self.clients.get(handle.address).call(
                        "get_profile", clear=True,
                        stop=not started.get("already_running"),
                        timeout=15), 20))
            except Exception as e:  # noqa: BLE001 — surfaced as a row
                errors.append({
                    "node_id": self.node_id, "pid": handle.pid,
                    "worker_id": handle.worker_id.hex(),
                    "error": str(e)})

        if targets:
            await asyncio.gather(
                *(_collect(h, s) for h, s in zip(targets, starts)))
        if own_start.get("running"):
            own = profiler.get_profile(
                clear=True, stop=not own_start.get("already_running"))
            own.update(node_id=self.node_id, node_index=self.node_index,
                       component="raylet")
            reports.append(own)
        else:
            errors.append({"node_id": self.node_id, "pid": os.getpid(),
                           "component": "raylet",
                           "error": own_start.get(
                               "error", "sampler not running")})
        return {"node_id": self.node_id, "node_index": self.node_index,
                "hz": hz, "reports": reports, "errors": errors}

    async def handle_profiling_status(self):
        """Sampler status for every process on this node."""
        from . import profiler
        rows = [dict(profiler.profiling_status(), component="raylet",
                     node_id=self.node_id)]
        targets = self._profiling_targets()

        async def _one(handle):
            try:
                rows.append(await asyncio.wait_for(
                    self.clients.get(handle.address).call(
                        "profiling_status", timeout=10), 15))
            except Exception as e:  # noqa: BLE001 — surfaced as a row
                rows.append({"node_id": self.node_id, "pid": handle.pid,
                             "error": str(e)})
        if targets:
            await asyncio.gather(*(_one(h) for h in targets))
        return {"node_id": self.node_id, "node_index": self.node_index,
                "processes": rows}

    async def handle_stack_dump_node(self):
        """One-shot stack dump of every process on this node (the
        `cli stack` backend): the raylet's own threads plus every live
        worker's full dump, fetched concurrently."""
        from . import profiler
        rows: List[Dict[str, Any]] = [{
            "node_id": self.node_id, "node_index": self.node_index,
            "pid": os.getpid(), "component": "raylet",
            "text": profiler.stack_dump_text(),
        }]
        targets = self._profiling_targets()

        async def _one(handle):
            try:
                text = await asyncio.wait_for(
                    self.clients.get(handle.address).call(
                        "dump_stacks", quiet=True, timeout=15), 20)
                rows.append({
                    "node_id": self.node_id,
                    "node_index": self.node_index,
                    "pid": handle.pid, "component": "worker",
                    "worker_id": handle.worker_id.hex(),
                    "text": text if isinstance(text, str) else "",
                })
            except Exception as e:  # noqa: BLE001 — surfaced as a row
                rows.append({"node_id": self.node_id, "pid": handle.pid,
                             "worker_id": handle.worker_id.hex(),
                             "error": str(e)})
        if targets:
            await asyncio.gather(*(_one(h) for h in targets))
        return rows

    async def handle_push_object(self, object_hex: str,
                                 target_node_ids: Optional[List[str]] = None):
        """Push a locally-held object to `target_node_ids` (default: every
        other alive node). Returns when all receivers have sealed it."""
        oid = ObjectID.from_hex(object_hex)
        if not self.plasma.contains(oid):
            return {"ok": False, "error": "object not local to this node"}
        size = self.plasma.size_of(oid)
        if target_node_ids is None:
            target_node_ids = [nid for nid in self.cluster_view
                               if nid != self.node_id]
        addrs = []
        for nid in target_node_ids:
            if nid == self.node_id:
                continue
            addr = self.node_addresses.get(nid)
            if addr is not None:
                addrs.append(tuple(addr))
        if not addrs:
            return {"ok": True, "receivers": 0}
        await self._push_stream(oid, object_hex, size, addrs)
        return {"ok": True, "receivers": len(addrs)}

    async def _push_stream(self, oid, object_hex: str, size: int,
                           addrs: List[Address]):
        """Stream chunks to the two tree children (each forwarding to its
        own subtree), windowed for pipelining."""
        groups = self._tree_split(addrs)
        chunk = CONFIG.object_store_chunk_bytes
        view = self.plasma.map_read(oid)
        if view is None:
            raise KeyError(f"object {object_hex[:12]} vanished mid-push")
        sem = asyncio.Semaphore(4)

        async def _send(group, offset, n):
            peer = self.clients.get(group[0])
            async with sem:
                data = bytes(view[offset:offset + n])
                await peer.call(
                    "push_chunk", object_hex=object_hex, size=size,
                    offset=offset, data=data,
                    forward_to=list(group[1:]), timeout=120)
        tasks = [asyncio.ensure_future(
            _send(group, off, min(chunk, size - off)))
            for group in groups for off in range(0, size, chunk)]
        try:
            await asyncio.gather(*tasks)
        except BaseException:
            # siblings must stop touching the view before we release it
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            raise
        finally:
            view.release()

    async def handle_push_chunk(self, object_hex: str, size: int,
                                offset: int, data: bytes,
                                forward_to: List):
        """Receive one pushed chunk, forward it down the subtree, seal on
        completion. Replies only after local write + forward, so the
        sender's window regulates the whole pipeline. Forwarding happens
        even when the local copy is skipped (already held, or a pull of
        the same object is in flight) — the subtree must still be fed."""
        oid = ObjectID.from_hex(object_hex)
        skip_local = object_hex in self._pulls  # pull owns the tmp file
        assy = None
        if not skip_local:
            assy = self._push_assembly.get(object_hex)
            if assy is None:
                if self.plasma.contains(oid):
                    skip_local = True
                else:
                    buf = self.plasma.create(oid, size)
                    assy = {"buf": buf, "received": 0, "size": size,
                            "offsets": set(), "t": time.monotonic()}
                    self._push_assembly[object_hex] = assy
        if assy is not None:
            if offset not in assy["offsets"]:  # dedup concurrent pushes
                assy["buf"][offset:offset + len(data)] = data
                assy["received"] += len(data)
                assy["offsets"].add(offset)
            assy["t"] = time.monotonic()
        if forward_to:
            await asyncio.gather(*[
                self.clients.get(tuple(g[0])).call(
                    "push_chunk", object_hex=object_hex, size=size,
                    offset=offset, data=data, forward_to=list(g[1:]),
                    timeout=120)
                for g in self._tree_split(forward_to)])
        if assy is None:
            return {"ok": True, "dup": True}
        # Single-seal guard: concurrent chunk handlers resume from their
        # forwarding awaits after completion; only the first may seal.
        if assy["received"] >= size and not assy.get("sealed"):
            assy["sealed"] = True
            self._push_assembly.pop(object_hex, None)
            assy["buf"].release()
            self.plasma.seal(oid)
            self.objects[object_hex] = ObjectEntry(
                size=size, last_access=time.monotonic())
            self.store_used += size
            gcs = self.clients.get(self.gcs_address)
            aio.spawn(gcs.call(
                "add_object_location", object_hex=object_hex,
                node_id=self.node_id, size=size, owner_address=None,
                timeout=10), what="add_object_location")
        return {"ok": True}

    async def handle_free_objects(self, object_hexes: List[str]):
        for object_hex in object_hexes:
            entry = self.objects.pop(object_hex, None)
            if entry is not None:
                self.store_used -= entry.size
            spilled_size = self.spilled_objects.pop(object_hex, None)
            if spilled_size is not None:
                self.spilled_bytes -= spilled_size
            self.plasma.delete(ObjectID.from_hex(object_hex))
        return True

    async def handle_pin_object(self, object_hex: str, delta: int = 1):
        entry = self.objects.get(object_hex)
        if entry is not None:
            entry.pinned = max(0, entry.pinned + delta)
        return entry is not None

    # ------------------------------------------------------------------
    # misc
    # ------------------------------------------------------------------

    async def handle_ping(self):
        return "pong"

    # -- chaos harness (cli chaos / tests) -----------------------------

    async def handle_set_chaos(self, spec: str = "", seed: int = 0,
                               schedule: Optional[str] = None):
        from . import chaos
        return await chaos.handle_set_chaos(spec=spec, seed=seed,
                                            schedule=schedule)

    async def handle_chaos_kill_worker(self, worker_hex: str = "",
                                       pid: int = 0):
        """SIGKILL one of this raylet's workers (`cli chaos kill-worker`
        / tests): by worker hex or raw pid. Gated like kill-gcs."""
        if not CONFIG.chaos_allow_kill:
            raise PermissionError(
                "chaos kill refused: set RTPU_CHAOS_ALLOW_KILL=1 on the "
                "raylet process to allow it")
        from . import chaos
        if worker_hex:
            handle = next((h for h in self.workers.values()
                           if h.worker_id.hex().startswith(worker_hex)),
                          None)
            if handle is None:
                return False
            pid = handle.pid
        if not pid:
            return False
        return chaos.kill_pid(pid)

    async def handle_get_memory_report(self, limit: int = 10_000,
                                       include_workers: bool = True):
        """Node memory report: raylet store accounting (capacity,
        resident/pinned/spilled bytes, per-object pin counts + LRU age)
        plus every local worker's owner-side reference report, fetched
        concurrently (reference: LocalObjectManager::RecordMetrics +
        node_manager's FormatGlobalMemoryInfo fan-in)."""
        now = time.monotonic()
        rows = []
        for object_hex, entry in self.objects.items():
            rows.append({"object_id": object_hex, "size": entry.size,
                         "pinned": entry.pinned,
                         "age_s": now - entry.last_access,
                         "spilled": False})
            if len(rows) >= limit:
                break
        for object_hex, size in self.spilled_objects.items():
            if len(rows) >= limit:
                break
            rows.append({"object_id": object_hex, "size": size,
                         "pinned": 0, "age_s": None, "spilled": True})
        report = {
            "node_id": self.node_id,
            "node_index": self.node_index,
            "store": {
                "capacity": self.capacity,
                "used_bytes": self.store_used,
                "pinned_bytes": sum(e.size for e in self.objects.values()
                                    if e.pinned > 0),
                "num_objects": len(self.objects),
                "spilled_bytes": self.spilled_bytes,
                "num_spilled": len(self.spilled_objects),
                "spilled_bytes_total": self.spilled_bytes_total,
                "restored_bytes_total": self.restored_bytes_total,
                "spill_count": self.spill_count,
                "restore_count": self.restore_count,
            },
            "mem_pressure": self._mem_pressure,
            "objects": rows,
            "workers": [],
        }
        if include_workers:
            targets = [h for h in self.workers.values()
                       if h.address is not None and h.state != "DEAD"]

            async def _one(handle):
                try:
                    return await asyncio.wait_for(
                        self.clients.get(handle.address).call(
                            "get_memory_report", limit=limit,
                            timeout=10), 15)
                except Exception as e:  # noqa: BLE001 — report the gap
                    return {"worker_id": handle.worker_id.hex(),
                            "node_id": self.node_id, "pid": handle.pid,
                            "error": str(e)}
            if targets:
                report["workers"] = list(await asyncio.gather(
                    *(_one(h) for h in targets)))
        return report

    async def handle_get_logs(self, job: Optional[str] = None,
                              task: Optional[str] = None,
                              actor: Optional[str] = None,
                              level: Optional[str] = None,
                              grep: Optional[str] = None,
                              tail: Optional[int] = None,
                              since: Optional[Dict[str, int]] = None,
                              limit: int = 1000,
                              pid: Optional[int] = None,
                              include_dead: bool = True):
        """Query this node's worker log rings (live + retained dead).
        Filters: job/task/actor hex (prefix for ids), min `level`,
        `grep` regex, `tail`-N after the merge; `since` is the cursor
        dict a previous reply returned ({worker_hex: seq}) — pass it
        back to follow (only lines newer than the cursor return)."""
        since = since or {}
        limit = max(1, min(int(limit), 10_000))
        rows: List[Dict[str, Any]] = []
        cursors: Dict[str, int] = {}
        matched_counts: Dict[str, int] = {}
        scan_complete: Dict[str, int] = {}  # worker -> seq scanned to
        dropped = 0
        for ring in self.log_rings.all_rings():
            if not include_dead and not ring.alive:
                continue
            if pid is not None and ring.pid != pid:
                continue
            since_seq = int(since.get(ring.worker_hex, 0))
            cursors[ring.worker_hex] = since_seq
            # end-of-scan seq is captured BEFORE the query: an append
            # racing in between must not be fast-forwarded over (it
            # lands at a seq above this bound and the next poll gets it)
            end_seq = ring.next_seq
            matched = ring.query(
                job=job, task=task, actor=actor, level=level, grep=grep,
                since_seq=since_seq, limit=limit)
            matched_counts[ring.worker_hex] = len(matched)
            if len(matched) < limit:
                # the scan reached the ring's end — everything up to
                # end_seq was either matched or filtered out
                scan_complete[ring.worker_hex] = end_seq
            dropped += ring.dropped
            rows.extend(matched)
        rows.sort(key=lambda e: (e["ts"], e["seq"]))
        if tail:
            rows = rows[-max(1, int(tail)):]
        rows = rows[:limit]
        # Follow-cursor contract: advance a worker's cursor only past
        # lines actually RETURNED, or past fully scanned-and-filtered
        # ranges. Truncation (per-ring limit, the global limit, or
        # tail) must never fast-forward a follower over lines it was
        # not handed. Per ring, ts and seq are both monotonic, so
        # global-limit truncation drops a ring's HIGHEST seqs (safe to
        # cursor at the returned max) while tail drops its lowest
        # (skipping those is exactly what tail asks for).
        returned: Dict[str, int] = {}
        for r in rows:
            w = r["worker_id"]
            returned[w] = returned.get(w, 0) + 1
            if r["seq"] > cursors.get(w, 0):
                cursors[w] = r["seq"]
        for w, end_seq in scan_complete.items():
            if returned.get(w, 0) == matched_counts.get(w, 0):
                # every matched line of this ring was returned and the
                # scan was complete: skip the filtered-out remainder
                cursors[w] = max(cursors[w], end_seq)
        rows = [dict(r, node_id=self.node_id,
                     node_index=self.node_index) for r in rows]
        return {"node_id": self.node_id, "node_index": self.node_index,
                "lines": rows, "cursors": cursors, "dropped": dropped,
                "disabled": CONFIG.no_log_plane}

    async def handle_list_logs(self):
        """Ring inventory for this node: one meta row per worker ring
        (live and retained-dead) — line/drop/byte counts and the
        first/last timestamps, no line payloads."""
        return {"node_id": self.node_id, "node_index": self.node_index,
                "disabled": CONFIG.no_log_plane,
                "pub_dropped_lines": self._log_pub_window.dropped_lines,
                "rings": [dict(r.meta(), node_id=self.node_id,
                               node_index=self.node_index)
                          for r in self.log_rings.all_rings()]}

    async def handle_get_accel_report(self, include_workers: bool = True):
        """Node accelerator report: every local worker's device/compile/
        step telemetry, fetched concurrently (the get_memory_report
        fan-out pattern — the raylet IS the node agent). The raylet's
        own process never initializes jax, so its row is just the node
        wrapper."""
        report: Dict[str, Any] = {
            "node_id": self.node_id,
            "node_index": self.node_index,
            "workers": [],
        }
        if include_workers:
            targets = [h for h in self.workers.values()
                       if h.address is not None and h.state != "DEAD"]

            async def _one(handle):
                try:
                    return await asyncio.wait_for(
                        self.clients.get(handle.address).call(
                            "get_accel_report", timeout=10), 15)
                except Exception as e:  # noqa: BLE001 — report the gap
                    return {"worker_id": handle.worker_id.hex(),
                            "node_id": self.node_id, "pid": handle.pid,
                            "error": str(e)}
            if targets:
                report["workers"] = list(await asyncio.gather(
                    *(_one(h) for h in targets)))
        return report

    async def handle_get_rpc_stats(self):
        """Transport-observatory introspection for this raylet process
        (state.rpc_summary() merges these with the driver/worker rows)."""
        from . import rpc_metrics
        stats = rpc_metrics.local_stats()
        stats["node_id"] = self.node_id
        stats["mode"] = "raylet"
        return stats

    async def handle_get_node_stats(self):
        return {
            "node_id": self.node_id,
            "resources_total": self.resources.total.to_dict(),
            "resources_available": self.resources.available.to_dict(),
            "num_workers": len(self.workers),
            "num_leases": len(self.leases),
            "num_queued_leases": len(self.queued),
            "draining": self._draining,
            "queue_ages": self._queue_ages(),
            "object_store_used": self.store_used,
            "object_store_capacity": self.capacity,
            "num_objects": len(self.objects),
            "labels": self.labels,
            "workers": [
                {"worker_id": h.worker_id.hex(), "pid": h.pid,
                 "state": h.state,
                 "is_actor_worker": h.is_actor_worker}
                for h in self.workers.values()
            ],
        }
