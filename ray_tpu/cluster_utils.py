"""Multi-node test cluster on one machine
(reference: python/ray/cluster_utils.py — Cluster, add_node).

The head node (GCS + raylet) runs in-process; `add_node` launches additional
raylets as real subprocesses, giving genuine multi-node semantics — separate
object stores, cross-node object transfer, node kill/failure tests — without
containers. This fixture carries most of the reference's distributed test
coverage (SURVEY §4.2).

Fleet-operations extensions (rolling upgrades / chaos soak substrate):
``external_gcs=True`` runs the GCS as a real subprocess (killable with
SIGKILL and restartable at the same port — the PR-10 incarnation
reconnect-and-replay drill), ``restart_node`` performs one rolling-
restart step (GCS-coordinated drain → clean exit → fresh raylet at the
same index), and ``kill_gcs``/``restart_gcs`` are the head-failover
primitives the soak bench schedules."""

from __future__ import annotations

import json
import logging
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ._internal.node import Node, new_session_name
from ._internal.rpc import Address

logger = logging.getLogger(__name__)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_ready_line(proc: subprocess.Popen, marker: str,
                     what: str, timeout_s: float = 60.0) -> str:
    """Wait for a subprocess's readiness protocol line WITHOUT a
    blocking readline — a wedged child that prints nothing must trip
    the deadline, not hang the caller forever."""
    import select
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"{what} subprocess exited rc={proc.returncode}")
        ready, _, _ = select.select([proc.stdout], [], [], 0.5)
        if not ready:
            continue
        line = proc.stdout.readline()
        if line.startswith(marker):
            return line
    raise TimeoutError(f"{what} did not come up in {timeout_s:.0f}s")


def spawn_gcs(port: int, session: str, persist: Optional[str] = None,
              env: Optional[Dict[str, str]] = None) -> subprocess.Popen:
    """Run a GCS as a real subprocess (gcs_main) and wait for its
    readiness line — the killable head for failover drills."""
    # a GCS holds no chip: forced, since a chip host's ambient
    # environment names the TPU platform (setdefault would keep it)
    proc_env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc_env.update(env or {})
    cmd = [sys.executable, "-m", "ray_tpu._internal.gcs_main",
           "--host", "127.0.0.1", "--port", str(port),
           "--session", session]
    if persist:
        cmd += ["--persist-path", persist]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=None,
                            env=proc_env, text=True)
    _wait_ready_line(proc, "RTPU_GCS_READY", "gcs")
    return proc


class RemoteNodeHandle:
    def __init__(self, proc: subprocess.Popen, node_id: str, address: Address,
                 node_index: int, resources: Optional[Dict] = None,
                 labels: Optional[Dict] = None,
                 object_store_memory: int = 0,
                 env: Optional[Dict[str, str]] = None):
        self.proc = proc
        self.node_id = node_id
        self.address = address
        self.node_index = node_index
        # Spawn spec retained so restart_node can relaunch an identical
        # raylet (fresh node id) after a drain.
        self.resources = dict(resources or {})
        self.labels = dict(labels or {})
        self.object_store_memory = object_store_memory
        self.env = dict(env or {})


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[Dict] = None,
                 external_gcs: bool = False,
                 gcs_persist_path: Optional[str] = None,
                 gcs_env: Optional[Dict[str, str]] = None):
        self.session_name = new_session_name()
        self.head_node: Optional[Node] = None
        self.remote_nodes: List[RemoteNodeHandle] = []
        self._next_index = 1
        self._connected = False
        self.gcs_proc: Optional[subprocess.Popen] = None
        self._gcs_port: Optional[int] = None
        self._gcs_persist = gcs_persist_path
        self._gcs_env = dict(gcs_env or {})
        if initialize_head:
            args = dict(head_node_args or {})
            system_config = args.pop("_system_config", None)
            if system_config:
                from ._internal.config import CONFIG
                CONFIG.apply_system_config(system_config)
            gcs_address = None
            if external_gcs:
                # Killable control plane: the GCS lives in its own
                # process at a FIXED port (restarts keep the address, so
                # reconnecting clients need no rediscovery).
                self._gcs_port = free_port()
                self.gcs_proc = spawn_gcs(
                    self._gcs_port, self.session_name,
                    persist=self._gcs_persist, env=self._gcs_env)
                gcs_address = ("127.0.0.1", self._gcs_port)
            self.head_node = Node(
                head=not external_gcs, is_head=True,
                session_name=self.session_name,
                gcs_address=gcs_address,
                resources=args.get("resources",
                                   {"CPU": args.get("num_cpus", 2)}),
                labels=args.get("labels"),
                object_store_memory=args.get("object_store_memory"))
            self.head_node.start()

    @property
    def gcs_address(self) -> Address:
        return self.head_node.gcs_address

    @property
    def address(self) -> str:
        host, port = self.gcs_address
        return f"{host}:{port}"

    def connect(self, namespace: str = ""):
        """Attach the current process as the driver."""
        import ray_tpu
        worker = ray_tpu.init(_node=self.head_node, namespace=namespace)
        self._connected = True
        return worker

    def add_node(self, num_cpus: float = 2, num_tpus: float = 0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 object_store_memory: int = 0,
                 env: Optional[Dict[str, str]] = None,
                 wait: bool = True,
                 node_index: Optional[int] = None) -> RemoteNodeHandle:
        node_resources = dict(resources or {})
        node_resources.setdefault("CPU", num_cpus)
        if num_tpus:
            node_resources["TPU"] = num_tpus
        if node_index is None:
            index = self._next_index
            self._next_index += 1
        else:
            index = node_index
        cmd = [
            sys.executable, "-m", "ray_tpu._internal.raylet_main",
            "--gcs-address", self.address,
            "--session", self.session_name,
            "--node-index", str(index),
            "--resources", json.dumps(node_resources),
            "--labels", json.dumps(labels or {}),
        ]
        if object_store_memory:
            cmd += ["--object-store-memory", str(object_store_memory)]
        proc_env = dict(os.environ)
        proc_env.update(env or {})
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=None, env=proc_env, text=True)
        node_id, address = None, None
        if wait:
            line = _wait_ready_line(proc, "RTPU_RAYLET_READY", "raylet")
            _, node_id, addr = line.split()
            host, port = addr.rsplit(":", 1)
            address = (host, int(port))
        handle = RemoteNodeHandle(proc, node_id, address, index,
                                  resources=node_resources,
                                  labels=labels,
                                  object_store_memory=object_store_memory,
                                  env=env)
        self.remote_nodes.append(handle)
        return handle

    def remove_node(self, handle: RemoteNodeHandle,
                    allow_graceful: bool = False):
        """Kill a node (SIGKILL unless graceful) — failure-injection
        primitive for fault-tolerance tests."""
        if allow_graceful:
            handle.proc.terminate()
        else:
            handle.proc.kill()
        handle.proc.wait(timeout=30)
        self.remote_nodes.remove(handle)

    # -- fleet operations (rolling upgrades / head failover) -----------

    def drain_node(self, handle: RemoteNodeHandle,
                   timeout_s: Optional[float] = None,
                   exit_process: bool = False) -> Dict:
        """GCS-coordinated graceful drain of one subprocess raylet
        (requires a connected driver for the state API)."""
        from ray_tpu.util.state import api as state_api
        return state_api.drain_node(handle.node_id, timeout_s=timeout_s,
                                    exit_process=exit_process)

    def restart_node(self, handle: RemoteNodeHandle,
                     drain: bool = True,
                     timeout_s: Optional[float] = None,
                     wait: bool = True) -> RemoteNodeHandle:
        """One rolling-restart step: gracefully drain the raylet (fence
        → actor migration → in-flight leases → clean exit), then launch
        a replacement at the same index (fresh node id) and wait for it
        to register. With ``drain=False`` it is a crash-restart
        (SIGKILL) instead."""
        report: Dict = {}
        if drain:
            report = self.drain_node(handle, timeout_s=timeout_s,
                                     exit_process=True)
            if report.get("error"):
                raise RuntimeError(f"drain failed: {report['error']}")
            try:
                handle.proc.wait(timeout=(timeout_s or 60) + 30)
            except subprocess.TimeoutExpired:
                logger.warning("drained raylet %s did not exit; killing",
                               handle.node_id[:12])
                handle.proc.kill()
                handle.proc.wait(timeout=30)
            self.remote_nodes.remove(handle)
        else:
            self.remove_node(handle)
        replacement = self.add_node(
            resources=handle.resources, labels=handle.labels,
            object_store_memory=handle.object_store_memory,
            env=handle.env, wait=wait, node_index=handle.node_index)
        replacement.drain_report = report
        return replacement

    def rolling_restart(self, timeout_s: Optional[float] = None,
                        between=None) -> List[RemoteNodeHandle]:
        """Drain-and-replace every subprocess raylet one by one (the
        `cli rollout` flow against an in-test cluster). ``between`` is
        an optional callback run after each node (the soak bench
        injects its mid-rollout GCS kill there)."""
        replaced = []
        for handle in list(self.remote_nodes):
            replaced.append(self.restart_node(handle,
                                              timeout_s=timeout_s))
            if between is not None:
                between(replaced[-1])
        return replaced

    def kill_gcs(self):
        """SIGKILL the external GCS subprocess (head-failover drill)."""
        if self.gcs_proc is None:
            raise RuntimeError("kill_gcs requires external_gcs=True")
        self.gcs_proc.kill()
        self.gcs_proc.wait(timeout=30)

    def restart_gcs(self):
        """Respawn the external GCS at the SAME port (clients reconnect
        with no rediscovery; with a persist path the state recovers via
        WAL replay and the incarnation bumps)."""
        if self._gcs_port is None:
            raise RuntimeError("restart_gcs requires external_gcs=True")
        if self.gcs_proc is not None and self.gcs_proc.poll() is None:
            self.kill_gcs()
        self.gcs_proc = spawn_gcs(
            self._gcs_port, self.session_name,
            persist=self._gcs_persist, env=self._gcs_env)
        return self.gcs_proc

    def wait_for_nodes(self, count: Optional[int] = None,
                       timeout: float = 60.0):
        """Wait until the GCS sees `count` alive nodes (default: all)."""
        import ray_tpu
        expected = count if count is not None \
            else 1 + len(self.remote_nodes)
        deadline = time.time() + timeout
        while time.time() < deadline:
            alive = [n for n in ray_tpu.nodes() if n["state"] == "ALIVE"]
            if len(alive) >= expected:
                return
            time.sleep(0.1)
        raise TimeoutError(
            f"expected {expected} alive nodes within {timeout}s")

    def shutdown(self):
        import ray_tpu
        if self._connected:
            ray_tpu.shutdown()
        for handle in list(self.remote_nodes):
            try:
                handle.proc.kill()
                handle.proc.wait(timeout=10)
            except Exception:
                logger.debug("kill of remote node pid %s failed",
                             handle.proc.pid, exc_info=True)
        self.remote_nodes.clear()
        if self.head_node is not None:
            self.head_node.stop()
            self.head_node = None
        if self.gcs_proc is not None:
            try:
                if self.gcs_proc.poll() is None:
                    self.gcs_proc.terminate()
                    self.gcs_proc.wait(timeout=10)
            except Exception:
                logger.debug("gcs subprocess teardown failed",
                             exc_info=True)
            self.gcs_proc = None
