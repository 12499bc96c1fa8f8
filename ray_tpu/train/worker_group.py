"""WorkerGroup: gang-scheduled train worker actors
(reference: train/v2/_internal/execution/worker_group/worker_group.py:102 —
PG creation :275, actors pinned to bundles :396; TPU slice reservation via
accelerators.tpu.reserve_tpu_slice for multi-host).

Each worker is an actor running the user train loop in a worker process that
owns its host's TPU chips. Multi-worker rendezvous for the JAX coordination
service goes through the GCS KV (the analog of the reference's
jax.distributed.initialize master-addr exchange, v2/jax/config.py:36)."""

from __future__ import annotations

import logging
import os
import socket
import time
from typing import Any, Callable, Dict, List, Optional


class TrainWorker:
    """Actor wrapping one rank of the SPMD group."""

    def __init__(self, rank: int, world_size: int, run_name: str,
                 controller, use_tpu: bool, coordinator: Optional[str],
                 mesh_spec: Optional[Dict[str, Any]] = None):
        self.rank = rank
        self.world_size = world_size
        self.run_name = run_name
        self.controller = controller
        self.use_tpu = use_tpu
        self.coordinator = coordinator
        self.mesh_spec = mesh_spec or {}
        self._jax_initialized = False

    def setup_distributed(self):
        """Initialize the JAX coordination service for multi-host meshes.

        Single-worker groups skip this (the local mesh needs no service),
        and so do CPU groups: without accelerators jax.distributed cannot
        federate devices into one global runtime, so the data plane is the
        host collective backend (ray_tpu.util.collective) instead and the
        coordination service would only add a flaky moving part."""
        if self.world_size <= 1 or not self.use_tpu or self._jax_initialized:
            return True
        import jax
        jax.distributed.initialize(
            coordinator_address=self.coordinator,
            num_processes=self.world_size,
            process_id=self.rank)
        self._jax_initialized = True
        return True

    def get_coordinator(self) -> str:
        """Pick a routable IP + free port on THIS worker's host.

        The JAX coordination service binds on rank 0's host, so the port
        must be probed here — a port free on the controller's host may be
        taken on this one — and `gethostname()` may not resolve from peers,
        so the IP comes from the UDP-connect trick.
        """
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            sock.connect(("8.8.8.8", 80))
            ip = sock.getsockname()[0]
        except OSError:
            ip = "127.0.0.1"
        finally:
            sock.close()
        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
        return f"{ip}:{port}"

    def set_coordinator(self, coordinator: str):
        self.coordinator = coordinator
        return True

    def run(self, train_fn: Callable, config: Dict[str, Any],
            resume_checkpoint: Optional[str],
            dataset_factories: Dict[str, Any]):
        from .checkpoint import Checkpoint
        from .context import TrainContext, set_train_context
        shards = {}
        for name, factory in (dataset_factories or {}).items():
            shards[name] = factory(self.rank, self.world_size) \
                if callable(factory) else factory
        ctx = TrainContext(
            rank=self.rank, world_size=self.world_size,
            node_rank=self.rank, controller_handle=self.controller,
            run_name=self.run_name,
            resume_checkpoint=Checkpoint(resume_checkpoint)
            if resume_checkpoint else None,
            dataset_shards=shards,
            mesh_spec=self.mesh_spec)
        set_train_context(ctx)
        try:
            return train_fn(config) if config else train_fn({})
        finally:
            set_train_context(None)

    def ping(self):
        return "pong"


class WorkerGroup:
    def __init__(self, scaling, run_name: str, controller):
        self.scaling = scaling
        self.run_name = run_name
        self.controller = controller
        self.pg = None
        self.workers: List = []
        self._slice_pg = None

    def start(self):
        import ray_tpu
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import \
            PlacementGroupSchedulingStrategy

        n = self.scaling.num_workers
        resources = self.scaling.worker_resources()

        if self.scaling.use_tpu and self.scaling.topology and n > 1:
            # Gang-reserve one whole slice, then target its per-host
            # resource so every worker lands inside the ICI domain.
            from ..accelerators import tpu as tpu_accel
            self._slice_pg, slice_name = tpu_accel.reserve_tpu_slice(
                self.scaling.topology)
            resources = dict(resources)
            resources[slice_name] = 0.001

        bundles = [dict(resources) for _ in range(n)]
        self.pg = placement_group(bundles,
                                  strategy=self.scaling.placement_strategy,
                                  name=f"{self.run_name}-pg")
        if not self.pg.wait(timeout_seconds=300):
            raise TimeoutError(
                f"placement group for {n} train workers not placed in 300s "
                f"(per-worker {resources})")

        worker_cls = ray_tpu.remote(TrainWorker)
        # A worker whose bundle holds chips gets the TPU backend from the
        # raylet (lease -> JAX_PLATFORMS=tpu); nothing to ask for here.
        env_vars = {}
        if self.scaling.virtual_devices:
            # The --dryrun7b harness: each worker gets an n-device
            # virtual CPU mesh so the full GSPMD sharding compiles and
            # executes without real chips.
            env_vars["JAX_PLATFORMS"] = "cpu"
            env_vars["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count="
                f"{int(self.scaling.virtual_devices)}")
        mesh_spec = None
        if self.scaling.mesh_axes is not None:
            # build the MeshConfig HERE so a typo'd axis raises at
            # submit time; workers get the validated config itself
            mesh_spec = {"mesh_config": self.scaling.mesh_config(),
                         "num_slices": self.scaling.num_slices}
        coordinator = None
        self.workers = []
        for rank in range(n):
            bundle = bundles[rank]
            extra = {k: v for k, v in bundle.items()
                     if k not in ("CPU", "TPU", "GPU")}
            worker = worker_cls.options(
                num_cpus=0,
                num_tpus=bundle.get("TPU", 0),
                resources=extra or None,
                runtime_env={"env_vars": env_vars} if env_vars else None,
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self.pg,
                    placement_group_bundle_index=rank),
            ).remote(rank, n, self.run_name, self.controller,
                     self.scaling.use_tpu, coordinator, mesh_spec)
            self.workers.append(worker)
            if rank == 0 and n > 1:
                coordinator = ray_tpu.get(worker.get_coordinator.remote(),
                                          timeout=300)
        if n > 1:
            ray_tpu.get([w.set_coordinator.remote(coordinator)
                         for w in self.workers], timeout=300)
        ray_tpu.get([w.setup_distributed.remote() for w in self.workers],
                    timeout=600)
        return self

    def run_train_fn(self, train_fn, config, resume_checkpoint,
                     dataset_factories):
        return [w.run.remote(train_fn, config, resume_checkpoint,
                             dataset_factories)
                for w in self.workers]

    def shutdown(self):
        import ray_tpu
        from ray_tpu.util.placement_group import remove_placement_group
        for worker in self.workers:
            try:
                ray_tpu.kill(worker)
            except Exception:
                logging.getLogger(__name__).debug(
                    "worker kill at group shutdown failed", exc_info=True)
        self.workers = []
        for pg in (self.pg, self._slice_pg):
            if pg is not None:
                try:
                    remove_placement_group(pg)
                except Exception:
                    logging.getLogger(__name__).debug(
                        "placement group removal failed", exc_info=True)
        self.pg = None
        self._slice_pg = None
