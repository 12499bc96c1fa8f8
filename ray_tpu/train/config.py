"""Train configuration dataclasses
(reference: train/v2/api/config.py — ScalingConfig with use_tpu/topology
:89-123, RunConfig, FailureConfig, CheckpointConfig)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass
class ScalingConfig:
    """How many workers and what each one holds.

    TPU semantics: `use_tpu=True` with `topology` (e.g. "v5p-64") gang-
    reserves a whole slice (one worker per host, SPREAD across the slice's
    hosts, all inside one ICI domain) — reference: JaxTrainer's
    reserve_tpu_slice flow. Single-host: `resources_per_worker={"TPU": n}`.

    GSPMD semantics: `mesh_axes` declares the device-mesh layout each
    worker builds over its addressable devices (axis name -> size, the
    `parallel.MeshConfig` vocabulary; one axis may be -1). `dcn_axes`
    lists the axes that cross slice boundaries (their size product must
    equal `num_slices`); the trainer lays those hops on DCN and routes
    any OUT-of-program gradient combine through the topology-aware
    `util.collective` backend. `virtual_devices` forces an n-device
    virtual CPU mesh in each worker (the `--dryrun7b` harness — the same
    `--xla_force_host_platform_device_count` trick the driver dryruns
    use; None/0 = real devices).
    """
    num_workers: int = 1
    use_tpu: bool = False
    topology: Optional[str] = None
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    mesh_axes: Optional[Dict[str, int]] = None
    dcn_axes: Tuple[str, ...] = ()
    num_slices: Optional[int] = None
    virtual_devices: Optional[int] = None

    def __post_init__(self):
        if self.use_tpu and self.topology is None \
                and self.num_workers > 1:
            raise ValueError(
                "multi-worker TPU training requires topology= (the slice "
                "pod type, e.g. 'v5p-64') so the workers land on one ICI "
                "domain")
        if self.use_tpu:
            self.placement_strategy = "SPREAD"
        self.dcn_axes = tuple(self.dcn_axes or ())
        if self.dcn_axes and self.mesh_axes is None:
            raise ValueError("dcn_axes requires mesh_axes")
        if self.use_tpu and self.virtual_devices:
            raise ValueError(
                "use_tpu and virtual_devices are contradictory: "
                "virtual_devices forces an emulated CPU mesh (the "
                "dryrun harness); drop it to train on real chips")

    def mesh_config(self):
        """The per-worker `parallel.MeshConfig` this scaling declares,
        or None when no mesh_axes were given (rank-Python loops)."""
        if self.mesh_axes is None:
            return None
        from ..parallel.mesh import AXIS_ORDER, MeshConfig
        unknown = set(self.mesh_axes) - set(AXIS_ORDER)
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}; "
                             f"valid: {AXIS_ORDER}")
        return MeshConfig(**dict(self.mesh_axes),
                          dcn_axes=tuple(self.dcn_axes))

    def worker_resources(self) -> Dict[str, float]:
        resources = dict(self.resources_per_worker or {})
        if self.use_tpu and "TPU" not in resources:
            # one worker per host: it takes every chip the host has
            from ..accelerators.tpu import autodetect_num_chips
            chips = autodetect_num_chips()
            if not chips:
                raise ValueError(
                    "use_tpu=True but no TPU chip was detected on this "
                    "host; name the chips per worker with "
                    "resources_per_worker={'TPU': n}")
            resources["TPU"] = chips
        resources.setdefault("CPU", 1)
        return resources


@dataclasses.dataclass
class FailureConfig:
    max_failures: int = 0


@dataclasses.dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = 2
    checkpoint_frequency: int = 0


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = dataclasses.field(
        default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = dataclasses.field(
        default_factory=CheckpointConfig)
