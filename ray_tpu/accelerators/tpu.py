"""TPU accelerator support.

Equivalent of the reference's TPU accelerator manager
(python/ray/_private/accelerators/tpu.py:199-578), made first-class:

- chip autodetection via /dev/accel* and /dev/vfio (mockable via glob)
- `TPU_VISIBLE_CHIPS` isolation for sub-host scheduling, including the
  host-bounds env rewriting that 1/2-chip subsets require
- slice name / topology / worker-id discovery from GCE metadata or GKE env
- per-node extra resources: `{<slice-name>: 1}` on every host of a slice and
  `TPU-<pod-type>-head: 1` on worker 0 — the gang-reservation anchor
- node labels `rtpu.io/tpu-{slice-name,worker-id,topology,pod-type}`
- `reserve_tpu_slice`: gang-reserve a whole slice via a placement group on
  the head resource (used by the Train library for multi-host SPMD groups)

Valid chip counts per worker mirror the reference: {1, 2, 4, 8}.
"""

from __future__ import annotations

import errno
import glob as _glob_module
import logging
import os
import re
import time
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)

NUM_TPUS_PER_HOST = 8  # v5p default host size; detection below refines
TPU_VALID_CHIP_COUNTS = (1, 2, 4, 8)
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_HEAD_RESOURCE_PREFIX = "TPU-"
TPU_HEAD_RESOURCE_SUFFIX = "-head"

# Node label keys (reference: ray.io/tpu-* labels, tpu.py:548-578)
LABEL_SLICE_NAME = "rtpu.io/tpu-slice-name"
LABEL_WORKER_ID = "rtpu.io/tpu-worker-id"
LABEL_TOPOLOGY = "rtpu.io/tpu-topology"
LABEL_POD_TYPE = "rtpu.io/tpu-pod-type"

# GKE env vars (reference: tpu.py:326-433)
GKE_TPU_ACCELERATOR_ENV = "TPU_ACCELERATOR_TYPE"
GKE_TPU_TOPOLOGY_ENV = "TPU_TOPOLOGY"
GKE_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GKE_TPU_NAME_ENV = "TPU_NAME"


def _visible_chip_count() -> Optional[int]:
    visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
    if visible is None or visible == "":
        return None
    return len([c for c in visible.split(",") if c != ""])


def autodetect_num_chips(glob=_glob_module.glob) -> int:
    """Count TPU chips on this host (reference: tpu.py:226-245).

    Order: explicit RTPU_NUM_TPU_CHIPS override, TPU_VISIBLE_CHIPS
    restriction, /dev/accel* devices, /dev/vfio/*. JAX is deliberately never
    initialized from here — that would grab the host's chip lock."""
    override = os.environ.get("RTPU_NUM_TPU_CHIPS")
    if override is not None:
        return int(override)
    visible = _visible_chip_count()
    if visible is not None:
        return visible
    accel = glob("/dev/accel*")
    if accel:
        return len(accel)
    vfio = glob("/dev/vfio/[0-9]*")
    if vfio:
        return len(vfio)
    return 0


def chip_device_files(glob=_glob_module.glob, env=os.environ) -> List[str]:
    """The device files of the chips this process may open: /dev/accel<n>
    and the numbered /dev/vfio/<n>. All of them where `TPU_VISIBLE_CHIPS`
    is not set or names as many chips as the host has files (the lease is
    the whole host's); else only the files it names by number, and none if
    it names none of them (a /dev/vfio number is an IOMMU group, not
    always the chip's index: a file that is not positively this worker's
    may be another worker's, and is left alone)."""
    files = sorted(glob("/dev/accel*")) + sorted(glob("/dev/vfio/[0-9]*"))
    visible = {c for c in env.get(TPU_VISIBLE_CHIPS_ENV, "").split(",") if c}
    if not visible or len(visible) >= len(files):
        return files
    return [f for f in files if re.search(r"(\d+)$", f).group(1) in visible]


def wait_for_free_chips(timeout_s: float = 60.0, poll_s: float = 0.25,
                        glob=_glob_module.glob, env=os.environ,
                        opener=os.open, closer=os.close, sleep=time.sleep,
                        clock=time.monotonic) -> float:
    """Wait, bounded, until no other process holds this worker's chip
    device files (`chip_device_files`); returns the seconds waited. For a
    worker that was leased chips, before it first touches JAX: a device
    file is opened by one process at a time, and the process that held it
    last may have exited as a thread-group leader (state Z, which a `wait`
    takes for gone) while its runtime threads still tear the device down:
    JAX's backend then fails with `open(/dev/vfio/0): Device or resource
    busy` some seconds before the device is free (PERF.md section 7: two
    PRs' checks ended on it). Each file is opened and closed until none
    answers EBUSY; a free device costs one `open`. The first EBUSY is
    logged, so a worker that waits says so while it does. After
    `timeout_s` the worker goes on and fails as it would have; any other
    error (no such file, no permission) is the backend's to report, not
    waited on."""
    start = clock()
    files = chip_device_files(glob, env)
    said = False

    def held_elsewhere(path: str) -> bool:
        try:
            closer(opener(path, os.O_RDWR))
        except OSError as e:
            return e.errno == errno.EBUSY
        return False

    while True:
        busy = [f for f in files if held_elsewhere(f)]
        if not busy or clock() - start >= timeout_s:
            break
        if not said:
            logger.warning("%s held by another process: waiting up to "
                           "%.0f s for it", ", ".join(busy), timeout_s)
            said = True
        sleep(poll_s)
    return clock() - start


def compile_cache_dir(env=os.environ) -> str:
    """Point `env` at the persistent XLA compile cache and return its
    directory. Every process about to own a chip calls this before it
    imports jax (the raylet does it for the workers it spawns on a chip
    lease). JAX_COMPILATION_CACHE_DIR wins where it is set; otherwise the
    cache lives at ONE fixed path inside the checkout — the path is part
    of the cache key, so a directory that moves never hits."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return env.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(checkout, ".jax_cache"))


def on_virtual_cpu_mesh(n_devices: int, env=os.environ) -> bool:
    """Was this process started on a virtual CPU mesh of at least
    `n_devices`? Read from the environment: a launcher that asked jax for
    its devices would open whatever chip the host has, in a parent that
    only wants to start a child."""
    forced = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                       env.get("XLA_FLAGS", ""))
    return env.get("JAX_PLATFORMS") == "cpu" and forced is not None \
        and int(forced.group(1)) >= n_devices


def validate_chip_request(num_chips: float) -> None:
    if num_chips < 1:
        return  # fractional/zero handled by generic resource accounting
    if int(num_chips) not in TPU_VALID_CHIP_COUNTS:
        raise ValueError(
            f"TPU chip requests must be one of {TPU_VALID_CHIP_COUNTS} "
            f"(got {num_chips}); a multi-host slice is reserved via "
            "reserve_tpu_slice / placement groups instead")


def visible_chips_env(chip_ids: List[int], total_on_host: int
                      ) -> Dict[str, str]:
    """Env for a worker granted a chip subset (reference: tpu.py:283-323).

    For 1- or 2-chip subsets libtpu also needs the host bounds rewritten so
    it doesn't try to initialize the full host topology."""
    env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chip_ids)}
    n = len(chip_ids)
    if n in (1, 2) and n < total_on_host:
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = f"1,{n},1"
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    return env


# ---------------------------------------------------------------------------
# Slice metadata (GKE env or GCE metadata server; both absent on dev boxes)
# ---------------------------------------------------------------------------

def _gce_metadata(key: str) -> Optional[str]:
    # Zero-egress environments have no metadata server; env override only.
    return os.environ.get(f"RTPU_FAKE_GCE_{key.upper().replace('-', '_')}")

def get_tpu_pod_type() -> Optional[str]:
    """e.g. 'v5p-64' — accelerator type of the slice this host is part of."""
    accel = os.environ.get(GKE_TPU_ACCELERATOR_ENV) \
        or _gce_metadata("accelerator-type")
    if accel:
        return accel.lower()
    return None


def get_tpu_topology() -> Optional[str]:
    return os.environ.get(GKE_TPU_TOPOLOGY_ENV) or _gce_metadata("topology")


def get_tpu_worker_id() -> Optional[int]:
    wid = os.environ.get(GKE_TPU_WORKER_ID_ENV) \
        or _gce_metadata("agent-worker-number")
    return int(wid) if wid is not None else None


def get_tpu_slice_name() -> Optional[str]:
    name = os.environ.get(GKE_TPU_NAME_ENV) or _gce_metadata("instance-id")
    return name


# Chips per host by generation. v5e/v6e multi-host slices use 4-chip hosts;
# their 8-chip slices (ct5lp-hightpu-8t / ct6e-standard-8t, topology 2x4)
# are a single 8-chip host and are special-cased below.
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4,
                   "v5litepod": 4, "v5e": 4, "v6e": 4}

_CHIP_SUFFIX_SINGLE_HOST_8 = ("v5litepod", "v5e", "v6e")

# Generations whose pod-type suffix counts TensorCores (2 per chip), not
# chips (reference: _private/accelerators/tpu.py SINGLE_CORE_TPU_TYPES —
# v2/v3/v4/v5p all name slices by core count: v5p-8 is one 4-chip host).
_CORE_SUFFIX_GENERATIONS = ("v2", "v3", "v4", "v5p")


def num_workers_in_slice(pod_type: str, topology: Optional[str]) -> int:
    """Hosts in the slice = total chips / chips per host."""
    try:
        chips = int(pod_type.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return 1
    generation = pod_type.split("-")[0]
    if generation in _CORE_SUFFIX_GENERATIONS:
        chips //= 2  # suffix counts TensorCores
    if generation in _CHIP_SUFFIX_SINGLE_HOST_8 and chips == 8:
        return 1  # one 8-chip host, not two 4-chip hosts
    per_host = _CHIPS_PER_HOST.get(generation, 4)
    chips_per_host = min(chips, per_host)
    return max(1, chips // chips_per_host)


def node_tpu_labels() -> Dict[str, str]:
    labels = {}
    pod_type = get_tpu_pod_type()
    if pod_type:
        labels[LABEL_POD_TYPE] = pod_type
    topology = get_tpu_topology()
    if topology:
        labels[LABEL_TOPOLOGY] = topology
    worker_id = get_tpu_worker_id()
    if worker_id is not None:
        labels[LABEL_WORKER_ID] = str(worker_id)
    slice_name = get_tpu_slice_name()
    if slice_name:
        labels[LABEL_SLICE_NAME] = slice_name
    return labels


def node_tpu_resources() -> Dict[str, float]:
    """Extra per-node resources advertising slice membership
    (reference: tpu.py:482-545)."""
    resources: Dict[str, float] = {}
    slice_name = get_tpu_slice_name()
    pod_type = get_tpu_pod_type()
    if slice_name and autodetect_num_chips() > 0:
        resources[slice_name] = 1.0
        if get_tpu_worker_id() == 0 and pod_type:
            resources[
                f"{TPU_HEAD_RESOURCE_PREFIX}{pod_type}"
                f"{TPU_HEAD_RESOURCE_SUFFIX}"] = 1.0
    return resources


def reserve_tpu_slice(pod_type: str, timeout: float = 600.0):
    """Gang-reserve one whole TPU slice; returns its slice name
    (reference: tpu.py:145-196).

    Places a 1-bundle placement group on the `TPU-<pod-type>-head` resource
    (only worker 0 of each slice advertises it), then reads the slice name
    from that node's labels. Training then targets every host of the slice
    via the `{slice_name: 1}` per-host resource."""
    import ray_tpu
    from ray_tpu.util.placement_group import placement_group

    head_resource = (f"{TPU_HEAD_RESOURCE_PREFIX}{pod_type}"
                     f"{TPU_HEAD_RESOURCE_SUFFIX}")
    pg = placement_group([{head_resource: 1}], strategy="STRICT_PACK",
                         name=f"tpu-slice-{pod_type}")
    ready = pg.wait(timeout)
    if not ready:
        raise TimeoutError(
            f"could not reserve a {pod_type} slice within {timeout}s")

    @ray_tpu.remote(num_cpus=0, resources={head_resource: 0.001},
                    scheduling_strategy=ray_tpu.util.scheduling_strategies.
                    PlacementGroupSchedulingStrategy(placement_group=pg))
    def _read_slice_name():
        return get_tpu_slice_name()

    name = ray_tpu.get(_read_slice_name.remote(), timeout=timeout)
    return pg, name
