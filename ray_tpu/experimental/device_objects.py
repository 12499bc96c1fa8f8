"""Device-resident objects: jax.Arrays that stay in accelerator memory
with only a control-plane descriptor crossing the object store.

Role of the reference's GPU objects
(python/ray/experimental/gpu_object_manager/gpu_object_manager.py:61 —
tensors live on-device, Ray carries refs; collective/NIXL transports move
them device-to-device). TPU-native design:

- `device_put_ref(array)` in the producing actor pins the array in a
  process-local store and returns an ObjectRef OWNED BY THE PRODUCER
  whose control-plane value is a tiny `DeviceObjectDescriptor`. The
  array itself never leaves HBM and never touches /dev/shm.
- `device_get(ref)` anywhere resolves the descriptor (normal object
  path: bytes-sized), then pulls the array runtime-to-runtime through
  `jax.experimental.transfer` (PJRT cross-host DMA — ICI/DCN on TPU) —
  or returns the pinned array directly when the consumer IS the
  producer process.
- Lifetime rides the existing borrower protocol: consumers hold borrows
  of the producer-owned descriptor; when the last ref drops, the
  producer's `_free_owned_object` fires `on_free` and the pin is
  released.

Transport: `jax.experimental.transfer` (PJRT cross-runtime DMA) — the
payload never touches the object store or /dev/shm (the property the
zero-copy tests pin).
"""

from __future__ import annotations

import logging
import os
import threading
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from .._internal.ids import ObjectID
from .._internal.object_ref import ObjectRef

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_cond = threading.Condition(_lock)
_pinned: Dict[ObjectID, Any] = {}          # oid -> jax.Array (producer)
_pinned_nbytes: Dict[ObjectID, int] = {}
_accounted_bytes = [0]                     # pins + channel staging
_server = None                             # this process's TransferServer
_server_addr: Optional[str] = None
_next_uuid = [1]
_conns: Dict[str, Any] = {}                # addr -> TransferConnection


def _build_device_object_metrics():
    from types import SimpleNamespace

    from ..util.metrics import Counter, Gauge
    return SimpleNamespace(
        pinned_bytes=Gauge(
            "rtpu_device_object_pinned_bytes",
            "HBM bytes pinned for device-resident objects "
            "(device_put_ref + DeviceChannel staging)"),
        pulls=Counter(
            "rtpu_device_object_pulls_total",
            "Runtime-to-runtime device-object pulls started by this "
            "process"),
        pull_bytes=Counter(
            "rtpu_device_object_pull_bytes_total",
            "Bytes moved by runtime-to-runtime device-object pulls"),
    )


from ..util.metrics import LazyMetrics  # noqa: E402 — after _build def

_metrics = LazyMetrics(_build_device_object_metrics)


def _update_gauge():
    try:
        _metrics().pinned_bytes.set(float(_accounted_bytes[0]))
    except Exception:  # noqa: BLE001 — metrics best-effort
        logger.debug("pinned-bytes gauge update failed", exc_info=True)


def pinned_bytes() -> int:
    """HBM bytes currently accounted (pins + channel staging)."""
    with _lock:
        return _accounted_bytes[0]


def reserve_bytes(nbytes: int, timeout_s: Optional[float] = None) -> bool:
    """Backpressure gate: block until `nbytes` fits under the HBM budget
    (CONFIG.device_object_hbm_budget; 0 = unlimited). Returns False on
    timeout — callers then spill to host instead of OOMing HBM, and the
    exhaustion is published as a DEVICE_MEMORY_PRESSURE event (silent
    degradation made a slow pipeline look healthy while every pin was
    detouring through the host store)."""
    from .._internal.config import CONFIG
    budget = CONFIG.device_object_hbm_budget
    if timeout_s is None:
        timeout_s = CONFIG.device_object_backpressure_timeout_s
    held = 0
    with _cond:
        if not budget:
            _accounted_bytes[0] += nbytes
            _update_gauge()
            return True
        import time as _time
        deadline = _time.monotonic() + timeout_s
        ok = True
        while _accounted_bytes[0] + nbytes > budget:
            remaining = deadline - _time.monotonic()
            if remaining <= 0 or nbytes > budget:
                ok = False
                held = _accounted_bytes[0]
                break
            _cond.wait(remaining)
        if ok:
            _accounted_bytes[0] += nbytes
            _update_gauge()
            return True
    # Emission OUTSIDE the condition lock: it is a (best-effort,
    # bounded) GCS RPC from this user thread.
    from .._internal import accel
    accel.emit_pressure_event(
        f"device-object HBM budget exhausted: {nbytes} B requested, "
        f"{held}/{budget} B pinned after {timeout_s:g}s — spilling "
        "to host object store",
        fields={"requested_bytes": nbytes, "pinned_bytes": held,
                "budget_bytes": budget, "source": "device_objects"})
    return False


def release_bytes(nbytes: int):
    with _cond:
        _accounted_bytes[0] = max(0, _accounted_bytes[0] - nbytes)
        _update_gauge()
        _cond.notify_all()


@dataclass
class DeviceObjectDescriptor:
    object_hex: str
    transfer_addr: str          # producer's TransferServer address
    producer_rpc_addr: Tuple[str, int]
    shape: Tuple[int, ...]
    dtype: str
    nbytes: int


# Chunk size for the RPC-fallback pull: big enough to amortize the
# per-call overhead, small enough that a 1 GiB array never builds a
# frame near the ring's 1 GiB oversized-prefix guard.
def _ensure_server():
    """This process's PJRT transfer server (started on first use)."""
    global _server, _server_addr
    from jax.experimental import transfer
    with _lock:
        if _server is None:
            import jax
            client = jax.devices()[0].client
            # A bulk-transport address is REQUIRED for cross-process
            # pulls (the default server only short-circuits locally).
            host = os.environ.get("RTPU_TRANSFER_HOST", "127.0.0.1")
            _server = transfer.start_transfer_server(
                client, f"{host}:0", [f"{host}:0"])
            _server_addr = _server.address()
        return _server


def device_put_ref(array, *, timeout_s: Optional[float] = None
                   ) -> ObjectRef:
    """Pin `array` on-device in this process and return a control-plane
    ref to it. Call inside the producing actor; return the ref (or a
    structure containing it) to consumers.

    HBM accounting: pins count against
    CONFIG.device_object_hbm_budget. When producers outrun consumers the
    call BLOCKS (up to device_object_backpressure_timeout_s) for frees,
    then falls back to spilling the array to the host object store — the
    returned ref then resolves through the normal object path and
    device_get re-devices it (reference: gpu_object_manager.py:61)."""
    import numpy as np

    from .._internal.core_worker import get_core_worker

    worker = get_core_worker()
    nbytes = int(array.nbytes)
    if not reserve_bytes(nbytes, timeout_s):
        # Budget exhausted: spill to host instead of risking HBM OOM.
        import ray_tpu
        return ray_tpu.put(np.asarray(array))
    _ensure_server()
    oid = ObjectID.from_random()
    with _lock:
        _pinned[oid] = array
        _pinned_nbytes[oid] = nbytes
    desc = DeviceObjectDescriptor(
        object_hex=oid.hex(), transfer_addr=_server_addr,
        producer_rpc_addr=tuple(worker.rpc_address),
        shape=tuple(array.shape), dtype=str(np.dtype(array.dtype)),
        nbytes=nbytes)
    worker.reference_counter.add_owned(oid)
    worker.memory_store.put(oid, desc)
    _register_free_hook()
    return ObjectRef(oid, worker.rpc_address)


def device_get(ref: ObjectRef):
    """Resolve a device-object ref to a jax.Array in THIS process's
    runtime. Same-process: the pinned array itself (zero copy). Remote:
    a runtime-to-runtime pull via jax.experimental.transfer — no host
    shared-memory file is ever written."""
    import ray_tpu

    oid = ref.id()
    with _lock:
        local = _pinned.get(oid)
    if local is not None:
        return local
    return resolve_control(ray_tpu.get(ref), ref)


def resolve_control(control, ref=None):
    """The device_get tail for a caller that already fetched the ref's
    control-plane value (saves the duplicate ray_tpu.get per hop on hot
    paths like the MPMD pipeline's activation resolve)."""
    if isinstance(control, DeviceObjectDescriptor):
        return _pull(control)
    import numpy as np
    if isinstance(control, np.ndarray):
        # producer spilled to host under HBM backpressure — re-device
        import jax.numpy as jnp
        return jnp.asarray(control)
    raise TypeError(f"{ref if ref is not None else 'control value'} is "
                    f"not a device object (got {type(control).__name__})")


def _pull(desc: DeviceObjectDescriptor):
    import jax
    import numpy as np

    from .._internal.core_worker import get_core_worker

    metrics = _metrics()
    metrics.pulls.inc()
    metrics.pull_bytes.inc(desc.nbytes)
    worker = get_core_worker()
    client = worker.clients.get(tuple(desc.producer_rpc_addr))
    server = _ensure_server()
    # Ask the producer to stage the array for one pull under a fresh
    # uuid (await_pull is single-shot; N consumers = N stagings).
    reply = client.call_sync("device_object_stage",
                             object_hex=desc.object_hex, timeout=120)
    if not reply.get("ok"):
        raise RuntimeError(
            f"device object {desc.object_hex[:12]} unavailable: "
            f"{reply.get('error')}")
    uuid = reply["uuid"]
    with _lock:
        conn = _conns.get(desc.transfer_addr)
        if conn is None:
            conn = server.connect(desc.transfer_addr)
            _conns[desc.transfer_addr] = conn
    spec = jax.ShapeDtypeStruct(
        desc.shape, np.dtype(desc.dtype),
        sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    out = conn.pull(uuid, [spec])
    return out[0]


# -- producer-side plumbing -------------------------------------------------

def _stage_for_pull(object_hex: str) -> Dict[str, Any]:
    """RPC handler body: stage one pull of a pinned array."""
    oid = ObjectID.from_hex(object_hex)
    with _lock:
        array = _pinned.get(oid)
        if array is None:
            return {"ok": False, "error": "not pinned in this process"}
        uuid = _next_uuid[0]
        _next_uuid[0] += 1
    _ensure_server().await_pull(uuid, [array])
    return {"ok": True, "uuid": uuid}


_hook_installed = False


def _register_free_hook():
    """Install the RPC handlers + free callback on this process's
    worker."""
    global _hook_installed
    if _hook_installed:
        return
    from .._internal.core_worker import get_core_worker

    worker = get_core_worker()

    async def handle_device_object_stage(object_hex: str):
        return _stage_for_pull(object_hex)

    worker.server.register("device_object_stage", handle_device_object_stage)
    worker.device_object_free_hooks.append(on_free)
    _hook_installed = True


def on_free(object_id: ObjectID):
    with _lock:
        _pinned.pop(object_id, None)
        nbytes = _pinned_nbytes.pop(object_id, 0)
    if nbytes:
        release_bytes(nbytes)


def num_pinned() -> int:
    with _lock:
        return len(_pinned)
