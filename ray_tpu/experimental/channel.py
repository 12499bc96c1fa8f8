"""Channels: fixed-topology data paths between processes
(reference: python/ray/experimental/channel/ —
shared_memory_channel.py (mutable plasma objects), intra_process_channel.py,
communicator.py ABC; the accelerator channel
torch_tensor_accelerator_channel.py:49 maps here to keeping tensors
device-resident and passing only control tokens).

SharedMemoryChannel: single-producer single-consumer seqlock ring over one
mmap file in /dev/shm — write payload, then bump the 8-byte aligned write
counter; the reader acks by matching its counter. No RPC, no allocation,
no serialization of the channel itself — this is the per-step hot path of
a compiled DAG, where the task RPC plane would dominate the microsecond
budget."""

from __future__ import annotations

import mmap
import os
import struct
import time
from typing import Any, Optional

from .._internal import serialization

_HEADER = struct.Struct("<QQQ")  # write_seq, ack_seq, payload_len
HEADER_SIZE = _HEADER.size


class ChannelTimeoutError(TimeoutError):
    pass


class ChannelClosedError(RuntimeError):
    pass


class DagTaskError(RuntimeError):
    """A bound method raised inside a compiled DAG; carries the remote
    traceback. Forwarded through channels as a poison pill so the driver
    sees the real error instead of an output timeout."""

    def __init__(self, method: str, traceback_str: str):
        super().__init__(f"DAG task {method} failed:\n{traceback_str}")
        self.method = method
        self.traceback_str = traceback_str

    def __reduce__(self):
        return (DagTaskError, (self.method, self.traceback_str))


_CLOSE_SENTINEL = (1 << 64) - 1


class SharedMemoryChannel:
    """One-slot SPSC channel backed by an mmap file.

    Writer: wait until the previous payload is acked, write, bump
    write_seq. Reader: wait for write_seq to advance, read, bump ack_seq.
    The single 8-byte aligned counter store is the publication point.
    """

    def __init__(self, path: str, capacity: int = 8 * 1024 * 1024,
                 create: bool = False):
        self.path = path
        self.capacity = capacity
        if create:
            fd = os.open(path, os.O_CREAT | os.O_RDWR, 0o600)
            os.ftruncate(fd, HEADER_SIZE + capacity)
        else:
            deadline = time.monotonic() + 30
            while not os.path.exists(path):
                if time.monotonic() > deadline:
                    raise FileNotFoundError(path)
                time.sleep(0.005)
            fd = os.open(path, os.O_RDWR)
            self.capacity = os.fstat(fd).st_size - HEADER_SIZE
        try:
            self._mm = mmap.mmap(fd, HEADER_SIZE + self.capacity)
        finally:
            os.close(fd)
        self._view = memoryview(self._mm)

    # -- low-level header access ------------------------------------------

    def _read_header(self):
        return _HEADER.unpack_from(self._view, 0)

    def _set_write_seq(self, seq: int):
        struct.pack_into("<Q", self._view, 0, seq)

    def _set_ack_seq(self, seq: int):
        struct.pack_into("<Q", self._view, 8, seq)

    def _set_len(self, n: int):
        struct.pack_into("<Q", self._view, 16, n)

    # -- API ---------------------------------------------------------------

    def put(self, value: Any, timeout: Optional[float] = 10.0):
        sobj = serialization.serialize(value)
        total = sobj.total_bytes()
        if total > self.capacity:
            raise ValueError(
                f"value of {total} bytes exceeds channel capacity "
                f"{self.capacity}")
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            write_seq, ack_seq, _len = self._read_header()
            if write_seq == _CLOSE_SENTINEL:
                raise ChannelClosedError(self.path)
            if ack_seq == write_seq:
                break
            if deadline is not None and time.monotonic() > deadline:
                raise ChannelTimeoutError(
                    f"reader did not consume within {timeout}s")
            time.sleep(0.0001)
        sobj.write_into(self._view[HEADER_SIZE:HEADER_SIZE + total])
        self._set_len(total)
        self._set_write_seq(write_seq + 1)

    def get(self, timeout: Optional[float] = 10.0) -> Any:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            write_seq, ack_seq, length = self._read_header()
            if write_seq == _CLOSE_SENTINEL:
                raise ChannelClosedError(self.path)
            if write_seq > ack_seq:
                break
            if deadline is not None and time.monotonic() > deadline:
                raise ChannelTimeoutError(
                    f"no value within {timeout}s on {self.path}")
            time.sleep(0.0001)
        # Copy out before acking: deserialize_from_buffer keeps zero-copy
        # views, and the writer reuses the slot immediately after the ack.
        payload = bytes(self._view[HEADER_SIZE:HEADER_SIZE + length])
        value = serialization.deserialize_from_buffer(memoryview(payload))
        self._set_ack_seq(write_seq)
        return value

    def close(self):
        try:
            self._set_write_seq(_CLOSE_SENTINEL)
        except (ValueError, OSError):
            pass

    def destroy(self):
        self.close()
        try:
            self._view.release()
            self._mm.close()
        except (BufferError, ValueError):
            pass
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass

    def __reduce__(self):
        return (SharedMemoryChannel, (self.path, self.capacity, False))


class IntraProcessChannel:
    """Same-process channel (reference: intra_process_channel.py)."""

    def __init__(self):
        import queue
        self._q = queue.Queue(maxsize=1)
        self._closed = False

    def put(self, value: Any, timeout: Optional[float] = 10.0):
        if self._closed:
            raise ChannelClosedError("intra-process channel closed")
        self._q.put(value, timeout=timeout)

    def get(self, timeout: Optional[float] = 10.0) -> Any:
        import queue
        try:
            value = self._q.get(timeout=timeout)
        except queue.Empty:
            if self._closed:
                raise ChannelClosedError("intra-process channel closed")
            raise ChannelTimeoutError("no value")
        return value

    def close(self):
        self._closed = True

    def destroy(self):
        self.close()


class DeviceChannel:
    """SPSC channel for device-resident jax.Arrays (reference:
    experimental/channel/torch_tensor_accelerator_channel.py:49 — NCCL
    P2P between pinned actors; here PJRT cross-runtime DMA via
    jax.experimental.transfer, which rides ICI/DCN on TPU).

    Control tokens (transfer address + uuid + aval) ride a tiny
    SharedMemoryChannel; the array payload moves runtime-to-runtime and
    never touches host shared memory. Constructed on the writer, shipped
    to the reader by pickling (like SharedMemoryChannel).
    """

    # Arrays kept staged until overwritten. The ctrl channel is a
    # ONE-SLOT SPSC (put blocks until the reader ACKS the previous
    # message, whatever the byte capacity), so a writer can be at most
    # ~2 entries ahead of the reader's payload pull — the window below
    # can never drop an entry the reader still needs.
    _PIN_DEPTH = 4

    def __init__(self, path: str, _role: str = "writer"):
        self._ctrl = SharedMemoryChannel(path, capacity=1 << 16,
                                         create=(_role == "writer"))
        self._path = path
        self._role = _role
        self._uuid = int.from_bytes(os.urandom(4), "big") << 16
        self._staged = []   # writer: [(uuid, array)] keep-alive window
        self._conn = None   # reader: TransferConnection to the writer

    def put(self, array, timeout: Optional[float] = 10.0):
        from . import device_objects as dobj
        server = dobj._ensure_server()
        # Staged arrays hold HBM until overwritten: account them against
        # the same process budget as device_put_ref pins so a fast writer
        # backpressures instead of silently growing the keep-alive window
        # (reference: gpu_object_manager's producer/consumer accounting).
        nbytes = int(array.nbytes)
        if not dobj.reserve_bytes(nbytes, timeout):
            raise TimeoutError(
                "DeviceChannel.put blocked on the device-object HBM "
                f"budget for {timeout}s (pinned={dobj.pinned_bytes()}B)")
        self._uuid += 1
        server.await_pull(self._uuid, [array])
        self._staged.append((self._uuid, array, nbytes))
        if len(self._staged) > self._PIN_DEPTH:
            _, _, old_bytes = self._staged.pop(0)
            dobj.release_bytes(old_bytes)
        self._ctrl.put((dobj._server_addr, self._uuid,
                        tuple(array.shape), str(array.dtype)), timeout)

    def get(self, timeout: Optional[float] = 10.0):
        import jax
        import numpy as np

        from . import device_objects as dobj
        addr, uuid, shape, dtype = self._ctrl.get(timeout)
        if self._conn is None:
            self._conn = dobj._ensure_server().connect(addr)
        spec = jax.ShapeDtypeStruct(
            shape, np.dtype(dtype),
            sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]))
        return self._conn.pull(uuid, [spec])[0]

    def close(self):
        self._ctrl.close()

    def destroy(self):
        if self._staged:
            from . import device_objects as dobj
            for _, _, nbytes in self._staged:
                dobj.release_bytes(nbytes)
        self._staged.clear()
        self._ctrl.destroy()

    def __reduce__(self):
        return (DeviceChannel, (self._path, "reader"))
