"""Worker process entrypoint
(reference: python/ray/_private/workers/default_worker.py).

Spawned by a raylet's worker pool. Registers back with the raylet, then
serves `push_task` RPCs on its CoreWorker until killed, told to exit, or its
raylet disappears (a dead raylet orphans the worker — exit so nodes die
cleanly in fault-tolerance tests).

Task frames arrive on the flat wire path (see task_spec's codec): the
first push of each shape announces a template, every later push is a
struct-packed delta decoded into a `__slots__` TaskSpec drawn from the
template's freelist and returned to it once the reply has flushed — the
steady-state execution loop runs with no pickler and no spec allocation.
`RTPU_NO_FLAT_WIRE=1` (driver-side) forces the legacy pickled specs for
A/B runs; this worker serves both forms.
"""

from __future__ import annotations

import asyncio
import logging
import os
import sys
import time


def main():
    # Log & forensics plane: stamp every stdout/stderr line and logging
    # record with (task, actor, job, level) BEFORE anything writes —
    # the raylet's pump parses the stamps into its per-worker ring.
    # install_worker_capture puts a level-stamping handler on the root
    # logger (same format as the basicConfig below, which then no-ops);
    # under RTPU_NO_LOG_PLANE it installs nothing and basicConfig runs
    # exactly as before.
    from .logplane import install_worker_capture
    install_worker_capture()
    logging.basicConfig(
        level=logging.INFO,
        format="[worker %(process)d] %(levelname)s %(name)s: %(message)s")
    if "tpu" in os.environ.get("JAX_PLATFORMS", "").split(","):
        # leased chips (the raylet names the platform): the process that
        # held them last may still be tearing the device down
        from ..accelerators.tpu import wait_for_free_chips
        waited = wait_for_free_chips()
        if waited >= 1.0:
            logging.getLogger(__name__).warning(
                "waited %.1f s for the TPU device files to be free", waited)
    # `kill -USR2 <pid>` dumps every thread's stack to stderr (reference:
    # the dashboard's on-demand py-spy; this is the dependency-free
    # always-on variant for debugging wedged workers).
    import faulthandler
    import signal
    try:
        faulthandler.register(signal.SIGUSR2, all_threads=True)
    except (AttributeError, ValueError):
        pass
    # RTPU_SANITIZE=1 (inherited from the raylet) instruments this
    # worker's locks too — must run before any ray_tpu lock exists.
    from .lint import sanitizer as _sanitizer
    _sanitizer.enable_from_env()
    if os.environ.get("RTPU_WORKER_PROFILE"):
        # Dev/profiling hook: dump the io-loop thread's cProfile stats on
        # SIGUSR1 to RTPU_WORKER_PROFILE/<pid>.prof.
        _install_profile_hook(os.environ["RTPU_WORKER_PROFILE"])
    worker_id = bytes.fromhex(os.environ["RTPU_WORKER_ID"])
    session = os.environ["RTPU_SESSION"]
    node_id = os.environ["RTPU_NODE_ID"]
    node_index = int(os.environ["RTPU_NODE_INDEX"])
    raylet_host, raylet_port = os.environ["RTPU_RAYLET_ADDR"].rsplit(":", 1)
    gcs_host, gcs_port = os.environ["RTPU_GCS_ADDR"].rsplit(":", 1)
    raylet_addr = (raylet_host, int(raylet_port))
    gcs_addr = (gcs_host, int(gcs_port))

    from .core_worker import CoreWorker, set_core_worker
    from .rpc import EventLoopThread
    # Warm the flat-wire codec (struct tables + template registry) before
    # the first push lands, keeping import cost off the first task.
    from . import task_spec as _codec  # noqa: F401

    worker = CoreWorker(
        mode="worker", session_name=session, gcs_address=gcs_addr,
        raylet_address=raylet_addr, node_id=node_id, node_index=node_index,
        worker_id=worker_id)
    worker.start()
    set_core_worker(worker)

    raylet = worker.clients.get(raylet_addr)
    reply = raylet.call_sync(
        "register_worker", worker_id=worker_id,
        address=worker.rpc_address, pid=os.getpid(), retries=5)
    if reply.get("exit"):
        sys.exit(0)

    # Stay alive while the raylet does. The raylet is our parent process,
    # so reparenting (getppid changes) is the authoritative death signal —
    # it is immune to event-loop starvation, which on a 1-core box can
    # stall RPC pings for tens of seconds during worker-spawn bursts.
    # Pings remain as a slow fallback for a wedged-but-alive raylet.
    parent = os.getppid()
    ping_misses = 0
    last_ping = time.monotonic()
    while True:
        time.sleep(2.0)
        if os.getppid() != parent:
            logging.getLogger(__name__).warning(
                "raylet process gone; worker exiting")
            os._exit(1)
        if time.monotonic() - last_ping >= 10.0:
            last_ping = time.monotonic()
            try:
                raylet.call_sync("ping", timeout=10, retries=0)
                ping_misses = 0
            except Exception:
                ping_misses += 1
                if ping_misses >= 30:  # ~5 min of continuous failure
                    logging.getLogger(__name__).warning(
                        "raylet unresponsive for ~5min; worker exiting")
                    os._exit(1)


def _install_profile_hook(out_dir: str):
    import cProfile
    import pstats
    import signal

    from .rpc import EventLoopThread

    # One FRESH Profile per toggle cycle: reusing a single instance
    # across cycles accumulated stats forever, and a fixed <pid>.prof
    # overwrote the previous cycle's dump — each cycle now stands alone
    # under a timestamped filename.
    state = {"prof": None}

    def toggle(_sig, _frm):
        loop = EventLoopThread.get().loop
        if state["prof"] is None:
            prof = state["prof"] = cProfile.Profile()
            loop.call_soon_threadsafe(prof.enable)
        else:
            prof, state["prof"] = state["prof"], None

            def dump(prof=prof):
                os.makedirs(out_dir, exist_ok=True)
                stamp = time.strftime("%Y%m%d-%H%M%S")
                path = os.path.join(
                    out_dir, f"{os.getpid()}-{stamp}.prof")
                with open(path, "w") as f:
                    pstats.Stats(prof, stream=f).sort_stats(
                        "cumulative").print_stats(40)

            def disable_then_dump(prof=prof):
                # disable and the dump hand-off run as ONE loop
                # callback: spawning the dump thread before the loop
                # has executed disable() would let pstats walk timing
                # entries the still-profiled loop thread is mutating
                prof.disable()
                from .threads import spawn_daemon
                spawn_daemon(dump, name="rtpu-profile-dump")
            loop.call_soon_threadsafe(disable_then_dump)
    signal.signal(signal.SIGUSR1, toggle)


if __name__ == "__main__":
    main()
