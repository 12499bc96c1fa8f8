"""Starting and stopping the program around one run, from a driver process
that never opens a JAX backend (chip_smoke.py's rule: a parent that has
touched JAX holds the chip, and the replica or train worker then cannot)."""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, Optional



class BenchFailure(RuntimeError):
    pass


def say(line: str) -> None:
    """Progress and observations go to stderr; stdout carries the one
    result line, last."""
    print(line, file=sys.stderr, flush=True)


def prepare_environment(root: str, rehearse: bool) -> str:
    """The children inherit os.environ: give them the compile cache inside
    this checkout (a fixed path — it is part of the cache's key), with no
    size cap (the chip machine's ambient cache is capped at 192 MiB and a
    90 MB weight-init entry evicts the rest) and no lower limit on what is
    worth caching (dozens of small programs otherwise compile every run)."""
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        platforms = os.environ.get("JAX_PLATFORMS", "")
        if platforms and "tpu" not in platforms.split(","):
            raise BenchFailure(
                f"JAX_PLATFORMS={platforms!r} keeps JAX off the TPU; "
                "nothing here falls back to the CPU")
    return cache


def start_cluster(chips: int, rehearse: bool,
                  settings: Optional[Dict[str, Any]] = None) -> None:
    """`settings`: the configuration file's "program_settings", the
    program's own documented knobs (RTPU_<NAME>), which its workers read
    from the environment they inherit."""
    import ray_tpu
    for name, value in (settings or {}).items():
        os.environ["RTPU_" + name.upper()] = str(value)
    ray_tpu.init()
    have = ray_tpu.cluster_resources().get("TPU", 0)
    if not rehearse and have < chips:
        raise BenchFailure(f"this machine advertises TPU: {have}, the cell "
                           f"needs {chips}")


def wait_pid_gone(pid: int, what: str, timeout_s: float = 120.0) -> None:
    """Every process a run starts has ended before the run exits. A zombie
    has ended: it holds no chip, only a row its parent has yet to read."""
    def state() -> Optional[str]:
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0]
        except (FileNotFoundError, ProcessLookupError):
            return None
    start = time.monotonic()
    while time.monotonic() - start < timeout_s:
        if state() in (None, "Z"):
            return
        time.sleep(0.1)
    raise BenchFailure(f"{what} (pid {pid}) still alive {timeout_s:.0f}s "
                       "after shutdown")


def check_device(device: Dict[str, Any], chips: int, rehearse: bool) -> None:
    want = "cpu" if rehearse else "tpu"
    if device["platform"] != want:
        raise BenchFailure(f"the worker runs on {device}, expected "
                           f"platform {want!r}")
    if device["count"] < chips:
        raise BenchFailure(f"the worker sees {device['count']} devices, "
                           f"the cell needs {chips}")


def actor_options(chips: int, rehearse: bool) -> Dict[str, Any]:
    if not rehearse:
        return {"num_tpus": chips}
    env = {"JAX_PLATFORMS": "cpu"}
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return {"runtime_env": {"env_vars": env}}
