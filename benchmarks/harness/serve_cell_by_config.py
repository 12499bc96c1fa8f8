"""A serving cell whose configuration names its own parity check.

`serve_cell.run` and `replica.BenchLLMServer` are hard-wired to the dense
decoder's reference (`parity.serve`). A configuration of another
architecture names its comparison under "parity" ("package.module:function",
called as `fn(engine, config, seed)`) and the modules of the program it
needs under "requires"; its traffic file names this module's `run` as its
driver. A shim: a `benchmark` PR should fold the "parity" key into
replica.py and delete this file (PERF.md section 7).
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

from . import readers, replica, serve_cell, spec
from .cluster import BenchFailure, say

# stats() keys of the recurrent-state pool, marked at the window's edges
STATE_STATS = ("state_bytes", "state_installs", "prefix_skipped_recurrent")


class ConfigParityServer(replica.BenchLLMServer):
    def __init__(self, config: Dict[str, Any], seed: int,
                 rehearse: bool = False):
        super().__init__(config, seed, rehearse)
        engine = self._engine
        if hasattr(engine, "_write_state"):
            engine._write_state = replica._Dispatch(
                engine._write_state, "dispatch:write_state")

    def _mark(self) -> Dict[str, Any]:
        mark = super()._mark()
        stats = self._engine.stats()
        mark["stats"].update({k: stats[k] for k in STATE_STATS
                              if k in stats})
        # the report's memory is read after the tear-down, which admits
        # every parked request at once; this is the peak up to the mark
        import jax
        mark["memory"] = [d.memory_stats() for d in jax.devices()]
        return mark

    async def bench_parity(self) -> Dict[str, Any]:
        check = spec.resolve(self._bench_config["parity"])
        return await self._off_loop(
            lambda: self._between_steps(
                lambda: check(self._engine, self._bench_config,
                              self._bench_seed)))


def missing_modules(config: Dict[str, Any]):
    """The configuration's "requires" that this checkout's program lacks.
    `find_spec` imports the parent packages (jax among them) and opens no
    backend."""
    missing = []
    for name in config.get("requires", []):
        try:
            found = importlib.util.find_spec(name) is not None
        except ModuleNotFoundError:
            found = False
        if not found:
            missing.append(name)
    return missing


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        rehearse: bool, started: float) -> Dict[str, Any]:
    """`serve_cell.run` with the replica class above. A program that
    cannot build the configuration fails here, before any cluster, worker
    or backend exists."""
    missing = missing_modules(cell.config)
    if missing:
        raise BenchFailure(
            f"this checkout's program has no {', '.join(missing)}: it "
            f"cannot run configuration {cell.entry['config']!r}")
    original = replica.BenchLLMServer
    # serve_cell.run reads replica.BenchLLMServer when it is called
    replica.BenchLLMServer = ConfigParityServer
    try:
        record = serve_cell.run(cell, seed, seconds, traced, rehearse,
                                started)
    finally:
        replica.BenchLLMServer = original
    in_window = readers.memory_peak_bytes(record["closed"])
    if in_window is not None:
        say(f"bench: device memory peak {in_window / 2 ** 30:.2f} GiB at "
            f"the window's close, "
            f"{readers.hbm_peak_gib(record):.2f} GiB after the tear-down")
    return record
