"""The driver of a serving cell whose model counts on the device:
`serve_cell_by_config.run` with a replica that also marks the engine's
expert counters at the window's edges and, in a traced run, keeps the
device time of each instruction of the decode step beside the reduced
trace, so that a reader can sum it by the named scope the instruction came
from (`moe/route`, `moe/experts`, ...: the profiler's events carry the
instruction, the compiled program's text its scope).

A shim beside a shim: serve_cell_by_config.py hard-wires its replica class
and the counters it marks (STATE_STATS), so `run` below swaps the class for
the call as that file's own `run` swaps replica.BenchLLMServer, and each
layer of `_mark` asks stats() again; harness/trace.py keeps cleaned
operation names only. No PR but a `benchmark` one may edit those files: it
should let a configuration name its replica class and the stats it marks,
and keep instruction names in the reduction (PERF.md section 7).
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from . import serve_cell_by_config as by_config
from . import spec, trace
from .cluster import BenchFailure

# stats() keys of the expert layers, marked at the window's edges
EXPERT_STATS = ("expert_pairs", "expert_steps", "layer_kinds")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)


def instruction_scopes(compiled_text: str) -> Dict[str, str]:
    """instruction name -> the `op_name` its metadata gives (the path of
    named scopes it was traced under), from `compiled.as_text()`."""
    return dict(_INSTRUCTION.findall(compiled_text))


# an event of these covers the events of the body it runs
CONTAINERS = ("while", "conditional", "call")


def program_instructions(xplane: Dict[str, Any], program: str
                         ) -> Dict[str, Any]:
    """Device events of the first TPU plane that start inside a run of
    `program`, summed by instruction: {"runs": count, "total_s": the
    runs' device time, "by_instruction": {name: [events, seconds]}}. A
    loop's, a conditional's or a call's own event is left out: its body's
    instructions have theirs."""
    planes = trace.device_planes(xplane)
    if not planes:
        return {}
    plane = planes[0]
    runs = sorted((s, s + d) for name, s, d in trace._line(
        plane, "XLA Modules") if trace.clean(name).endswith(program))
    sums: Dict[str, list] = {}
    at = 0
    for name, start, dur in sorted(trace._line(plane, "XLA Ops"),
                                   key=lambda e: e[1]):
        while at < len(runs) and runs[at][1] <= start:
            at += 1
        if at < len(runs) and runs[at][0] <= start:
            name = name.split(" = ")[0].strip().lstrip("%")
            if not name.startswith(CONTAINERS):
                row = sums.setdefault(name, [0, 0.0])
                row[0] += 1
                row[1] += dur / 1e9
    return {"runs": len(runs),
            "total_s": sum(b - a for a, b in runs) / 1e9,
            "by_instruction": sums}


def seconds_under(instructions: Dict[str, Any], scopes: Dict[str, str],
                  *needles: str) -> Optional[float]:
    """Device seconds of the kept instructions that were traced under a
    scope that holds any of the needles; None without either."""
    if not instructions or not instructions.get("by_instruction") \
            or not scopes:
        return None
    return sum(seconds for name, (_, seconds)
               in instructions["by_instruction"].items()
               if any(n in scopes.get(name, "") for n in needles))


def expert_window(record: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The expert counters between the window's marks: per E layer and
    held expert the tokens routed (`pairs`) and the decode steps that
    routed it any (`steps`), and the decode steps dispatched; None where
    the program keeps no such counters or no step fell in the window."""
    import numpy as np

    from . import readers
    opened, closed = record["opened"]["stats"], record["closed"]["stats"]
    if not closed.get("expert_pairs"):
        return None
    decode_steps = readers.step_row(record["closed"], "decode")["steps"] \
        - readers.step_row(record["opened"], "decode")["steps"]
    if decode_steps <= 0:
        return None
    delta = lambda key: np.asarray(closed[key], np.int64) \
        - np.asarray(opened[key], np.int64)  # noqa: E731
    return {"pairs": delta("expert_pairs"), "steps": delta("expert_steps"),
            "decode_steps": decode_steps}


def scoped_seconds(record: Dict[str, Any], *needles: str):
    """(device seconds of the traced decode steps under the scopes that
    hold a needle, the kept instructions' summary); None without a trace
    of them."""
    from . import readers
    reduced = readers.trace_of(record)
    kept = (reduced or {}).get("decode_step_instructions")
    scopes = record.get("parity", {}).get("moe_instructions")
    seconds = seconds_under(kept, scopes, *needles)
    return None if seconds is None else (seconds, kept)


class CountingServer(by_config.ConfigParityServer):
    def _mark(self) -> Dict[str, Any]:
        # a mark runs between steps, on the stepping thread: the one place
        # the donated counters may be fetched (engine.read_counters)
        self._engine.read_counters()
        mark = super()._mark()
        stats = self._engine.stats()
        mark["stats"].update({k: stats[k] for k in EXPERT_STATS
                              if k in stats})
        return mark

    async def bench_trace_stop(self, directory: str,
                               keep_events: Optional[str] = None):
        reduced = await super().bench_trace_stop(directory, keep_events)

        def by_instruction():
            return program_instructions(
                trace.load_xplane(trace.find_xplane(directory)),
                "decode_step")
        reduced["decode_step_instructions"] = \
            await self._off_loop(by_instruction)
        return reduced

    async def bench_parity(self) -> Dict[str, Any]:
        """The parity check's verdict, and beside it which instructions of
        the compiled decode step were traced under a `moe/` scope (the
        compile is a hit in the jit's persistent cache where there is
        one, and outside the window either way)."""
        out = await super().bench_parity()

        def scopes():
            text = self._engine.decode_program_text()
            return {name: scope for name, scope
                    in instruction_scopes(text).items() if "moe/" in scope}
        out["moe_instructions"] = await self._off_loop(
            lambda: self._between_steps(scopes))
        return out


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        rehearse: bool, started: float) -> Dict[str, Any]:
    """Fails before any cluster, worker or backend exists where the
    checkout's program cannot build the configuration."""
    missing = by_config.missing_modules(cell.config)
    if missing:
        raise BenchFailure(
            f"this checkout's program has no {', '.join(missing)}: it "
            f"cannot run configuration {cell.entry['config']!r}")
    original = by_config.ConfigParityServer
    # by_config.run reads its ConfigParityServer when it is called
    by_config.ConfigParityServer = CountingServer
    try:
        return by_config.run(cell, seed, seconds, traced, rehearse, started)
    finally:
        by_config.ConfigParityServer = original
