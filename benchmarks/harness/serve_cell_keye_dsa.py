"""The driver of the serving cell whose rows SELECT what they attend:
`serve_cell_sarvam_mla`'s sessions over documents (`SessionLoad`,
`first_asks`, `later_turns`, by import: a set-up of first asks, then the
closed loop of later turns) on `serve_cell_nemotron_h`'s counting replica,
with the sparse path's counters marked at the window's edges and logged a
tick, and the decode step's instructions under the `dsa/` and `attn/`
scopes named beside the `moe/` ones.

A shim beside three shims, as serve_cell_sarvam_mla.py is: no PR but a
`benchmark` one may edit serve_cell.py, which should let a traffic file
name its load class, its set-up requests and the counters its replica marks
(PERF.md section 7).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from . import client, readers, serve_cell_by_config as by_config, spec
from . import serve_cell_nemotron_h as counting
from .cluster import BenchFailure, say
from .serve_cell_evabyte import sent_rows
from .serve_cell_sarvam_mla import SessionLoad, hit_experts  # noqa: F401

# stats() keys of the sparse path, marked at the window's edges
DSA_STATS = ("index_rows_scanned", "index_pages_rowwise",
             "index_pages_distinct", "sparse_rows_selected",
             "sparse_rows_context", "prefix_shared_tokens",
             "prefill_computed_tokens", "prefill_ctx_rows",
             "radix_evictions", "sparse_kernel", "decode_rows",
             "prefill_chunks", "index_cache_bytes")
# the engine's running sums (`_ahead_counts`) a tick's log row takes the
# differences of
TICK_SUMS = ("index_rows_scanned", "index_pages_rowwise",
             "index_pages_distinct", "sparse_rows_selected",
             "sparse_rows_context", "decode_rows", "prefill_ctx_rows",
             "prefill_chunks")
SCOPES = ("dsa/index", "dsa/select", "dsa/attend", "attn/qkv", "attn/out",
          "moe/route", "moe/experts")


class SparseServer(counting.CountingServer):
    def __init__(self, config: Dict[str, Any], seed: int,
                 rehearse: bool = False):
        super().__init__(config, seed, rehearse)
        engine = self._engine
        self._dsa_ticks: List[tuple] = []
        step = engine.step

        def logged_step():
            t0 = time.monotonic()
            before = engine._ahead_counts()
            out = step()
            if self._logging:
                after = engine._ahead_counts()
                self._dsa_ticks.append((t0,) + tuple(
                    after.get(name, 0) - before.get(name, 0)
                    for name in TICK_SUMS))
            return out

        engine.step = logged_step

    def _mark(self) -> Dict[str, Any]:
        mark = super()._mark()
        stats = self._engine.stats()
        mark["stats"].update({k: stats[k] for k in DSA_STATS if k in stats})
        return mark

    async def bench_report(self, t0: float, t1: float) -> Dict[str, Any]:
        report = await super().bench_report(t0, t1)
        report["dsa_ticks"] = [t for t in self._dsa_ticks if t0 <= t[0] < t1]
        return report

    async def bench_parity(self) -> Dict[str, Any]:
        out = await super().bench_parity()

        def scopes():
            text = self._engine.decode_program_text()
            return {name: scope for name, scope
                    in counting.instruction_scopes(text).items()
                    if any(s in scope for s in SCOPES)}
        out["dsa_instructions"] = await self._off_loop(
            lambda: self._between_steps(scopes))
        return out


def dsa_ticks(record: Dict[str, Any], began: float, ended: float
              ) -> Optional[Dict[str, float]]:
    """Sums over the logged ticks in [began, ended): the sparse path's
    counters by name, `steps` (ticks that dispatched a decode step) and
    `ticks`. None where the program keeps no such counters (the parent) or
    nothing was scored in the span."""
    ticks = [t for t in record["report"].get("dsa_ticks", [])
             if began <= t[0] < ended]
    if not ticks:
        return None
    sums = {name: float(sum(t[1 + i] for t in ticks))
            for i, name in enumerate(TICK_SUMS)}
    sums["steps"] = float(sum(1 for t in ticks if t[1 + TICK_SUMS.index(
        "decode_rows")] > 0))
    sums["ticks"] = float(len(ticks))
    return sums if sums["index_rows_scanned"] else None


def traced_step(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """A mean decode step of the traced span, for the cost functions: keys
    scored, tokens selected and rows decoding, each a step."""
    reduced = readers.trace_of(record)
    if not reduced:
        return None
    sums = dsa_ticks(record, reduced["host_began"], reduced["host_ended"])
    if sums is None or not sums["steps"]:
        return None
    return {"scored": sums["index_rows_scanned"] / sums["steps"],
            "selected": sums["sparse_rows_selected"] / sums["steps"],
            "rows": sums["decode_rows"] / sums["steps"]}


def scoped_seconds(record: Dict[str, Any], *needles: str):
    """(device seconds of the traced decode steps under the scopes that
    hold a needle, the kept instructions' summary with its `runs` and
    `total_s`); None without a trace of them."""
    reduced = readers.trace_of(record)
    kept = (reduced or {}).get("decode_step_instructions")
    seconds = counting.seconds_under(
        kept, record.get("parity", {}).get("dsa_instructions"), *needles)
    if seconds is None or not kept.get("runs") or not kept["total_s"]:
        return None
    return seconds, kept


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        rehearse: bool, started: float) -> Dict[str, Any]:
    """Fails before any cluster, worker or backend exists where the
    checkout's program cannot build the configuration."""
    missing = by_config.missing_modules(cell.config)
    if missing:
        raise BenchFailure(
            f"this checkout's program has no {', '.join(missing)}: it "
            f"cannot run configuration {cell.entry['config']!r}")
    loads: List[SessionLoad] = []

    def session_load(address, traffic, _stream, vocab):
        loads.append(SessionLoad(address, traffic, seed, vocab))
        return loads[-1]

    server, load = by_config.ConfigParityServer, client.Load
    # by_config.run reads its ConfigParityServer, and serve_cell.run
    # client.Load, when they are called
    by_config.ConfigParityServer, client.Load = SparseServer, session_load
    try:
        record = by_config.run(cell, seed, seconds, traced, rehearse,
                               started)
    finally:
        by_config.ConfigParityServer, client.Load = server, load
    sent = sent_rows(record["rows"])
    if len(sent) < len(record["rows"]):
        say(f"bench: {len(record['rows']) - len(sent)} rows left out: "
            f"cancelled before their request was sent")
    record["rows"] = sent
    say_sessions(record, loads[0] if loads else None)
    return record


def say_sessions(record: Dict[str, Any], load: Optional[SessionLoad]
                 ) -> None:
    """What the sessions did (stderr): the first asks' time, what the
    radix gave the later turns, evictions and preemptions (the
    configuration is sized for none), what the steps scored and selected,
    the fewest pages the pool had free."""
    delta = lambda key: readers.stat_delta(record, key)  # noqa: E731
    free = [t[2] for t in record["report"]["ticks"]]
    first = load.first_seconds if load is not None else None
    say(f"bench: {len(load.first) if load else 0} first asks in "
        f"{first if first is None else round(first, 1)} s of set-up; in "
        f"the window {delta('prefix_shared_tokens'):.0f} prompt tokens "
        f"came from the radix and {delta('prefill_computed_tokens'):.0f} "
        f"were computed; radix evictions {delta('radix_evictions'):.0f}, "
        f"preemptions {delta('preemptions'):.0f}; index keys scored "
        f"{delta('index_rows_scanned'):.0f}, tokens selected "
        f"{delta('sparse_rows_selected'):.0f}; index pages a step counted "
        f"a row / once {delta('index_pages_rowwise'):.0f} / "
        f"{delta('index_pages_distinct'):.0f}; fewest free pages "
        f"{min(free) if free else None} of {record['report']['num_pages']}; "
        f"sparse kernel {record['closed']['stats'].get('sparse_kernel')}")
