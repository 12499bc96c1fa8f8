"""The driver of the LFM2 serving cell: `serve_cell_nemotron_h`'s replica
(the expert counters marked at the window's edges, the decode step's device
time by instruction) behind plain closed-loop traffic whose prompts are
prefilled inside the window, with what the in-place prefill of a
state-carrying model adds marked beside them, the prefill chunk's device
time kept by instruction as the decode step's is, and the `conv/` and
`attn/` scopes named beside the `moe/` ones.

A shim beside three shims, as serve_cell_xing_mhc.py is: no PR but a
`benchmark` one may edit serve_cell_by_config.py, which should let a
configuration name its replica class and the scopes it keeps (PERF.md
section 7).
"""

from __future__ import annotations

import asyncio
import functools
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

from . import costs_lfm2, readers, serve_cell_by_config as by_config
from . import spec, trace
from . import serve_cell_nemotron_h as counting
from .cluster import BenchFailure, say
from .serve_cell_evabyte import sent_rows
# the same two programs' instructions, kept under the same keys
from .serve_cell_xing_mhc import SCOPES_OF, scoped_seconds, tick_spans

# stats() keys of the in-place prefill, marked at the window's edges
PREFILL_STATS = ("prefill_chunks", "prefill_chunks_in_place",
                 "prefill_ctx_rows", "prefill_computed_tokens",
                 "decode_rows", "paged_kernel")
# the scopes a split of a program's device time is told by (stderr)
SPLIT = ("conv/in/", "conv/filter/", "conv/out/", "attn/qk_norm/",
         "attn/attend/", "moe/route/", "moe/experts/", "/mlp/")
# how far into the driver's 4-s span the profiler starts: 1 s is traced
TRACE_LATE_S = 3.0
ConfigServer = by_config.ConfigParityServer


class ShortConvServer(counting.CountingServer):
    """The counting replica; a traced run also keeps the prefill chunk's
    device time by instruction, and the parity verdict carries the scope
    of every instruction of the decode step and (after a traced span) of
    the largest chunk that lies under a scope of SPLIT."""

    def __init__(self, config: Dict[str, Any], seed: int,
                 rehearse: bool = False):
        super().__init__(config, seed, rehearse)
        self._bench_rehearse = rehearse
        # the decoding rows' cached tokens a tick, at their own lengths
        # (the stock tick log rounds every row up to whole pages)
        self._length_ticks: List[tuple] = []
        engine = self._engine
        step = engine.step

        def logged_step():
            t0 = time.monotonic()
            out = step()
            if self._logging:
                rows = [s.length for s in engine.seqs
                        if s.request is not None and s.phase == "decode"]
                self._length_ticks.append((t0, len(rows), sum(rows)))
            return out

        engine.step = logged_step

    async def bench_report(self, t0: float, t1: float) -> Dict[str, Any]:
        report = await super().bench_report(t0, t1)
        report["length_ticks"] = [t for t in self._length_ticks
                                  if t0 <= t[0] < t1]
        return report

    def _mark(self) -> Dict[str, Any]:
        mark = super()._mark()
        stats = self._engine.stats()
        mark["stats"].update({k: stats[k] for k in PREFILL_STATS
                              if k in stats})
        return mark

    async def bench_warm(self, prompts) -> float:
        """The six programs (the decode step and a chunk a bucket) compiled
        TOGETHER, from shapes, before the warm-up's requests call them one
        after another: the calls then find them compiled. At 40 layers
        they were 113 s of a cold start one after another, the longest
        alone 33, and 64 s together (my chip runs, PR 56)."""
        engine = self._engine

        def ahead():
            began = time.monotonic()
            jobs = [engine.lower_decode] + [
                functools.partial(engine.lower_chunk, bucket)
                for bucket in engine.config.prefill_buckets]
            with ThreadPoolExecutor(len(jobs)) as pool:
                list(pool.map(lambda job: job().compile(), jobs))
            return time.monotonic() - began
        spent = await self._off_loop(ahead)
        say(f"bench: six programs compiled together in {spent:.1f}s")
        return spent + await super().bench_warm(prompts)

    async def bench_trace_start(self, directory: str) -> None:
        """The profiler starts TRACE_LATE_S into the span the driver
        gives it (serve_cell.run stops it 4 s after this call returns),
        from a timer: this model's programs are thousands of small
        operations, a 4-s trace of them is 99 MB, and while it was taken
        down the replica twice failed the controller's 10-s health check
        and was replaced (PERF.md section 6, PR 56). What is left is 0.96 s
        at 22 layers and 0.25 s at 40 (the profiler's own start takes
        longer there); both lived."""
        late = 0.0 if self._bench_rehearse else TRACE_LATE_S

        async def start():
            await asyncio.sleep(late)
            await ConfigServer.bench_trace_start(self, directory)
        self._trace_starting = asyncio.ensure_future(start())

    async def bench_trace_stop(self, directory: str,
                               keep_events: Optional[str] = None):
        await self._trace_starting
        reduced = await super().bench_trace_stop(directory, keep_events)

        def by_instruction():
            return counting.program_instructions(
                trace.load_xplane(trace.find_xplane(directory)),
                "chunk_prefill")
        reduced["chunk_prefill_instructions"] = \
            await self._off_loop(by_instruction)
        self._traced = True
        return reduced

    async def bench_parity(self) -> Dict[str, Any]:
        out = await super().bench_parity()
        engine = self._engine

        def scopes():
            kept = lambda text: {  # noqa: E731
                name: scope + "/" for name, scope
                in counting.instruction_scopes(text).items()
                if any(s in scope + "/" for s in SPLIT)}
            named = {"decode_instructions": kept(
                engine.decode_program_text())}
            if getattr(self, "_traced", False):
                # the largest bucket's program: four chunks in five are its
                named["chunk_instructions"] = kept(
                    engine.lower_chunk().compile().as_text())
            return named
        out.update(await self._off_loop(
            lambda: self._between_steps(scopes)))
        return out


def traced_mean(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """A mean decode step of the traced span (rows decoding, and the
    tokens they hold cached, at the rows' own lengths: this replica's
    `length_ticks`) and a mean prefill chunk of the window (the rows its
    last token attends: the engine's `prefill_ctx_rows` a chunk between the
    marks), for the cost functions. None without a trace or a step in it."""
    reduced = readers.trace_of(record)
    if not reduced:
        return None
    ticks = [t for t in record["report"].get("length_ticks", ())
             if reduced["host_began"] <= t[0] < reduced["host_ended"]
             and t[1]]
    if not ticks:
        return None
    opened, closed = record["opened"]["stats"], record["closed"]["stats"]
    chunks = closed.get("prefill_chunks", 0) - opened.get("prefill_chunks", 0)
    ctx = closed.get("prefill_ctx_rows", 0) \
        - opened.get("prefill_ctx_rows", 0)
    return {"rows": sum(t[1] for t in ticks) / len(ticks),
            "context_tokens": sum(t[2] for t in ticks) / len(ticks),
            "chunk_rows_read": ctx / chunks if chunks and ctx else None}


def filled(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """What of the chip's memory the window's requests USE, beside what is
    reserved: the weights, the pages in use (the mean and the most over the
    window's ticks) and the windows, as shares of the device's limit. The
    pool is sized by ISSUE 56's rule (as large as fits), not by the
    traffic."""
    # the CPU's devices report no memory
    memory = (record["report"].get("memory") or [None])[0] or {}
    limit = memory.get("bytes_limit")
    ticks = record["report"]["ticks"]
    if not limit or not ticks:
        return None
    table = costs_lfm2.table(record["config"])
    pages = record["report"]["num_pages"]
    used = [pages - t[2] for t in ticks]
    fixed = table["weights_bytes"] + table["window_bytes"]
    return {"limit_gb": limit / 1e9,
            "reserved_pct": 100.0 * (fixed + table["pool_bytes"]) / limit,
            "filled_mean_pct": 100.0 * (
                fixed + table["page_bytes"] * sum(used) / len(used)) / limit,
            "filled_most_pct": 100.0 * (
                fixed + table["page_bytes"] * max(used)) / limit}


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
    by_config.ConfigParityServer = ShortConvServer
    try:
        record = by_config.run(cell, seed, seconds, traced, rehearse,
                               started)
    finally:
        by_config.ConfigParityServer = original
    sent = sent_rows(record["rows"])
    if len(sent) < len(record["rows"]):
        say(f"bench: {len(record['rows']) - len(sent)} rows left out: "
            f"cancelled before their request was sent")
    record["rows"] = sent
    say_window(record)
    return record


def say_window(record: Dict[str, Any]) -> None:
    """What the window held (stderr): chunks written in place, windows
    installed, preemptions (the configuration is sized for none), the
    fewest pages the pool had free, the ticks by span, the parity verdict's
    controls, and in a traced run the split of a decode step and of a
    prefill chunk by named scope."""
    delta = lambda key: readers.stat_delta(record, key)  # noqa: E731
    free = [t[2] for t in record["report"]["ticks"]]
    stats = record["closed"]["stats"]
    in_place = delta("prefill_chunks_in_place") \
        if "prefill_chunks_in_place" in stats else None
    say(f"bench: in the window {delta('prefill_computed_tokens'):.0f} "
        f"prompt tokens were computed in {delta('prefill_chunks'):.0f} "
        f"chunks ({in_place} written in place), "
        f"{delta('state_installs'):.0f} windows installed; preemptions "
        f"{delta('preemptions'):.0f}; fewest free pages "
        f"{min(free) if free else None} of {record['report']['num_pages']}; "
        f"paged kernel {stats.get('paged_kernel')}")
    say(f"bench: the window's ticks {tick_spans(record['report']['ticks'])}")
    say(f"bench: of the device's memory, by the configuration's table "
        f"and the ticks' free pages: {filled(record)}")
    parity = record.get("parity") or {}
    if "controls" in parity:
        local = {k: v for k, v in parity.get("local", {}).items()
                 if k != "by_layer"}
        say(f"bench: parity controls {parity['controls']}; local {local}; "
            f"routing {parity.get('routing')}; counters "
            f"{parity.get('counters')}; timed {parity.get('timed')}; "
            f"failed {parity.get('failed')}")
    for program in SCOPES_OF:
        found = {scope: scoped_seconds(record, scope, program=program)
                 for scope in SPLIT}
        if all(found.values()):
            kept = found[SPLIT[0]][1]
            parts = {scope: round(1e3 * seconds / kept["runs"], 3)
                     for scope, (seconds, _) in found.items()}
            say(f"bench: a traced {program} takes "
                f"{1e3 * kept['total_s'] / kept['runs']:.3f} ms on the "
                f"device over {kept['runs']} runs; ms under each scope: "
                f"{parts}")
