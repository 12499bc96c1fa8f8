"""The driver of a serving cell whose rows keep summaries of closed windows
in their pages: `serve_cell_by_config.run` with a replica that also logs,
a tick, the pages the decoding rows HOLD (the stock tick log has their
lengths rounded to pages, which is what a dense row holds and four times
what a row of this model does), puts a host span around `compress_window`,
warms that program before the window, marks the engine's window
counters at the window's edges, and leaves out of the record the rows a
caller made and never sent (`sent_rows`).

A shim beside a shim, as serve_cell_nemotron_h.py is: serve_cell_by_config
hard-wires its replica class and the counters it marks, and replica.py the
tick log's columns and the programs it wraps. No PR but a `benchmark` one
may edit those files: it should let a configuration name its replica
class, the stats it marks and the programs it wraps (PERF.md section 7).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

from . import readers, replica
from . import serve_cell_by_config as by_config
from . import spec
from .cluster import BenchFailure, say

# stats() keys of the windows and their compression, marked at the edges
WINDOW_STATS = ("window_closes_prefill", "window_closes_decode",
                "pages_released", "summary_rows", "window_rows",
                "prefix_skipped_compressed")


class WindowServer(by_config.ConfigParityServer):
    def __init__(self, config: Dict[str, Any], seed: int,
                 rehearse: bool = False):
        super().__init__(config, seed, rehearse)
        engine = self._engine
        self._page_ticks: List[tuple] = []
        if hasattr(engine, "_compress_window"):
            engine._compress_window = replica._Dispatch(
                engine._compress_window, "dispatch:compress_window")
        step = engine.step

        def logged_step():
            t0 = time.monotonic()
            out = step()
            if self._logging:
                rows = [s for s in engine.seqs
                        if s.request is not None and s.phase == "decode"]
                self._page_ticks.append(
                    (t0, len(rows), sum(len(s.pages) for s in rows)))
            return out

        engine.step = logged_step

    async def bench_warm(self, prompts) -> float:
        """The stock warm-up (every prefill bucket, the decode step), then
        a prompt a little longer than a window, so that `compress_window`
        has run before any request."""
        warm_s = await super().bench_warm(prompts)
        model = self._engine.config.model
        if not hasattr(model, "window_size"):
            return warm_s

        def close_one():
            t0 = time.monotonic()
            self._engine.generate(
                [[1 + i % 7 for i in range(model.window_size + 5)]],
                max_new_tokens=3)
            return time.monotonic() - t0
        return warm_s + await self._off_loop(close_one)

    def _mark(self) -> Dict[str, Any]:
        mark = super()._mark()
        stats = self._engine.stats()
        mark["stats"].update({k: stats[k] for k in WINDOW_STATS
                              if k in stats})
        return mark

    async def bench_report(self, t0: float, t1: float) -> Dict[str, Any]:
        report = await super().bench_report(t0, t1)
        report["page_ticks"] = [t for t in self._page_ticks
                                if t0 <= t[0] < t1]
        return report


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
    by_config.ConfigParityServer = WindowServer
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
    say_windows(record)
    return record


def sent_rows(rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The rows whose request was sent. A closed-loop caller makes its
    next row and then opens its connection; `Load.stop()` cancels the
    callers where they stand, and one cancelled in between leaves a row
    with `sent: None`, no chunk, no error: no request at all, and
    `serve_cell.judge` has already left it out of `attempted`. But
    `arith.ttft_samples` compares every row's `sent` with the window's
    edges, so the one reader that calls it in a closed cell
    (ttft_p50_ms.closed: a per-layer metric, read in traced runs only)
    raises TypeError on such a row and the run exits 1 with its numbers
    lost (the driver's refusal of PR 42's first hand-in; reproduced on the
    CPU, 1 traced rehearsal in 12). A row that failed before it was sent (its
    `error` set) stays and still stops the run. For a `benchmark` issue:
    PERF.md section 7."""
    return [r for r in rows if r["sent"] is not None or r["error"]]


def say_windows(record: Dict[str, Any]) -> None:
    """What the window did to the rows' pages (stderr): closes by phase,
    pages handed back, preemptions (the configuration is sized for none),
    and the fewest pages the pool had free."""
    delta = lambda key: readers.stat_delta(record, key)  # noqa: E731
    free = [t[2] for t in record["report"]["ticks"]]
    say(f"bench: windows closed in prefill {delta('window_closes_prefill')}"
        f", in decode {delta('window_closes_decode')}; pages released "
        f"{delta('pages_released')}; preemptions {delta('preemptions')}; "
        f"fewest free pages {min(free) if free else None} of "
        f"{record['report']['num_pages']}")
