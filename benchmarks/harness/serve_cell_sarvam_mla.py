"""The driver of a serving cell whose callers are SESSIONS over documents:
`serve_cell_nemotron_h`'s replica (the expert counters marked at the
window's edges, the decode step's device time by instruction) with the
latent path's counters marked beside them and logged a tick, and a load
whose set-up sends every session's first ask (document + question), waits
for all of them, and only then starts the closed loop of later turns
(`sessions`, `SessionLoad`). `client.Load` takes its request stream as an
argument and `serve_cell.run` builds the load where it is called, so the
stream and the load's ramp are this file's own and client.py / traffic.py
stay as they are.

A shim beside two shims, as serve_cell_evabyte.py is: no PR but a
`benchmark` one may edit serve_cell.py, which should let a traffic file
name its load class and its set-up requests (PERF.md section 7).
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from . import client, readers, serve_cell_by_config as by_config, spec
from . import serve_cell_nemotron_h as counting
from . import traffic as traffic_mod
from .cluster import BenchFailure, say
from .serve_cell_evabyte import sent_rows

# stats() keys of the latent path, marked at the window's edges
LATENT_STATS = ("latent_rows_attended", "latent_pages_rowwise",
                "latent_pages_distinct", "prefix_shared_tokens",
                "prefill_computed_tokens", "prefill_ctx_rows",
                "radix_evictions", "latent_kernel", "decode_rows",
                "prefill_chunks")
# the engine's running sums (`_ahead_counts`) a tick's log row takes the
# differences of
TICK_SUMS = ("latent_rows_attended", "latent_pages_rowwise",
             "latent_pages_distinct", "decode_rows", "prefill_ctx_rows",
             "prefill_chunks")
FIRST_ASK = 1_000_000      # index of session s's first ask: FIRST_ASK + s


def documents(traffic: Dict[str, Any], seed: int, vocab: int
              ) -> List[List[int]]:
    """The sessions' documents: lengths the evenly spaced quantiles of the
    file's distribution (one multiset for every seed), ids from `seed`."""
    sharing = traffic["sharing"]
    sizes = traffic_mod._sizes(sharing["document_tokens"],
                               int(sharing["documents_per_cycle"]))
    return [traffic_mod._rng(seed, 5, d).integers(
        1, vocab, size=int(size)).tolist() for d, size in enumerate(sizes)]


def _question_sizes(traffic: Dict[str, Any]):
    n = int(traffic["cycle"])
    pairing = traffic_mod._rng(int(traffic.get("pairing_seed", 0)), 0)
    return (traffic_mod._sizes(traffic["prompt_tokens"], n),
            traffic_mod._sizes(traffic["output_tokens"], n)[
                pairing.permutation(n)])


def first_asks(traffic: Dict[str, Any], seed: int, vocab: int,
               docs: List[List[int]]) -> List[traffic_mod.Request]:
    """Each session's first ask: its document, then a question and an
    answer of the cycle's sizes (session s takes the s-th)."""
    questions, answers = _question_sizes(traffic)
    out = []
    for s, doc in enumerate(docs):
        question = traffic_mod._rng(seed, 7, s).integers(
            1, vocab, size=int(questions[s % len(questions)])).tolist()
        out.append(traffic_mod.Request(
            index=FIRST_ASK + s, prompt=doc + question,
            max_new=int(answers[s % len(answers)]),
            shared_tokens=0, document_tokens=len(doc)))
    return out


def later_turns(traffic: Dict[str, Any], seed: int, vocab: int,
                docs: List[List[int]]) -> Iterator[traffic_mod.Request]:
    """The endless stream of later turns. A cycle is `cycle` turns, `asks`
    of each session, in ONE order for every seed (`schedule_seed`); the
    i-th turn of a cycle takes the i-th (question, answer) sizes under
    that order; `seed` draws the question's ids."""
    n = int(traffic["cycle"])
    asks = int(traffic["sharing"]["asks"])
    if asks * len(docs) != n:
        raise BenchFailure(f"a cycle of {n} turns is not {asks} of each of "
                           f"{len(docs)} sessions")
    questions, answers = _question_sizes(traffic)
    schedule = int(traffic.get("schedule_seed", seed))
    index = cycle = 0
    while True:
        order = traffic_mod._rng(schedule, 1, cycle).permutation(n)
        sessions = traffic_mod._rng(schedule, 4, cycle).permutation(
            np.repeat(np.arange(len(docs)), asks))
        ids = traffic_mod._rng(seed, 3, cycle)
        for j in range(n):
            doc = docs[int(sessions[j])]
            question = ids.integers(
                1, vocab, size=int(questions[order[j]])).tolist()
            yield traffic_mod.Request(
                index=index, prompt=doc + question,
                max_new=int(answers[order[j]]), shared_tokens=len(doc),
                document_tokens=len(doc))
            index += 1
        cycle += 1


class SessionLoad(client.Load):
    """`client.Load`'s closed loop behind a set-up of first asks: they go
    out `first_ask_gap_s` apart, ALL finish, then the callers start as
    they always do, and `ramped()` waits for the first asks before it says
    how long the callers' ramp is."""

    def __init__(self, address: str, traffic: Dict[str, Any], seed: int,
                 vocab: int):
        docs = documents(traffic, seed, vocab)
        super().__init__(address, traffic,
                         later_turns(traffic, seed, vocab, docs), vocab)
        self.first = first_asks(traffic, seed, vocab, docs)
        self.first_seconds: Optional[float] = None
        self._first_done = threading.Event()
        self._failure: Optional[str] = None

    def ramped(self) -> float:
        while not self._first_done.wait(1.0):
            if self._finished.is_set():
                break
        if self._failure or not self._first_done.is_set():
            raise BenchFailure(self._failure
                               or "the load ended before its ramp")
        return super().ramped()

    async def _closed(self) -> None:
        gap = float(self.traffic.get("first_ask_gap_s", 0.05))
        began = time.monotonic()
        asks = []
        for request in self.first:
            row = self._row(request, None)
            asks.append(asyncio.ensure_future(client.stream_one(
                self.host, self.port, request, row, self.vocab)))
            await asyncio.sleep(gap)
        await asyncio.gather(*asks)
        bad = [r for r in self.rows if r["error"]
               or sum(n for _, n in r["chunks"]) != r["expected"]]
        if bad:
            self._failure = (f"{len(bad)} first asks failed, e.g. "
                             f"{bad[0]['error']}")
            self._first_done.set()
            return
        self.first_seconds = time.monotonic() - began
        self._first_done.set()
        await super()._closed()


class SessionServer(counting.CountingServer):
    """The counting replica, with the latent path's counters marked at the
    window's edges and logged a tick, and the decode step's instructions
    under the `mla/` scopes named beside the `moe/` ones."""

    def __init__(self, config: Dict[str, Any], seed: int,
                 rehearse: bool = False):
        super().__init__(config, seed, rehearse)
        engine = self._engine
        self._latent_ticks: List[tuple] = []
        step = engine.step

        def logged_step():
            t0 = time.monotonic()
            before = engine._ahead_counts()
            out = step()
            if self._logging:
                after = engine._ahead_counts()
                self._latent_ticks.append((t0,) + tuple(
                    after.get(name, 0) - before.get(name, 0)
                    for name in TICK_SUMS))
            return out

        engine.step = logged_step

    def _mark(self) -> Dict[str, Any]:
        mark = super()._mark()
        stats = self._engine.stats()
        mark["stats"].update({k: stats[k] for k in LATENT_STATS
                              if k in stats})
        return mark

    async def bench_report(self, t0: float, t1: float) -> Dict[str, Any]:
        report = await super().bench_report(t0, t1)
        report["latent_ticks"] = [t for t in self._latent_ticks
                                  if t0 <= t[0] < t1]
        return report

    async def bench_parity(self) -> Dict[str, Any]:
        out = await super().bench_parity()

        def scopes():
            text = self._engine.decode_program_text()
            return {name: scope for name, scope
                    in counting.instruction_scopes(text).items()
                    if "mla/" in scope}
        out["mla_instructions"] = await self._off_loop(
            lambda: self._between_steps(scopes))
        return out


def latent_ticks(record: Dict[str, Any], began: float, ended: float
                 ) -> Optional[Dict[str, float]]:
    """Sums over the logged ticks in [began, ended) that dispatched a
    decode step, and over all of them for the chunks: the latent path's
    counters by name, `steps` and `ticks`. None where the program keeps no
    such counters (the parent) or nothing fell in the span."""
    ticks = [t for t in record["report"].get("latent_ticks", [])
             if began <= t[0] < ended]
    if not ticks:
        return None
    sums = {name: float(sum(t[1 + i] for t in ticks))
            for i, name in enumerate(TICK_SUMS)}
    sums["steps"] = float(sum(1 for t in ticks if t[1 + TICK_SUMS.index(
        "decode_rows")] > 0))
    sums["ticks"] = float(len(ticks))
    return sums if sums["latent_pages_distinct"] else None


def hit_experts(record: Dict[str, Any]) -> Optional[float]:
    """Held experts of one expert layer that a decode step of the window
    routed at least one token to, mean over layers and steps."""
    window = counting.expert_window(record)
    if window is None:
        return None
    return float(window["steps"].sum()
                 / (window["steps"].shape[0] * window["decode_steps"]))


def scoped_seconds(record: Dict[str, Any], key: str, *needles: str):
    """`serve_cell_nemotron_h.scoped_seconds` over the instructions the
    parity verdict lists under `key`."""
    reduced = readers.trace_of(record)
    kept = (reduced or {}).get("decode_step_instructions")
    seconds = counting.seconds_under(
        kept, record.get("parity", {}).get(key), *needles)
    return None if seconds is None else (seconds, kept)


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
    by_config.ConfigParityServer, client.Load = SessionServer, session_load
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
    configuration is sized for none), the fewest pages the pool had free."""
    delta = lambda key: readers.stat_delta(record, key)  # noqa: E731
    free = [t[2] for t in record["report"]["ticks"]]
    shared, computed = (delta("prefix_shared_tokens"),
                        delta("prefill_computed_tokens"))
    first = load.first_seconds if load is not None else None
    say(f"bench: {len(load.first) if load else 0} first asks in "
        f"{first if first is None else round(first, 1)} s of set-up; in "
        f"the window {shared:.0f} prompt tokens came from the radix and "
        f"{computed:.0f} were computed; radix evictions "
        f"{delta('radix_evictions'):.0f}, preemptions "
        f"{delta('preemptions'):.0f}; pages held a step counted a row / "
        f"once {delta('latent_pages_rowwise'):.0f} / "
        f"{delta('latent_pages_distinct'):.0f}; fewest free pages "
        f"{min(free) if free else None} of {record['report']['num_pages']}; "
        f"latent kernel {record['closed']['stats'].get('latent_kernel')}")
