"""The driver of the SDAR serving cell: `serve_cell_lfm2`'s replica (the
expert counters and the in-place prefill's sums marked at the window's
edges, the decode step's and the prefill chunk's device time by instruction,
the rows' committed tokens a tick) behind closed-loop traffic whose every
request says its own `denoising_steps`, with the block steps' sums marked
beside them and the `sdar/` scopes named beside the `moe/` ones.

What this cell's traffic needs that the harness's generator and client do
not give, brought here as new code (no PR but a `benchmark` one may edit
traffic.py or client.py): a request's `denoising_steps`, drawn from the
traffic file's list in an order its `schedule_seed` fixes, sent in the
request's body (`stream_one`, the client's with that one key more); and ids
that never are the mask's (a prompt that holds it is refused by the engine).

The seventh shim beside six, as serve_cell_lfm2.py is: serve_cell_by_config
should let a configuration name its replica class, the stats it marks and
the scopes it keeps, and client.py take a request's extra body keys
(PERF.md section 7; ROADMAP D14).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from . import client, readers, serve_cell_by_config as by_config, spec
from . import serve_cell_lfm2 as in_place
from . import serve_cell_nemotron_h as counting
from .cluster import BenchFailure, say
from .serve_cell_evabyte import sent_rows
from .serve_cell_lfm2 import traced_mean  # noqa: F401 — the readers use it
from .serve_cell_xing_mhc import SCOPES_OF, scoped_seconds, tick_spans
from .traffic import Request

# stats() keys of the block steps, marked at the window's edges
BLOCK_STATS = ("block_forwards", "commit_forwards", "block_tokens_out",
               "blocks_early", "discarded_tokens", "prefix_shared_tokens",
               # the chunks' own expert counters (the largest bucket's)
               "prefill_chunks_largest", "chunk_expert_pairs",
               "chunk_expert_steps")
# the scopes a split of a program's device time is told by (stderr)
SPLIT = ("attn/qk_norm/", "sdar/attend/", "moe/route/", "moe/experts/",
         "sdar/confidence/", "sdar/unmask/")


def mask_id_of(config: Dict[str, Any], rehearse: bool) -> int:
    from .builders_sdar import model_keys
    return int(model_keys(config, rehearse)["mask_token_id"])


def never_the_mask(ids: List[int], mask_id: int) -> List[int]:
    """The generator draws over the whole vocabulary: the mask's id becomes
    its neighbour."""
    return [t - 1 if t == mask_id else t for t in ids]


class BlockServer(in_place.ShortConvServer):
    """The in-place replica; the marks also carry the block steps' sums,
    and the parity verdict the scope of every instruction of the block step
    and (after a traced span) of the largest chunk under a scope of SPLIT."""

    def _mark(self) -> Dict[str, Any]:
        mark = super()._mark()
        stats = self._engine.stats()
        mark["stats"].update({k: stats[k] for k in BLOCK_STATS
                              if k in stats})
        return mark

    async def bench_warm(self, prompts) -> float:
        mask_id = mask_id_of(self._bench_config, self._bench_rehearse)
        return await super().bench_warm(
            [[never_the_mask(p, mask_id) for p in round_]
             for round_ in prompts])

    async def bench_parity(self) -> Dict[str, Any]:
        out = await counting.CountingServer.bench_parity(self)
        engine = self._engine

        def scopes():
            kept = lambda text: {  # noqa: E731
                name: scope + "/" for name, scope
                in counting.instruction_scopes(text).items()
                if any(s in scope + "/" for s in SPLIT)}
            named = {"decode_instructions": kept(
                engine.decode_program_text())}
            if getattr(self, "_traced", False):
                named["chunk_instructions"] = kept(
                    engine.lower_chunk().compile().as_text())
            return named
        out.update(await self._off_loop(
            lambda: self._between_steps(scopes)))
        return out


async def stream_one(host: str, port: int, request: Request,
                     row: Dict[str, Any], vocab: int,
                     route: str = "/llm") -> None:
    """`client.stream_one` with the request's `extra` keys in its body
    (`with_steps` hangs them on the request); fills `row` as that does."""
    body = json.dumps(dict(
        {"prompt_tokens": request.prompt, "max_new_tokens": request.max_new,
         "stream": True, "temperature": 0.0},
        **getattr(request, "extra", {}))).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        row["sent"] = time.monotonic()
        writer.write((f"POST {route} HTTP/1.1\r\nHost: bench\r\n"
                      f"X-RTPU-Request-Id: {row['id']}\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      "Connection: close\r\n\r\n").encode() + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200" not in status:
            rest = await reader.read(300)
            raise RuntimeError(f"{status!r} {rest[:200]!r}")
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            size = await reader.readline()
            if not size:
                raise RuntimeError("stream cut before its last chunk")
            n = int(size.strip() or b"0", 16)
            if n == 0:
                break
            data = await reader.readexactly(n + 2)
            at = time.monotonic()
            got = 0
            for line in data.splitlines():
                if not line.strip():
                    continue
                record = json.loads(line)
                tokens = record.get("tokens", [])
                got += len(tokens)
                if any(not 0 <= t < vocab for t in tokens):
                    row["error"] = f"token id out of range in {tokens}"
                if record.get("error"):
                    row["error"] = str(record["error"])
            if got:
                row["chunks"].append((at, got))
        row["done"] = time.monotonic()
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        row["error"] = f"{type(e).__name__}: {e}"
        row["done"] = time.monotonic()
    finally:
        if writer is not None:
            writer.close()


def steps_of(traffic: Dict[str, Any], index: int) -> int:
    """Request `index`'s denoising steps: the file's list dealt evenly over
    a cycle, in an order its `schedule_seed` fixes for every --seed."""
    n, choices = int(traffic["cycle"]), list(traffic["denoising_steps"])
    cycle, j = divmod(index, n)
    order = np.random.default_rng(
        [int(traffic["schedule_seed"]), 7, cycle]).permutation(n)
    return int(choices[order[j] % len(choices)])


def with_steps(stream: Iterator[Request], traffic: Dict[str, Any],
               mask_id: int) -> Iterator[Request]:
    """The generator's requests, each with its `denoising_steps` and the
    file's rule in `extra`, and no mask id in its prompt."""
    for request in stream:
        request.prompt = never_the_mask(request.prompt, mask_id)
        request.extra = {"denoising_steps": steps_of(traffic, request.index),
                         "remasking": traffic.get("remasking", "static")}
        yield request


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        rehearse: bool, started: float) -> Dict[str, Any]:
    """Fails before any cluster, worker or backend exists where the
    checkout's program cannot build the configuration."""
    missing = by_config.missing_modules(cell.config)
    if missing:
        raise BenchFailure(
            f"this checkout's program has no {', '.join(missing)}: it "
            f"cannot run configuration {cell.entry['config']!r}")
    from . import traffic as traffic_mod
    mask_id = mask_id_of(cell.config, rehearse)
    traffic = dict(cell.traffic)
    if rehearse:
        traffic.update(traffic.get("rehearse", {}))
    originals = (by_config.ConfigParityServer, client.stream_one,
                 traffic_mod.requests)
    # by_config.run reads its ConfigParityServer, serve_cell.run the
    # generator and client.Load its stream_one when they are called
    by_config.ConfigParityServer = BlockServer
    client.stream_one = stream_one
    traffic_mod.requests = lambda *a, **kw: with_steps(
        originals[2](*a, **kw), traffic, mask_id)
    try:
        record = by_config.run(cell, seed, seconds, traced, rehearse,
                               started)
    finally:
        (by_config.ConfigParityServer, client.stream_one,
         traffic_mod.requests) = originals
    sent = sent_rows(record["rows"])
    if len(sent) < len(record["rows"]):
        say(f"bench: {len(record['rows']) - len(sent)} rows left out: "
            f"cancelled before their request was sent")
    record["rows"] = sent
    say_window(record)
    return record


def block_window(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The block steps between the window's marks: row-forwards, those that
    were commits, tokens handed out; None where the program counts none."""
    stats = record["closed"]["stats"]
    if "block_forwards" not in stats:
        return None
    delta = lambda key: readers.stat_delta(record, key)  # noqa: E731
    return {"forwards": delta("block_forwards"),
            "commits": delta("commit_forwards"),
            "tokens": delta("block_tokens_out"),
            "early": delta("blocks_early")}


def chunk_hit_experts(record: Dict[str, Any]) -> Optional[float]:
    """Held experts of one layer that a prefill chunk of the largest bucket
    routed at least one token to, mean over layers and the window's such
    chunks, from the chunks' OWN counters; None where the program keeps
    none or no such chunk fell in the window."""
    opened, closed = record["opened"]["stats"], record["closed"]["stats"]
    if not closed.get("chunk_expert_steps"):
        return None
    chunks = readers.stat_delta(record, "prefill_chunks_largest")
    if chunks <= 0:
        return None
    hit = np.asarray(closed["chunk_expert_steps"], np.int64) \
        - np.asarray(opened["chunk_expert_steps"], np.int64)
    return float(hit.sum() / (hit.shape[0] * chunks))


def slow_visits(record: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The window's three longest visits that the `tick` row kept whole (over four
    times the running median), each with where in the window it ended, its
    seconds by phase and the stamped pauses that overlap it: what names the
    phase of a stall in an untraced run too."""
    from . import tickstalls
    window = tickstalls.visits(record)
    if window is None:
        return []
    begin = record["opened"].get("t", record["t0"])
    slow = sorted(window["slow"], key=lambda s: -s["extent_s"])[:3]
    return [{"ended_at_s": round(s["end"] - begin, 2),
             "extent_s": round(s["extent_s"], 3),
             "phases": {name: round(seconds, 3) for name, seconds
                        in s["phases"].items() if seconds >= 0.001},
             "pauses": [(p["what"], round(p["seconds"], 3))
                        for p in s["pauses"]]} for s in slow]


def say_window(record: Dict[str, Any]) -> None:
    """What the window held (stderr): the block steps' sums, the prompts
    computed, preemptions (the configuration is sized for none), the fewest
    pages the pool had free, the ticks by span, the parity verdict's
    controls, and in a traced run the split of a block step and of a
    prefill chunk by named scope."""
    delta = lambda key: readers.stat_delta(record, key)  # noqa: E731
    free = [t[2] for t in record["report"]["ticks"]]
    stats = record["closed"]["stats"]
    say(f"bench: in the window the block steps {block_window(record)}; "
        f"{delta('prefill_computed_tokens'):.0f} prompt tokens were "
        f"computed in {delta('prefill_chunks'):.0f} chunks and "
        f"{delta('prefix_shared_tokens'):.0f} came from the radix; "
        f"discarded {delta('discarded_tokens'):.0f}; preemptions "
        f"{delta('preemptions'):.0f}; fewest free pages "
        f"{min(free) if free else None} of {record['report']['num_pages']}; "
        f"paged kernel {stats.get('paged_kernel')}")
    say(f"bench: the window's ticks {tick_spans(record['report']['ticks'])}")
    say(f"bench: the window's longest visits {slow_visits(record)}")
    from . import tickphases
    say("bench: a visit's ms by phase " + str({
        name: tickphases.phase_ms(record, *phases)
        for name, phases in (("stage", ("stage", "dispatch")),
                             ("wait", ("wait",)), ("emit", ("emit", "gauges")),
                             ("prefill", ("prefill",)),
                             ("admit", ("admit",)))}))
    from .serve_cell_sarvam_mla import hit_experts
    say(f"bench: experts of a layer hit by a block step "
        f"{hit_experts(record)}, by a chunk of the largest bucket "
        f"{chunk_hit_experts(record)} "
        f"({delta('prefill_chunks_largest'):.0f} such chunks)")
    say(f"bench: of the device's memory, by the configuration's table "
        f"and the ticks' free pages: {filled_by_table(record)}")
    parity = record.get("parity") or {}
    if "controls" in parity:
        say(f"bench: parity logits {parity.get('logits')}; rule "
            f"{parity.get('rule')}; router {parity.get('router')}; timed "
            f"{parity.get('timed')}; controls {parity['controls']}; "
            f"controls that passed {parity.get('controls_that_passed')}; "
            f"{parity.get('seconds')} s")
    for program in SCOPES_OF:
        found = {scope: scoped_seconds(record, scope, program=program)
                 for scope in SPLIT}
        found = {scope: f for scope, f in found.items() if f}
        if found:
            kept = next(iter(found.values()))[1]
            parts = {scope: round(1e3 * seconds / kept["runs"], 3)
                     for scope, (seconds, _) in found.items()}
            say(f"bench: a traced {program} takes "
                f"{1e3 * kept['total_s'] / kept['runs']:.3f} ms on the "
                f"device over {kept['runs']} runs; ms under each scope: "
                f"{parts}")


def filled_by_table(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """`serve_cell_lfm2.filled` by this configuration's table: what of the
    chip's memory the window's requests USE beside what is reserved."""
    from . import costs_sdar
    memory = (record["report"].get("memory") or [None])[0] or {}
    limit = memory.get("bytes_limit")
    ticks = record["report"]["ticks"]
    if not limit or not ticks:
        return None
    table = costs_sdar.table(record["config"])
    used = [record["report"]["num_pages"] - t[2] for t in ticks]
    return {"limit_gb": limit / 1e9,
            "reserved_pct": 100.0 * (table["weights_bytes"]
                                     + table["pool_bytes"]) / limit,
            "filled_mean_pct": 100.0 * (
                table["weights_bytes"]
                + table["page_bytes"] * sum(used) / len(used)) / limit,
            "filled_most_pct": 100.0 * (
                table["weights_bytes"]
                + table["page_bytes"] * max(used)) / limit}
