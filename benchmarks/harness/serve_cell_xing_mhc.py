"""The driver of the Xing4.0 serving cell: `serve_cell_sarvam_mla`'s replica
(the expert counters and the latent path's counters marked at the window's
edges and logged a tick, the decode step's device time by instruction)
behind plain closed-loop traffic (no sessions, no first asks: every prompt
is fresh and is prefilled inside the window), and beside the `moe/` and
`mla/` scopes the `mhc/` ones: which instructions of the decode step and of
the prefill chunk were traced under a hyper-connection, and the chunk's
device time by instruction as the decode step's is kept.

A shim beside three shims, as serve_cell_sarvam_mla.py is: no PR but a
`benchmark` one may edit serve_cell_by_config.py, which should let a
configuration name its replica class and the scopes it keeps (PERF.md
section 7).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

from . import readers, serve_cell_by_config as by_config, spec, trace
from . import serve_cell_nemotron_h as counting
from . import serve_cell_sarvam_mla as latent
from .cluster import BenchFailure, say
from .serve_cell_evabyte import sent_rows

# the scopes a split of a program's device time is told by (stderr)
SPLIT = ("moe/experts/", "moe/route/", "moe/shared/", "/mlp/", "mhc/coeff/",
         "mhc/pre/", "mhc/post/", "mla/q/", "mla/latent/", "mla/absorb/",
         "mla/attend/", "mla/out/")
_LAYER = re.compile(r"layer_(\d+)/")
# where the parity verdict keeps a traced program's instruction scopes
SCOPES_OF = {"decode_step": "decode_instructions",
             "chunk_prefill": "chunk_instructions"}


class MixingServer(latent.SessionServer):
    """The latent path's counting replica; a traced run also keeps the
    prefill chunk's device time by instruction, and the parity verdict
    carries the scope of every instruction of the decode step and (after a
    traced span) of the largest chunk that lies under a scope of SPLIT."""

    async def bench_trace_stop(self, directory: str,
                               keep_events: Optional[str] = None):
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
                # the largest bucket's program: nine chunks in ten are its
                named["chunk_instructions"] = kept(
                    engine.lower_chunk().compile().as_text())
            return named
        out.update(await self._off_loop(
            lambda: self._between_steps(scopes)))
        return out


def scoped_seconds(record: Dict[str, Any], *needles: str,
                   program: str = "decode_step"):
    """(device seconds of the traced runs of `program` under the scopes
    that hold a needle, the kept instructions' summary with its `runs`
    and `total_s`); None without a trace of them."""
    reduced = readers.trace_of(record)
    kept = (reduced or {}).get(program + "_instructions")
    scopes = record.get("parity", {}).get(SCOPES_OF[program])
    seconds = counting.seconds_under(kept, scopes, *needles)
    if seconds is None or not kept.get("runs") or not kept["total_s"]:
        return None
    return seconds, kept


def mhc_by_layer(record: Dict[str, Any]) -> Optional[List[float]]:
    """Device seconds a decode step spends under `mhc/` in each layer (a
    layer is two sublayers), from the traced steps; None without them."""
    reduced = readers.trace_of(record)
    kept = (reduced or {}).get("decode_step_instructions")
    scopes = record.get("parity", {}).get("decode_instructions")
    if not kept or not kept.get("runs") or not scopes:
        return None
    by_layer: Dict[int, float] = {}
    for name, (_, seconds) in kept["by_instruction"].items():
        found = _LAYER.search(scopes.get(name, ""))
        if found and "mhc/" in scopes[name]:
            layer = int(found.group(1))
            by_layer[layer] = by_layer.get(layer, 0.0) + seconds
    if not by_layer:
        return None
    return [by_layer[layer] / kept["runs"] for layer in sorted(by_layer)]


def traced_mean(record: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """A mean decode step and a mean prefill chunk of the traced span, for
    the cost functions: distinct latent pages and rows decoding a step,
    rows a chunk's last token attends."""
    reduced = readers.trace_of(record)
    if not reduced:
        return None
    sums = latent.latent_ticks(record, reduced["host_began"],
                               reduced["host_ended"])
    if sums is None or not sums["steps"]:
        return None
    return {"pages": sums["latent_pages_distinct"] / sums["steps"],
            "rows": sums["decode_rows"] / sums["steps"],
            "chunk_rows_read": sums["prefill_ctx_rows"]
            / sums["prefill_chunks"] if sums["prefill_chunks"] else None}


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
    by_config.ConfigParityServer = MixingServer
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


def tick_spans(ticks, span_s: float = 5.0) -> Dict[str, Any]:
    """The replica's log of the window's ticks (began, ended, free pages,
    rows decoding, ...) by spans of `span_s` from the first: the ticks and
    the mean rows decoding in each; the longest tick and the longest pause
    between two. Tells a run that read low by a stall (one long tick or
    pause) from one whose every tick was slower or whose rows stood empty
    (a run in twelve read 6 % under the others, PERF.md section 7, PR 52)."""
    if not ticks:
        return {}
    spans: Dict[int, List[int]] = {}
    for began, _, _, decoding, *_ in ticks:
        spans.setdefault(int((began - ticks[0][0]) // span_s), []).append(
            decoding)
    return {"a_span_s": span_s,
            "ticks": [len(spans.get(i, ())) for i in range(max(spans) + 1)],
            "rows_decoding": [round(sum(spans[i]) / len(spans[i]), 1)
                              if i in spans else None
                              for i in range(max(spans) + 1)],
            "longest_tick_s": round(max(t[1] - t[0] for t in ticks), 3),
            "longest_pause_s": round(max(
                [b[0] - a[1] for a, b in zip(ticks, ticks[1:])] or [0.0]), 3)}


def say_window(record: Dict[str, Any]) -> None:
    """What the window held (stderr): prompts computed, evictions and
    preemptions (the configuration is sized for none), the fewest pages
    the pool had free, the ticks by span (`tick_spans`), and in a traced run
    the split of a decode step and of a prefill chunk by named scope."""
    delta = lambda key: readers.stat_delta(record, key)  # noqa: E731
    free = [t[2] for t in record["report"]["ticks"]]
    say(f"bench: in the window {delta('prefill_computed_tokens'):.0f} "
        f"prompt tokens were computed in {delta('prefill_chunks'):.0f} "
        f"chunks and {delta('prefix_shared_tokens'):.0f} came from the "
        f"radix; radix evictions {delta('radix_evictions'):.0f}, "
        f"preemptions {delta('preemptions'):.0f}; fewest free pages "
        f"{min(free) if free else None} of {record['report']['num_pages']}; "
        f"latent kernel {record['closed']['stats'].get('latent_kernel')}")
    say(f"bench: the window's ticks {tick_spans(record['report']['ticks'])}")
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
