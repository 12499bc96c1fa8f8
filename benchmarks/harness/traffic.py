"""One general traffic generator, driven by a traffic file's parameters.

The multiset of sizes is the same under every seed. Request i of a cycle of
N takes the i-th of N evenly spaced quantiles of each stated distribution;
which prompt length goes with which output length is fixed by the file's
`pairing_seed`; `--seed` permutes the order inside each cycle and draws the
token ids. So a tail cannot move because one seed drew longer prompts.

An open loop's arrivals are a Poisson process at `rate_per_s`: independent
exponential gaps, bursts and lulls included. A file with a `schedule_seed`
fixes ONE realisation of that process and of the order of sizes for every
`--seed`, which then draws only the token ids: on the chip the order alone
(which long miss lands behind which) moved a 90th percentile over ~67
requests by a quarter between seeds, and runs of one order agree to
7-10 % (PERF.md section 6). Without it, `--seed` draws order and gaps too.

Kinds of traffic file:
  closed  `clients` callers, each sends its next request when the last ended
  open    arrivals on a schedule at `rate_per_s`, whatever the system does
  train   `batch` x `sequence` token ids a step
`sharing` (closed or open) gives requests a shared prefix: a stream of
documents, each asked `asks` times, the later asks `ask_offsets` requests
after the first.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt: List[int]
    max_new: int
    due_s: Optional[float] = None      # open loop: offset from the start
    shared_tokens: int = 0             # leading tokens an earlier ask had
    document_tokens: int = 0           # leading tokens of its document


GAP_BLOCK = 1024   # arrival gaps are drawn this many at a time


def stratified(dist: Dict[str, Any], n: int) -> List[float]:
    """The n evenly spaced quantiles (i + 1/2) / n of `dist`, ascending."""
    qs = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "loguniform":
        low, high = float(dist["low"]), float(dist["high"])
        return list(low * (high / low) ** qs)
    raise ValueError(f"unknown distribution {kind!r}")


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # --seed may exceed 2**31; SeedSequence takes any non-negative ints
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, *stream])


def _sizes(dist: Dict[str, Any], n: int) -> np.ndarray:
    return np.maximum(1, np.rint(stratified(dist, n))).astype(np.int64)


def _document_slots(asks: int, offsets: List[int]) -> Iterator[tuple]:
    """Slot s of the request stream -> (document number, asks still to
    come after this one). A new document starts where slot % asks == 0,
    so with offsets of distinct residues first, second and third asks
    interleave evenly; the stream is rolled forward before slot 0, so it
    opens in that steady state and not on a run of first asks."""
    reserved: Dict[int, tuple] = {}
    doc = 0
    top = max(offsets)
    slot = -asks * (top // asks + 1)
    while True:
        if slot in reserved:
            out = reserved.pop(slot)
        else:
            for ask in range(1, asks):
                at = slot + offsets[ask]
                while at in reserved or at % asks == 0:
                    at += 1
                reserved[at] = (doc, asks - 1 - ask)
            out = (doc, asks - 1)
            doc += 1
        if slot >= 0:
            yield out
        slot += 1


def requests(traffic: Dict[str, Any], seed: int,
             vocab: int) -> Iterator[Request]:
    """The endless request stream of a closed or open traffic file."""
    n = int(traffic["cycle"])
    pairing = _rng(int(traffic.get("pairing_seed", 0)), 0)
    prompts = _sizes(traffic["prompt_tokens"], n)
    outputs = _sizes(traffic["output_tokens"], n)[pairing.permutation(n)]
    # what orders the sizes and draws the arrivals: the file's own
    # realisation where it fixes one, else --seed
    schedule = int(traffic.get("schedule_seed", seed))
    open_loop = traffic["kind"] == "open"
    gaps = None
    sharing = traffic.get("sharing")
    slots = docs_per_cycle = doc_sizes = None
    documents: Dict[int, List[int]] = {}
    if sharing:
        asks = int(sharing["asks"])
        docs_per_cycle = int(sharing["documents_per_cycle"])
        doc_sizes = _sizes(sharing["document_tokens"], docs_per_cycle)
        slots = _document_slots(asks, list(sharing["ask_offsets"]))
    due = 0.0
    index = 0
    cycle = 0
    while True:
        order = _rng(schedule, 1, cycle).permutation(n)
        ids = _rng(seed, 3, cycle)
        for j in range(n):
            prompt = ids.integers(1, vocab, size=int(prompts[order[j]]))
            shared = document = 0
            if sharing:
                doc, to_come = next(slots)
                seen = doc in documents
                if not seen:
                    c, k = divmod(doc, docs_per_cycle)
                    size = int(doc_sizes[_rng(schedule, 4, c).permutation(
                        docs_per_cycle)[k]])
                    documents[doc] = _rng(seed, 5, doc).integers(
                        1, vocab, size=size).tolist()
                body = documents[doc]
                document = len(body)
                shared = document if seen else 0
                if not to_come:
                    del documents[doc]
                prompt = body + prompt.tolist()
            else:
                prompt = prompt.tolist()
            if open_loop:
                block, k = divmod(index, GAP_BLOCK)
                if k == 0:
                    gaps = _rng(schedule, 2, block).exponential(
                        1.0 / float(traffic["rate_per_s"]), GAP_BLOCK)
                due += float(gaps[k])
            yield Request(index=index, prompt=prompt,
                          max_new=int(outputs[order[j]]),
                          due_s=due if open_loop else None,
                          shared_tokens=shared, document_tokens=document)
            index += 1
        cycle += 1


def longest(traffic: Dict[str, Any]) -> int:
    """Largest prompt + output of the file: must fit the engine's max_len."""
    n = int(traffic["cycle"])
    top = int(_sizes(traffic["prompt_tokens"], n).max()) \
        + int(_sizes(traffic["output_tokens"], n).max())
    sharing = traffic.get("sharing")
    if sharing:
        top += int(_sizes(sharing["document_tokens"],
                          int(sharing["documents_per_cycle"])).max())
    return top


def train_batch(traffic: Dict[str, Any], seed: int, step: int,
                vocab: int) -> np.ndarray:
    """The token ids of training step `step`: [batch, sequence] int32."""
    return _rng(seed, 6, step).integers(
        0, vocab, size=(int(traffic["batch"]), int(traffic["sequence"])),
        dtype=np.int32)
