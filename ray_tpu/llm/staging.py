"""What a decode step is told about its rows, kept between visits.

A visit of `PagedLLMEngine.step` hands the step program the rows' block
tables, their lengths, their sampling triples and, for the programs that
take it, the vector of rows that decode. Of these only the lengths change
every step; a row's table changes once in `page_size` steps and its triple
when the row changes hands. `StagedRows` keeps all of them on the host as
numpy arrays that are never rebuilt, writes what changed where it changed,
and keeps the array last sent of each on the device: a clean array is
passed to the program again, a dirty one is sent (`send`). A row that is
not in the step reads what a fresh array held (length 0, table 0,
temperature 0, top_k 0, top_p 1), so the program's output for it and the
pools' page 0 are what they were when every visit built the arrays anew.

None of the engine's five `decode_step` builders donates one of these
arguments (`donate_argnums` names pools, state and counters alone), so a
device array handed to a step is whole after it and may be handed to the
next.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .sampling import sampler_tier

# the arrays a step takes, under the names `send` knows; `lengths` is sent
# every step, the rest when written since they were last sent
TRIPLE = ("temps", "top_ks", "top_ps")
KEPT = ("tables", "live") + TRIPLE
# the triple a row out of the step reads: no sampling, no filter
_REST = (0.0, 0, 1.0)


class StagedRows:
    """The kept arrays of `rows` slots with tables of `pages_per_seq`
    entries. `sync` brings them in line with the rows of the next step;
    `send` gives the device's copy of one. `held[i]` is the pages row i's
    table holds, `owner[i]` the sequence it was written from (None: the row
    reads zeros), `active` / `index` the rows of the last `sync` as a list
    and as an index array, `tier` `sampler_tier` of the triples as they
    stand. `uploads` counts the arrays sent, `steps` the steps they were
    sent for (`sent`)."""

    def __init__(self, rows: int, pages_per_seq: int):
        self.tables = np.zeros((rows, pages_per_seq), np.int32)
        self.lengths = np.zeros((rows,), np.int32)
        self.temps = np.zeros((rows,), np.float32)
        self.top_ks = np.zeros((rows,), np.int32)
        self.top_ps = np.ones((rows,), np.float32)
        self.live = np.zeros((rows,), bool)
        self.held = np.zeros((rows,), np.int32)
        self.owner: List[Optional[Any]] = [None] * rows
        self.active: List[int] = []
        self.index = np.zeros((0,), np.intp)
        self.tier = 0
        self.uploads = 0
        self.steps = 0
        self._device: Dict[str, Any] = {}
        self._dirty = set(KEPT)

    def sync(self, active: Sequence[int], seqs: Sequence[Any],
             sampling: Callable[[Any], Tuple[float, int, float]]) -> None:
        """Before a step over the rows `active` of `seqs`: a row that left
        since the last step reads zeros again, a row that joined (or whose
        slot changed hands) is written whole with `sampling(request)` as its
        triple and `seq.length` as its length, a row whose pages grew gets
        the new entries. Nothing is written for a row that did not change."""
        if active != self.active:
            for i in set(self.active).difference(active):
                self.clear(i)
            self.active = list(active)
            self.index = np.asarray(self.active, np.intp)
            self.live[:] = False
            self.live[self.index] = True
            self._dirty.add("live")
        owner, held = self.owner, self.held.tolist()
        for i in active:
            seq = seqs[i]
            if owner[i] is not seq:
                # joined, or the slot changed hands inside the step's rows
                owner[i] = seq
                self.lengths[i] = seq.length
                self._repage(i, seq.pages, 0)
                self._retriple(i, sampling(seq.request))
            elif held[i] != len(seq.pages):
                self._repage(i, seq.pages, min(held[i], len(seq.pages)))
        if self._dirty.intersection(TRIPLE):
            self.tier = int(sampler_tier(self.temps, self.top_ks,
                                         self.top_ps))

    def _repage(self, i: int, pages: List[int], keep: int) -> None:
        """Row i's table after its pages changed behind the first `keep`
        (0: the row whole): their ids, then zeros as far as it was held."""
        n, was = len(pages), int(self.held[i])
        self.tables[i, keep:n] = pages[keep:]
        if n < was:
            self.tables[i, n:was] = 0
        self.held[i] = n
        self._dirty.add("tables")

    def _retriple(self, i: int, triple) -> None:
        for name, value in zip(TRIPLE, triple):
            array = getattr(self, name)
            if array[i] != value:
                array[i] = value
                self._dirty.add(name)

    def stale(self, i: int) -> None:
        """Row i's pages changed otherwise than by an append (a closed
        window gave pages back): the next `sync` writes its table whole."""
        self.owner[i] = None

    def clear(self, i: int) -> None:
        """Row i is in no further step of its owner's: zeros again."""
        if self.held[i]:
            self._repage(i, (), 0)
        self.owner[i] = None
        self.lengths[i] = 0
        self._retriple(i, _REST)

    def send(self, name: str):
        """The device's copy of array `name` for the step about to be
        dispatched: sent if it was written since it was last sent (`lengths`
        always), else the array the last step was handed. What is sent is a
        copy: the kept array is written again while the transfer, or a CPU
        backend's view of the buffer, may still read it."""
        if name == "lengths" or name in self._dirty:
            self._device[name] = jnp.asarray(getattr(self, name).copy())
            self._dirty.discard(name)
            self.uploads += 1
        return self._device[name]

    def sent(self, advance: bool) -> None:
        """A step was handed its arrays. With `advance`, each of its rows
        computes one token: the next step finds it a position on."""
        self.steps += 1
        if advance:
            self.lengths[self.index] += 1
