"""Radix tree over KV pages: prefix sharing for the paged engine
(reference: SGLang RadixAttention / vLLM automatic prefix caching — the
prefix store is a tree keyed by page-sized token runs, each node owning
one refcounted physical page, so lookup cost scales with the match
length and eviction can drop cold leaves without touching hot ancestor
pages).

The tree holds a reference (via ``PagePool.incref``) on every page it
caches. ``match`` walks the tree for the longest cached prefix of a
prompt and hands the caller refcounted page ids — the caller maps them
into a block table copy-on-write style (the engine never writes a page
it does not own, so no copy is ever actually needed). ``insert`` commits
the full prompt pages of an admitted sequence. Eviction removes only
refcount-1 leaves (pages nothing else maps), oldest ``last_use`` first,
so an entry disappears only when both cold and unshared.

Cost. ``match`` and ``insert`` take one dictionary step a page of the
prompt, whatever the tree holds; ``insert`` ends in ``evict``. ``evict``
and ``clear`` are ``evict_pages`` with another count, and that returns
at its first test when nothing has to leave (``entries <= max_entries``,
``want <= 0``): no walk, no heap. Otherwise a call walks the tree ONCE
(``walks`` counts them), puts the leaves nothing else maps into a heap
by ``last_use``, and from there each node dropped costs one pop and a
look at its parent alone: O(nodes + dropped * log leaves) a call, not a
walk a victim. A page a live row maps has two references and never
counts as a leaf here, so a tree of 48 pinned prompts of ~75 pages
stands at ~3,500 nodes over a budget of 128, and a commit still drops
only what was released (~1 ms there on the serving host, where a walk a
victim took 20-190).

Ties. One clock value stamps one root path (a ``match`` or an
``insert``), and of one path at most one node is a leaf, so leaves
queued by this module's own calls never tie and the victims are exactly
the oldest-first ones. Were ``last_use`` set equal by hand, the order
among equals is the order of queueing: the walk's leaves as it met them,
then parents as their last child left. Any order among equals is right.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Dict, List, Optional, Tuple


class _Node:
    __slots__ = ("key", "page", "parent", "children", "last_use")

    def __init__(self, key: Tuple[int, ...], page: int,
                 parent: Optional["_Node"]):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: Dict[Tuple[int, ...], _Node] = {}
        self.last_use = 0


class RadixPrefixCache:
    """Prefix store over a :class:`PagePool`.

    One node = one full page of prompt tokens = one physical page id
    (ids are shared across layers, exactly like sequence block tables).
    ``max_entries`` is the node budget enforced after each insert;
    ``evict_pages`` frees pages on demand under pool pressure. ``poll``,
    where the owner sets one, is called once a page of an insert and once
    a node evicted (the engine's `DryWatch.poll`: a long prompt's commit
    is the longest stretch its stepping thread spends off the device).
    """

    def __init__(self, pool, page_size: int, max_entries: int = 128):
        self._pool = pool
        self._page_size = page_size
        self.max_entries = max_entries
        self._root = _Node((), -1, None)
        self._clock = 0
        self.entries = 0
        self.hits = 0
        self.misses = 0
        self.walks = 0  # full-tree walks eviction has made
        self.poll = None

    # -- lookup / commit ---------------------------------------------------

    def _max_match_pages(self, tokens: List[int]) -> int:
        # Cap the match one token short of the prompt: at least one tail
        # token must prefill so the sequence has last-position logits to
        # sample its first token from (and the engine always owns the
        # page decode first writes into).
        return max(0, (len(tokens) - 1) // self._page_size)

    def match(self, tokens: List[int]) -> List[int]:
        """Longest cached prefix of ``tokens`` in whole pages. Returns
        the page ids with ONE REFERENCE EACH taken for the caller (drop
        with ``release`` if the caller cannot admit after all). Every
        node on the match path has its recency refreshed."""
        ps = self._page_size
        self._clock += 1
        node = self._root
        pages: List[int] = []
        for i in range(self._max_match_pages(tokens)):
            child = node.children.get(tuple(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            child.last_use = self._clock
            pages.append(child.page)
            node = child
        if pages:
            self.hits += 1
            for page in pages:
                self._pool.incref(page)
        elif len(tokens) // ps:
            # only a prompt with at least one full page can miss — a
            # short prompt has nothing the tree could have held
            self.misses += 1
        return pages

    def release(self, pages: List[int]):
        """Return references handed out by ``match``."""
        for page in pages:
            self._pool.decref(page)

    def insert(self, tokens: List[int], pages: List[int]) -> int:
        """Commit the full prompt pages of ``tokens`` (physical ids
        ``pages``, one per full page). Nodes already present keep their
        existing page (byte-identical by construction); new nodes take a
        reference on theirs. Returns the number of new nodes."""
        ps = self._page_size
        self._clock += 1
        node = self._root
        added = 0
        poll = self.poll
        for i in range(len(tokens) // ps):
            if poll is not None:
                poll()
            key = tuple(tokens[i * ps:(i + 1) * ps])
            child = node.children.get(key)
            if child is None:
                child = _Node(key, pages[i], node)
                node.children[key] = child
                self._pool.incref(pages[i])
                self.entries += 1
                added += 1
            child.last_use = self._clock
            node = child
        self.evict(self.max_entries)
        return added

    # -- eviction ----------------------------------------------------------

    def _unshared_leaves(self) -> List[_Node]:
        """One walk of the tree: the leaves whose page only the tree
        holds, in the walk's order."""
        self.walks += 1
        refs = self._pool.refs
        out = []
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif refs[node.page] == 1:
                out.append(node)
        return out

    def evict(self, max_entries: Optional[int] = None) -> int:
        """Evict LRU refcount-1 leaves until at most ``max_entries``
        nodes remain (pinned/shared pages never move). Returns pages
        freed."""
        if max_entries is None:
            max_entries = self.max_entries
        return self.evict_pages(self.entries - max_entries)

    def evict_pages(self, want: int) -> int:
        """Free up to ``want`` pages by evicting LRU refcount-1 leaves
        (the pool-pressure path asks regardless of the entry budget).
        The leaves are collected once, into a heap by ``last_use``; a
        dropped node's parent joins it when that leaves the parent a leaf
        nothing else maps, so a cold chain goes from its tail upward with
        no second walk. Returns pages freed."""
        if want <= 0:
            return 0
        order = itertools.count()  # equal `last_use`: first queued first
        heap = [(node.last_use, next(order), node)
                for node in self._unshared_leaves()]
        heapq.heapify(heap)
        refs = self._pool.refs
        freed = 0
        while heap and freed < want:
            if self.poll is not None:
                self.poll()
            node = heapq.heappop(heap)[2]
            parent = node.parent
            del parent.children[node.key]
            self._pool.decref(node.page)
            self.entries -= 1
            freed += 1
            if parent is not self._root and not parent.children \
                    and refs[parent.page] == 1:
                heapq.heappush(heap, (parent.last_use, next(order), parent))
        return freed

    def clear(self) -> int:
        """Drop every unshared entry (pages mapped by live sequences
        stay). Returns pages freed."""
        return self.evict_pages(self.entries)

    # -- introspection -----------------------------------------------------

    def pages(self) -> List[int]:
        out = []
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            out.append(node.page)
            stack.extend(node.children.values())
        return out

    def shared_pages(self) -> int:
        """Cached pages currently also mapped by at least one live
        sequence (refcount above the tree's own reference)."""
        return sum(1 for p in self.pages() if self._pool.refs[p] > 1)
