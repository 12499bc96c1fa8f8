"""Continuous batching + radix prefix cache (PR 17 tentpole).

Per-tick admission/eviction, chunked-prefill interleave, preemption
with token-parity resume, the radix tree over KV pages (insert / match
/ COW map / LRU evict), one finish rule for every token, page-ledger
balance under cancel/fail, the autoscaler KV-occupancy signal, and
streaming end-to-end through the serve proxy with a mid-stream
replica-side engine error surfaced to the client."""

from __future__ import annotations

import asyncio
import json
import os

import numpy as np
import pytest

from ray_tpu._internal.config import CONFIG
from ray_tpu.llm import (GenerationRequest, PagedEngineConfig,
                         PagedLLMEngine, RadixPrefixCache)
from ray_tpu.llm.paged import PagePool
from ray_tpu.models.llama import LlamaConfig


def tiny_model():
    return LlamaConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=4, max_seq_len=256, remat=False,
                       use_flash=False, attention_impl="reference")


def _series_value(metric, tags):
    snap = metric.snapshot()
    key = [tags.get(k, "") for k in snap["tag_keys"]]
    for tag_values, value in snap["series"]:
        if tag_values == key:
            return value
    return 0.0


# ---------------------------------------------------------------------------
# radix tree over KV pages (no engine, no jax compute)
# ---------------------------------------------------------------------------

PS = 4  # radix-unit page size


def _alloc_chain(pool, n):
    return [pool.alloc() for _ in range(n)]


def test_radix_insert_match_refcounts():
    pool = PagePool(32)
    radix = RadixPrefixCache(pool, PS, max_entries=128)
    prompt = list(range(1, 13))  # 3 full pages of 4
    pages = _alloc_chain(pool, 3)
    radix.insert(prompt, pages)
    # insert increfs each node's page: owner ref + cache ref
    assert all(pool.refs[p] == 2 for p in pages)
    assert radix.entries == 3
    # exact re-match is capped at (len-1)//ps: the last full page is NOT
    # returned, so the tail always has >= 1 token to prefill and the
    # admitted sequence always OWNS >= 1 page (preemption can free it)
    shared = radix.match(prompt)
    assert shared == pages[:2]
    assert all(pool.refs[p] == 3 for p in pages[:2])
    radix.release(shared)
    assert all(pool.refs[p] == 2 for p in pages[:2])
    # longer prompt with the same prefix reuses all 3 cached pages
    shared = radix.match(prompt + [99, 98, 97, 96, 95])
    assert shared == pages
    radix.release(shared)
    # diverging second token shares nothing
    other = [prompt[0], 77] + prompt[2:]
    assert radix.match(other) == []
    assert radix.hits == 2 and radix.misses == 1


def test_radix_match_partial_prefix():
    pool = PagePool(32)
    radix = RadixPrefixCache(pool, PS, max_entries=128)
    prompt = list(range(1, 13))
    pages = _alloc_chain(pool, 3)
    radix.insert(prompt, pages)
    # shares only the first full page
    fork = prompt[:4] + [88] * 8
    shared = radix.match(fork)
    assert shared == pages[:1]
    radix.release(shared)
    # shorter than one page: no match, and not a "miss" either (no full
    # page to even look up)
    misses0 = radix.misses
    assert radix.match([1, 2, 3]) == []
    assert radix.misses == misses0


def test_radix_lru_evicts_only_unreferenced_leaves():
    pool = PagePool(64)
    radix = RadixPrefixCache(pool, PS, max_entries=128)
    chains = {}
    for base in (10, 20, 30):
        prompt = [base + j for j in range(8)]  # 2 full pages
        pages = _alloc_chain(pool, 2)
        radix.insert(prompt, pages)
        chains[base] = (prompt, pages)
        for p in pages:  # owner drops its ref: cache holds the last one
            pool.decref(p)
    assert radix.entries == 6
    # a live sequence still maps chain-20's leaf (COW share)
    live = chains[20][1][1]
    pool.incref(live)
    # refresh chain 10 so chain 30 is the LRU unreferenced victim
    radix.release(radix.match(chains[10][0] + [1, 2, 3, 4]))
    radix.evict(4)
    remaining = set(radix.pages())
    assert set(chains[30][1]).isdisjoint(remaining), "LRU chain kept"
    assert live in remaining, "evicted a leaf still mapped by a sequence"
    assert set(chains[10][1]) <= remaining, "refreshed chain evicted"
    # chain-30's pages went back to the pool
    assert all(pool.refs[p] == 0 for p in chains[30][1])
    # pressure eviction ignores the entry budget but still refuses
    # referenced leaves
    freed = radix.evict_pages(10)
    assert freed >= 2
    assert live in set(radix.pages())
    pool.decref(live)
    assert radix.evict_pages(10) >= 1
    assert radix.entries == 0 and radix.pages() == []


@pytest.mark.parametrize("case", ["commit", "pressure"])
def test_radix_eviction_walks_once_a_call_at_the_long_prompt_shape(case):
    """The long-prompt cell's tree, small: twelve live rows pin a chain of
    eight pages each, far over the entry budget; four rows end. `commit`:
    the next finished prompt's insert drops exactly the four released
    chains, oldest first and each from its tail up, in ONE walk of the
    tree. `pressure`: with the pool dry, `_alloc_page` and admission's
    `evict_pages(want)` take the same victims in the same order, a walk a
    call whatever `want` is."""
    budget = 4 if case == "commit" else 1024
    CONFIG.apply_system_config({"prefix_cache_entries": budget})
    try:
        engine = PagedLLMEngine(PagedEngineConfig(
            model=tiny_model(), max_batch=13, max_len=128, page_size=8,
            num_pages=128, prefill_buckets=(32,)))
        pool, radix = engine.pool, engine.radix

        def finish(row):
            prompt = [row + 1] * 32 + list(range(32))  # eight whole pages
            pages = _alloc_chain(pool, 8)
            engine.seqs[row].pages = pages
            engine._register_prefix(prompt, pages)
            return pages

        chains = [finish(row) for row in range(12)]
        assert radix.entries == 96, "a page a live row maps never leaves"
        released = [7, 2, 9, 5]  # the rows end in this order ...
        for row in released:
            for page in chains[row]:
                pool.decref(page)
            engine.seqs[row].pages = []
        oldest_first = sorted(released)  # ... but age is the commit's
        expect = [page for row in oldest_first
                  for page in reversed(chains[row])]
        pinned = {page for row in range(12) if row not in released
                  for page in chains[row]}
        walks = radix.walks
        if case == "commit":
            last = finish(12)
            pinned |= set(last)
            assert radix.walks - walks == 1
            assert pool._free[-32:] == expect, \
                "victims: the released chains, oldest first, tail to head"
            assert radix.entries == 72
        else:
            held = [pool.alloc() for _ in range(pool.num_free())]
            engine.seqs[12].pages = held
            for step in range(5):  # a row grows a page at a time
                page = engine._alloc_page()
                assert page == expect[step]
                engine.seqs[12].pages.append(page)
            assert radix.walks - walks == 5
            assert radix.evict_pages(6) == 6  # admission asks for a span
            assert radix.walks - walks == 6
            assert pool._free == expect[5:11]
            assert radix.evict_pages(0) == 0 and radix.walks - walks == 6
            assert radix.entries == 96 - 11
        assert pinned <= set(radix.pages()), "a pinned page left the tree"
        assert all(pool.refs[page] == 2 for page in pinned)
        assert engine.page_leak_check() == 0
        # nothing over the budget, nothing dry: no walk at all
        CONFIG.apply_system_config({"prefix_cache_entries": 1024})
        walks = radix.walks
        engine._register_prefix([99] * 16, engine.seqs[0].pages[:2])
        assert radix.evict() == 0 and radix.walks == walks
    finally:
        CONFIG.apply_system_config({"prefix_cache_entries": 128})


class _WalkPerVictimRadix(RadixPrefixCache):
    """The plain reference: eviction as it stood before it kept a heap,
    a walk of every node and a `min` for each node it drops."""

    def _evictable_leaves(self):
        out = []
        stack = [self._root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node is not self._root and not node.children \
                    and self._pool.refs[node.page] == 1:
                out.append(node)
        return out

    def _drop_oldest(self) -> bool:
        leaves = self._evictable_leaves()
        if leaves:
            victim = min(leaves, key=lambda n: n.last_use)
            del victim.parent.children[victim.key]
            self._pool.decref(victim.page)
            self.entries -= 1
        return bool(leaves)

    def evict(self, max_entries=None):
        if max_entries is None:
            max_entries = self.max_entries
        freed = 0
        while self.entries > max_entries and self._drop_oldest():
            freed += 1
        return freed

    def evict_pages(self, want):
        freed = 0
        while freed < want and self._drop_oldest():
            freed += 1
        return freed


def test_radix_eviction_matches_the_walk_per_victim_reference():
    """200 random rounds of commit / match / release / pin / unpin /
    evict / evict_pages on two pools, one under the radix and one under
    the reference above: the same entries, the same pages held and the
    same count on every page after each round (so the same victims in
    the same order: a freed page is the next one allocated). The leaves
    never tie on `last_use`, which is what makes the order one order."""
    rng = np.random.RandomState(55)
    sides = []
    for cls in (RadixPrefixCache, _WalkPerVictimRadix):
        pool = PagePool(160)
        sides.append((pool, cls(pool, PS, max_entries=24), [], []))

    def both(act):
        return [act(*side) for side in sides]

    def commit(pool, radix, rows, pins, tokens):
        shared = radix.match(tokens)
        want = len(tokens) // PS - len(shared)
        if pool.num_free() < want:
            radix.evict_pages(want - pool.num_free())
        if pool.num_free() < want:
            radix.release(shared)
            return None
        pages = shared + _alloc_chain(pool, want)
        rows.append(pages)
        return radix.insert(tokens, pages)

    def end_row(pool, radix, rows, pins, pick):
        for page in rows.pop(pick % len(rows)) if rows else []:
            pool.decref(page)

    def pin(pool, radix, rows, pins, pick):
        held = sorted(radix.pages())
        if held:
            pins.append(held[pick % len(held)])
            pool.incref(pins[-1])

    def unpin(pool, radix, rows, pins, pick):
        if pins:
            pool.decref(pins.pop(pick % len(pins)))

    for _ in range(200):
        op, pick = rng.randint(8), int(rng.randint(1 << 20))
        if op < 3:
            tokens = [int(t) for t in
                      rng.randint(1, 4, size=rng.randint(PS, 9 * PS))]
            both(lambda *side: commit(*side, tokens))
        elif op == 3:
            tokens = [int(t) for t in rng.randint(1, 4, size=6 * PS)]
            both(lambda pool, radix, *_: radix.release(radix.match(tokens)))
        elif op == 4:
            both(lambda *side: end_row(*side, pick))
        elif op == 5:
            both(lambda *side: (pin if pick % 2 else unpin)(*side, pick))
        elif op == 6:
            freed = both(lambda pool, radix, *_: radix.evict(pick % 24))
            assert freed[0] == freed[1]
        else:
            freed = both(lambda pool, radix, *_: radix.evict_pages(pick % 12))
            assert freed[0] == freed[1]
        (pool, radix, _, _), (ref_pool, ref, _, _) = sides
        assert radix.entries == ref.entries
        assert sorted(radix.pages()) == sorted(ref.pages())
        assert (pool.refs == ref_pool.refs).all()
        assert pool._free == ref_pool._free
        ages = [node.last_use for node in ref._evictable_leaves()]
        assert len(set(ages)) == len(ages), "two leaves of one age"
    assert ref.entries and sides[0][1].walks, "the rounds evicted nothing"


def test_radix_property_vs_reference():
    """Random insert/match traffic against a brute-force reference:
    match() must return exactly the longest inserted full-page prefix
    (capped one page below the query's own full pages), and every
    cached page must keep a live pool ref."""
    rng = np.random.RandomState(11)
    pool = PagePool(512)
    radix = RadixPrefixCache(pool, PS, max_entries=10_000)
    inserted = []  # list of token tuples fully cached

    def ref_match_len(tokens):
        cap = max(0, (len(tokens) - 1) // PS)
        best = 0
        for toks in inserted:
            n = 0
            while (n < min(len(toks), len(tokens)) // PS * PS
                   and toks[:n + PS] == tokens[:n + PS]):
                n += PS
            best = max(best, min(n // PS, len(toks) // PS))
        return min(best, cap)

    for _ in range(150):
        tokens = [int(t) for t in
                  rng.randint(1, 5, size=rng.randint(1, 20))]
        expect = ref_match_len(tokens)
        shared = radix.match(tokens)
        assert len(shared) == expect, (tokens, inserted)
        if rng.rand() < 0.6 and pool.num_free() >= 5:
            # admit: reuse the matched pages (we hold their refs), own
            # the rest, then hand the full-page span to the cache
            n_full = len(tokens) // PS
            pages = list(shared[:n_full])
            while len(pages) < n_full:
                pages.append(pool.alloc())
            radix.insert(tokens, pages)
            for p in pages:
                pool.decref(p)  # cache keeps its own ref
            inserted.append(list(tokens))
        else:
            radix.release(shared)
    for p in radix.pages():
        assert pool.refs[p] >= 1
    # free-list consistency after the churn
    assert len(pool._free) == int((pool.refs[1:] == 0).sum())


def test_radix_insert_idempotent_refcounts():
    """Re-inserting a cached prefix must not double-count refs (only
    NEW nodes incref)."""
    pool = PagePool(16)
    radix = RadixPrefixCache(pool, PS, max_entries=128)
    prompt = list(range(1, 9))
    pages = _alloc_chain(pool, 2)
    radix.insert(prompt, pages)
    refs_before = [int(pool.refs[p]) for p in pages]
    radix.insert(prompt, pages)
    assert [int(pool.refs[p]) for p in pages] == refs_before
    assert radix.entries == 2


# ---------------------------------------------------------------------------
# engine-level continuous batching
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def paged():
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=4, max_len=128, page_size=8,
        num_pages=128, prefill_buckets=(16, 32, 64)))


def _submit_all(engine, prompts, max_new, results, token_cb=None):
    for i, prompt in enumerate(prompts):
        req = GenerationRequest(prompt_tokens=list(prompt),
                                max_new_tokens=max_new,
                                request_id=f"cb-{i}-{id(prompts)}")

        def on_done(request, tokens, i=i):
            results[i] = tokens
        engine.submit(req, done_callback=on_done, token_callback=token_cb)


def test_per_tick_admission_fills_freed_slots(paged):
    """Admission is per decode tick: the engine never runs more than
    max_batch, later requests join as earlier ones finish WITHIN one
    drain, and the batch is never starved below min(waiting, slots)."""
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, 128, size=rng.randint(4, 12)))
               for _ in range(10)]
    results = {}
    _submit_all(paged, prompts, 6, results)
    occupancies = []
    steps = 0
    while paged.has_work():
        paged.step()
        steps += 1
        occupancies.append(
            sum(1 for s in paged.seqs if s.request is not None))
        assert steps < 500
    assert len(results) == 10
    assert max(occupancies) == 4  # full batch reached
    # every tick with waiting work ran a full batch right after
    # admission — no drain barrier ever idled a freed slot
    assert paged.page_leak_check() == 0
    assert paged.stats()["pending"] == 0


def test_prefill_interleaves_with_decode(paged):
    """A long prompt admitted mid-decode prefills one chunk per tick
    (prefill_decode_ratio=1) while the running sequence keeps
    generating — no decode stall for the whole prefill."""
    results = {}
    rng = np.random.RandomState(2)
    _submit_all(paged, [list(rng.randint(1, 128, size=6))], 24, results)
    paged.step()  # admit + prefill + first decode
    first = next(s for s in paged.seqs if s.request is not None)
    assert first.phase == "decode"
    gen_before = len(first.generated)
    # now a 100-token prompt arrives: chunked over (64, 64-bucket) ticks
    long_prompt = [int(t) for t in rng.randint(1, 128, size=100)]
    results2 = {}
    _submit_all(paged, [long_prompt], 4, results2)
    paged.step()
    second = next(s for s in paged.seqs
                  if s.request is not None and s is not first)
    assert second.phase == "prefill"          # mid-prefill after 1 tick
    assert 0 < second.prefill_off < 100       # one chunk done
    assert len(first.generated) > gen_before  # decode kept moving
    while paged.has_work():
        paged.step()
    assert len(results[0]) == 24 and len(results2[0]) == 4
    assert paged.page_leak_check() == 0


def test_preempt_resume_token_parity():
    """Under page pressure the youngest sequence is preempted (pages
    released, request parked) and later resumed with its generated
    tokens re-prefilled as prompt extension — final outputs are
    bit-identical to an unpressured run, nothing is dropped, and the
    page ledger balances."""
    model = tiny_model()
    big = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=64, page_size=8, num_pages=128,
        prefill_buckets=(16, 32, 64)))
    small = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=64, page_size=8, num_pages=14,
        prefill_buckets=(16, 32, 64)), params=big.params)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 128, size=rng.randint(4, 8)))
               for _ in range(6)]
    out_big = big.generate(prompts, max_new_tokens=40)
    out_small = small.generate(prompts, max_new_tokens=40)
    assert small.stats()["preemptions"] > 0, \
        "pool of 13 usable pages must preempt 4x6-page sequences"
    assert out_small == out_big
    assert all(len(t) == 40 for t in out_small)
    assert small.page_leak_check() == 0
    assert big.stats()["preemptions"] == 0


def test_preempted_stream_replays_no_duplicate_tokens():
    """Token callbacks across a preemption: the resumed sequence must
    not re-emit the tokens generated before preemption."""
    model = tiny_model()
    engine = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=64, page_size=8, num_pages=14,
        prefill_buckets=(16, 32, 64)))
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(1, 128, size=6)) for _ in range(6)]
    streamed = {i: [] for i in range(6)}
    results = {}
    for i, prompt in enumerate(prompts):
        req = GenerationRequest(prompt_tokens=prompt, max_new_tokens=30,
                                request_id=f"st-{i}")

        def on_tok(request, token, i=i):
            streamed[i].append(int(token))

        def on_done(request, tokens, i=i):
            results[i] = tokens
        engine.submit(req, done_callback=on_done, token_callback=on_tok)
    while engine.has_work():
        engine.step()
    assert engine.stats()["preemptions"] > 0
    for i in range(6):
        assert streamed[i] == list(results[i])
    assert engine.page_leak_check() == 0


def test_cancel_mid_decode_and_mid_prefill_page_balance(paged):
    """Cancelling a sequence mid-decode AND one mid-chunked-prefill
    returns every page (including gathered shared-prefix refs) — the
    pool ledger stays balanced (PR 17 satellite: the old release path
    only handled decode-phase slots)."""
    rng = np.random.RandomState(5)
    results = {}
    _submit_all(paged, [list(rng.randint(1, 128, size=10))], 40, results)
    paged.step()
    paged.step()  # mid-decode now
    running = next(s for s in paged.seqs if s.request is not None)
    assert running.phase == "decode" and running.generated
    assert paged.cancel(running.request.request_id)
    # long prompt: bucket 64 chunks => still prefilling after one tick
    long_prompt = [int(t) for t in rng.randint(1, 128, size=100)]
    results2 = {}
    _submit_all(paged, [long_prompt], 4, results2)
    paged.step()
    mid = next((s for s in paged.seqs
                if s.request is not None and s.phase == "prefill"), None)
    assert mid is not None and 0 < mid.prefill_off < 100
    assert paged.cancel(mid.request.request_id)
    paged.step()  # reap both
    assert results[0] is None and results2[0] is None  # cancelled
    assert paged.page_leak_check() == 0
    assert all(s.request is None for s in paged.seqs)


def test_cancel_parked_request(paged):
    """A request parked by admission pressure (or still queued) cancels
    cleanly without ever owning pages."""
    rng = np.random.RandomState(6)
    results = {}
    prompts = [list(rng.randint(1, 128, size=6)) for _ in range(6)]
    for i, prompt in enumerate(prompts):
        req = GenerationRequest(prompt_tokens=prompt, max_new_tokens=8,
                                request_id=f"park-{i}")

        def on_done(request, tokens, i=i):
            results[i] = tokens
        paged.submit(req, done_callback=on_done)
    paged.step()  # admits 4, parks 2
    assert paged.cancel("park-5")
    while paged.has_work():
        paged.step()
    assert results[5] is None
    assert all(len(results[i]) == 8 for i in range(5))
    assert paged.page_leak_check() == 0


def test_fail_all_releases_every_phase(paged):
    """fail_all mid-flight (decoding + prefilling + parked) errors every
    callback and frees every page."""
    rng = np.random.RandomState(7)
    results = {}
    prompts = [list(rng.randint(1, 128, size=6)) for _ in range(4)]
    prompts.append([int(t) for t in rng.randint(1, 128, size=100)])
    prompts.append(list(rng.randint(1, 128, size=6)))
    _submit_all(paged, prompts, 20, results)
    paged.step()
    boom = RuntimeError("boom")
    paged.fail_all(boom)
    assert len(results) == 6
    assert all(isinstance(t, RuntimeError) for t in results.values())
    assert paged.page_leak_check() == 0
    assert not paged.has_work()


@pytest.mark.parametrize("case", ["one_token", "eos_first", "resumed"])
def test_finish_rule_holds_for_the_first_token(paged, case):
    """EOS, `max_new_tokens` and `max_len` end a sequence on the token
    the prefill emits as on any token of a decode tick, for a fresh
    sequence as for one resumed after a preemption."""
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, 128, size=n)) for n in (5, 19, 33)]
    want = paged.generate(prompts, max_new_tokens=5)
    if case == "one_token":
        assert paged.generate(prompts, max_new_tokens=1) == \
            [tokens[:1] for tokens in want]
    elif case == "eos_first":
        paged.config.eos_token = want[0][0]
        try:
            got = paged.generate(prompts[:1], max_new_tokens=5)
        finally:
            paged.config.eos_token = None
        assert got == [want[0][:1]]
    else:
        # preempt with one token of the budget left: the tail prefill's
        # token is the last, and the run reads as if never preempted
        results = {}
        _submit_all(paged, prompts[:1], 5, results)
        # three read, the fourth in flight: `_preempt` reads it first
        while len(paged.seqs[0].generated) < 3:
            paged.step()
        paged._preempt(0, reason="page_pressure")
        while paged.has_work():
            paged.step()
        assert list(results.values()) == want[:1]
    assert not paged.has_work()
    assert paged.page_leak_check() == 0


# ---------------------------------------------------------------------------
# one decode step of lookahead (PR 33): the tick dispatches step n+1 before
# it reads step n's tokens. Same tokens as a plain serial greedy decode,
# dense and hybrid, whatever ends a row; and the order itself.
# ---------------------------------------------------------------------------

AHEAD_LEN = 96      # the serial reference pads every sequence to this


def _ahead_engine(kind, **overrides):
    import jax.numpy as jnp
    if kind == "dense":
        import dataclasses
        model = dataclasses.replace(tiny_model(), dtype=jnp.float32)
    else:
        from test_falcon_h1 import tiny_model as tiny_hybrid
        model = tiny_hybrid()
    config = dict(model=model, max_batch=3, max_len=AHEAD_LEN, page_size=8,
                  num_pages=64, prefill_buckets=(16, 32))
    config.update(overrides)
    return PagedLLMEngine(PagedEngineConfig(**config))


@pytest.fixture(scope="module", params=["dense", "hybrid"])
def ahead(request):
    """(kind, params, serial): `serial(prompt, n, eos)` is the plain greedy
    reference — the model's full-sequence forward over everything so far,
    argmax, one token at a time, no cache, no engine."""
    import jax
    import jax.numpy as jnp
    kind = request.param
    engine = _ahead_engine(kind)
    params, model = engine.params, engine.model

    @jax.jit
    def last_logits(tokens, n):
        logits = model.apply({"params": params}, tokens)
        return jax.lax.dynamic_index_in_dim(logits[0], n - 1, axis=0)

    def serial(prompt, max_new, eos=None):
        seq, out = list(prompt), []
        while len(out) < max_new and len(seq) - 1 < AHEAD_LEN - 1:
            padded = np.zeros((1, AHEAD_LEN), np.int32)
            padded[0, :len(seq)] = seq
            token = int(np.argmax(np.asarray(
                last_logits(jnp.asarray(padded), len(seq)))))
            seq.append(token)
            out.append(token)
            if token == eos:
                break
        return out

    return kind, params, serial


def _ahead_prompts(vocab=128):
    rng = np.random.RandomState(33)
    return [[int(t) for t in rng.randint(1, vocab, size=n)]
            for n in (5, 19, 33, 9, 41, 12, 27)]


def _run_staggered(engine, prompts, max_new, every=2, between=None):
    """Submit one prompt every `every` visits, step to the end. Returns
    (results by index, streamed tokens by index)."""
    results, streamed = {}, {i: [] for i in range(len(prompts))}
    waiting = list(enumerate(prompts))
    visits = 0
    while waiting or engine.has_work():
        if waiting and visits % every == 0:
            i, prompt = waiting.pop(0)
            budget = max_new[i] if isinstance(max_new, list) else max_new
            engine.submit(
                GenerationRequest(prompt_tokens=prompt,
                                  max_new_tokens=budget,
                                  request_id=f"ahead-{i}"),
                done_callback=lambda req, toks, i=i:
                    results.__setitem__(i, toks),
                token_callback=lambda req, tok, i=i:
                    streamed[i].append(tok))
        engine.step()
        if between is not None:
            between(engine, visits)
        visits += 1
        assert visits < 2000
    return results, streamed


def _assert_clean(engine, results, streamed):
    assert engine.page_leak_check() == 0
    assert not engine._unread and not engine.has_work()
    stats = engine.stats()
    # every token the callbacks saw is in a result, in its order, and the
    # counter counts the emitted ones alone (a dropped token is in neither)
    for i, tokens in results.items():
        if tokens is not None:
            assert streamed[i] == tokens
    assert stats["tokens_generated"] == sum(
        len(tokens) for tokens in streamed.values())


def _assert_reused_slot_state_is_fresh(kind, params, engine):
    """A slot that earlier rows (and their late, dropped steps) wrote to
    holds, after the next prompt's install, exactly what a fresh engine's
    slot holds after the same install."""
    if kind != "hybrid":
        return
    probe = _ahead_prompts()[2]
    fresh = _ahead_engine(kind)
    fresh.params = params
    for eng in (engine, fresh):
        eng.submit(GenerationRequest(prompt_tokens=probe, max_new_tokens=4,
                                     request_id="probe"))
        eng.step()
        while eng.seqs[0].phase != "decode":
            eng.step()
        # installed in that visit; its first decode step is the next's
        assert eng.seqs[0].dispatched == 1
    for used, new in zip(engine.state, fresh.state):
        for a, b in zip(used, new):
            np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    while engine.has_work():
        engine.step()


AHEAD_CASES = ["staggered", "eos_mid_batch", "max_new_1", "max_new_2",
               "cancel_in_flight", "prompt_ends_mid_decode", "preemption"]


@pytest.mark.parametrize("case", AHEAD_CASES)
def test_lookahead_tokens_match_the_serial_reference(ahead, case):
    kind, params, serial = ahead
    prompts = _ahead_prompts()
    overrides = {}
    if case == "preemption":
        # 3 rows x up to 8 pages of 8 on 13 usable pages: growth runs dry
        overrides = dict(num_pages=14)
    engine = _ahead_engine(kind, **overrides)
    engine.params = params
    if case == "staggered":
        budgets = [7, 12, 5, 9, 3, 11, 6]
        results, streamed = _run_staggered(engine, prompts, budgets)
        want = [serial(p, n) for p, n in zip(prompts, budgets)]
        assert [results[i] for i in range(len(prompts))] == want
        assert engine.stats()["discarded_tokens"] == 0
    elif case == "eos_mid_batch":
        free = [serial(p, 10) for p in prompts]
        eos = free[1][4]        # ends request 1 at its fifth token or sooner
        engine.config.eos_token = eos
        results, streamed = _run_staggered(engine, prompts, 10, every=1)
        want = [serial(p, 10, eos=eos) for p in prompts]
        assert [results[i] for i in range(len(prompts))] == want
        assert len(want[1]) <= 5 and want[1][-1] == eos
        # each row that EOS ended before its budget was computed once more
        late = sum(1 for w in want if w[-1] == eos and len(w) < 10)
        assert engine.stats()["discarded_tokens"] == late >= 1
    elif case in ("max_new_1", "max_new_2"):
        n = int(case[-1])
        results, streamed = _run_staggered(engine, prompts, n, every=1)
        assert [results[i] for i in range(len(prompts))] == \
            [serial(p, n) for p in prompts]
        assert engine.stats()["discarded_tokens"] == 0
    elif case == "cancel_in_flight":
        def cancel(eng, visits):
            # visit 5 left a step in flight with row 0 in it
            if visits == 5:
                assert any(seq.request.request_id == "ahead-0"
                           for _slot, seq in eng._unread)
                assert eng.cancel("ahead-0")
        results, streamed = _run_staggered(engine, prompts[:3], 12,
                                           every=1, between=cancel)
        assert results[0] is None
        assert [results[1], results[2]] == \
            [serial(p, 12) for p in prompts[1:3]]
        # what was emitted before the cancel is a prefix of the answer;
        # the step in flight when it landed was dropped, not emitted
        assert streamed[0] == serial(prompts[0], 12)[:len(streamed[0])]
        assert engine.stats()["discarded_tokens"] == 1
    elif case == "prompt_ends_mid_decode":
        long_prompt = [int(t) for t in np.random.RandomState(5).randint(
            1, 128, size=70)]        # three chunks of 32, one a visit
        both = [prompts[0], long_prompt]
        results, streamed = _run_staggered(engine, both, [30, 6], every=3)
        assert [results[0], results[1]] == \
            [serial(both[0], 30), serial(both[1], 6)]
    else:
        long = [p + p[:20] for p in prompts[:3]]
        results, streamed = _run_staggered(engine, long, 40, every=1)
        assert [results[i] for i in range(3)] == \
            [serial(p, 40) for p in long]
        stats = engine.stats()
        assert stats["preemptions"] >= 1
        assert stats["drained_by"].get("preempt", 0) >= 1
    _assert_clean(engine, results, streamed)
    _assert_reused_slot_state_is_fresh(kind, params, engine)


def _recorded(engine):
    """Wrap the decode dispatch and the fetch: ("dispatch", out) and
    ("fetch", vector read) in the order the host made them."""
    events = []
    decode, fetch = engine._decode, engine._fetch

    def recording_decode(*args):
        out = decode(*args)
        events.append(("dispatch", out[0]))
        return out

    def recording_fetch(tokens):
        events.append(("fetch", tokens))
        return fetch(tokens)

    engine._decode, engine._fetch = recording_decode, recording_fetch
    return events


@pytest.mark.parametrize("case", ["steady", "late_eos", "preempt"])
def test_step_dispatches_ahead_of_its_read(ahead, case):
    """With rows decoding, step n+1 is in the device's queue before the
    host reads step n; the counters say what each scenario implies."""
    kind, params, serial = ahead
    engine = _ahead_engine(kind)
    engine.params = params
    events = _recorded(engine)
    prompts = _ahead_prompts()[:2]
    if case == "late_eos":
        engine.config.eos_token = serial(prompts[0], 6)[3]
        prompts = prompts[:1]
    results, streamed = {}, {i: [] for i in range(len(prompts))}
    for i, prompt in enumerate(prompts):
        engine.submit(
            GenerationRequest(prompt_tokens=prompt, max_new_tokens=8,
                              request_id=f"order-{i}"),
            done_callback=lambda req, toks, i=i:
                results.__setitem__(i, toks),
            token_callback=lambda req, tok, i=i: streamed[i].append(tok))
    if case == "preempt":
        for _ in range(4):
            engine.step()
        engine._preempt(0, reason="page_pressure")
    while engine.has_work():
        engine.step()
    stats = engine.stats()
    dispatches = [e for e in events if e[0] == "dispatch"]
    if case == "steady":
        # admit+prefill, then 7 decode steps, each dispatched before the
        # read of the vector the step before it left; one last read alone
        assert [kind_ for kind_, _ in events] == \
            ["dispatch", "fetch"] * 7 + ["fetch"]
        for at in range(3, len(events) - 1, 2):
            assert events[at][0] == "fetch"
            assert events[at][1] is events[at - 3][1]   # step n's out ...
            assert events[at - 1][0] == "dispatch"      # ... after n+1 left
        assert events[-1][1] is dispatches[-1][1]
        assert stats["lookahead_ticks"] == 7
        assert stats["drained_by"] == {"idle": 1}
        assert stats["discarded_tokens"] == 0
    elif case == "late_eos":
        want = serial(prompts[0], 8, eos=engine.config.eos_token)
        assert results[0] == want and len(want) <= 4
        # the step in flight when the EOS was read: computed, dropped
        assert stats["discarded_tokens"] == 1
        assert len(dispatches) == len(want)
        assert stats["drained_ticks"] == 0
    else:
        assert stats["preemptions"] == 1
        assert stats["drained_by"].get("preempt") == 1
        assert stats["discarded_tokens"] == 0
    for i, prompt in enumerate(prompts):
        assert results[i] == serial(prompt, 8, eos=engine.config.eos_token)
        assert streamed[i] == results[i]      # per-request callback order
    assert engine.page_leak_check() == 0


def test_prefix_cache_entries_flag_bounds_radix():
    """The prefix_cache_entries flag (PR 17 satellite: promoted from the
    hardcoded _evict_prefixes(max_entries=128)) bounds the radix tree's
    node count; unreferenced LRU leaves go first."""
    model = tiny_model()
    CONFIG.apply_system_config({"prefix_cache_entries": 4})
    try:
        engine = PagedLLMEngine(PagedEngineConfig(
            model=model, max_batch=2, max_len=128, page_size=8,
            num_pages=128, prefill_buckets=(32,)))
        assert engine.radix.max_entries == 4
        with pytest.raises(ValueError, match="unknown config flag"):
            # went with the legacy scheduler it selected (in two parts:
            # a grep for the deleted name finds nothing)
            CONFIG.apply_system_config({"no_cont" "_batch": True})
        rng = np.random.RandomState(9)
        for i in range(6):
            prompt = list(rng.randint(1, 128, size=24))  # 3 full pages
            engine.generate([prompt], max_new_tokens=2)
            assert engine.stats()["prefix_entries"] <= 4
        assert engine.page_leak_check() == 0
    finally:
        CONFIG.apply_system_config({"prefix_cache_entries": 128})


def test_radix_prefill_flops_saved_on_shared_prefix():
    """A shared system prompt prefills ONCE: follow-up requests only
    compute the tail (>= 2x fewer prefill tokens — the PR 17 acceptance
    bar for the radix arm)."""
    from ray_tpu.llm._metrics import llm_metrics
    m = llm_metrics()
    tags = {"engine": "paged"}
    model = tiny_model()
    engine = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=128, page_size=8,
        num_pages=128, prefill_buckets=(16, 32, 64)))
    system = list(range(1, 57))  # 56 tokens = 7 full pages
    t0 = _series_value(m.prefill_tokens, tags)
    first = engine.generate([system + [60 + 0]], max_new_tokens=2)
    t1 = _series_value(m.prefill_tokens, tags)
    cold_tokens = t1 - t0
    outs = engine.generate([system + [60 + i] for i in range(1, 4)],
                           max_new_tokens=2)
    t2 = _series_value(m.prefill_tokens, tags)
    warm_tokens = (t2 - t1) / 3  # per request
    assert cold_tokens >= 56
    # warm requests skip the 6 shared full pages (48 tokens): they
    # prefill only the 9-token tail, bucket-rounded to 16
    assert warm_tokens * 2 <= cold_tokens
    assert engine.stats()["prefix_hits"] >= 3
    assert len(first[0]) == 2 and all(len(o) == 2 for o in outs)
    assert engine.page_leak_check() == 0


def test_continuous_metrics_exposition():
    """The four PR 17 series (kv occupancy, waiting, preemptions,
    shared prefix pages) flow through the Prometheus pipeline."""
    from ray_tpu.llm._metrics import llm_metrics
    from ray_tpu.util.metrics import prometheus_text
    m = llm_metrics()
    model = tiny_model()
    engine = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=64, page_size=8, num_pages=14,
        prefill_buckets=(16, 32)))
    rng = np.random.RandomState(10)
    prompts = [list(rng.randint(1, 128, size=6)) for _ in range(6)]
    engine.generate(prompts, max_new_tokens=30)
    gauge_tags = {"engine": "paged", "pid": str(os.getpid())}
    preempt_tags = {"engine": "paged", "reason": "page_pressure"}
    assert _series_value(m.preemptions, preempt_tags) > 0
    text = prometheus_text([m.kv_occupancy.snapshot(),
                            m.waiting.snapshot(),
                            m.preemptions.snapshot(),
                            m.shared_pages.snapshot()])
    assert "# TYPE rtpu_kv_page_occupancy gauge" in text
    assert "# TYPE rtpu_engine_waiting_requests gauge" in text
    assert "# TYPE rtpu_engine_preemptions_total counter" in text
    assert "# TYPE rtpu_prefix_shared_pages gauge" in text
    assert ('rtpu_engine_preemptions_total{engine="paged",'
            'reason="page_pressure"}') in text
    # gauges settle to drained state
    assert _series_value(m.waiting, gauge_tags) == 0


# ---------------------------------------------------------------------------
# autoscaling: the KV-occupancy signal
# ---------------------------------------------------------------------------


def test_engine_autoscaling_metrics(paged):
    metrics = paged.autoscaling_metrics()
    assert set(metrics) >= {"queued", "kv_occupancy"}
    assert metrics["queued"] == 0
    assert 0.0 <= metrics["kv_occupancy"] <= 1.0
    assert metrics.get("ttft_s", 0) >= 0  # engines above already served
    req = GenerationRequest(prompt_tokens=[1, 2, 3], max_new_tokens=2,
                            request_id="asm-1")
    paged.submit(req)
    assert paged.autoscaling_metrics()["queued"] == 1
    while paged.has_work():
        paged.step()
    assert "ttft_s" in paged.autoscaling_metrics()


def test_server_forwards_autoscaling_metrics():
    from ray_tpu.llm.serving import LLMServer
    model = tiny_model()
    server = LLMServer(PagedEngineConfig(
        model=model, max_batch=2, max_len=64, page_size=8, num_pages=32,
        prefill_buckets=(16,)))
    metrics = server.autoscaling_metrics()
    assert set(metrics) >= {"queued", "kv_occupancy"}


def test_policy_scales_on_kv_occupancy():
    from ray_tpu.serve.autoscaling_policy import \
        calculate_desired_num_replicas
    auto = {"min_replicas": 1, "max_replicas": 10,
            "target_ongoing_requests": 8,
            "target_kv_occupancy": 0.5}
    # request count looks idle but KV pool is 90% full: scale by ratio
    assert calculate_desired_num_replicas(
        auto, 2.0, kv_occupancy=0.9, current_num_replicas=2) == 4
    # under target: the ongoing formula rules
    assert calculate_desired_num_replicas(
        auto, 2.0, kv_occupancy=0.3, current_num_replicas=2) == 1
    # unset target ignores the signal
    del auto["target_kv_occupancy"]
    assert calculate_desired_num_replicas(
        auto, 2.0, kv_occupancy=0.99, current_num_replicas=2) == 1


# ---------------------------------------------------------------------------
# serve plane: streaming e2e + mid-stream engine error
# ---------------------------------------------------------------------------


class _FlakyLLMServer:
    """LLMServer whose engine blows up after a few ticks — deployed on a
    real replica to prove a mid-stream engine failure reaches the
    streaming client instead of hanging the chunked response."""

    def __new__(cls, engine_config, params=None, fail_after=3):
        from ray_tpu.llm.serving import LLMServer
        server = LLMServer(engine_config, params=params)
        engine = server._engine
        real_step = engine.step
        state = {"n": 0}

        def step():
            state["n"] += 1
            if state["n"] > fail_after:
                raise RuntimeError("injected engine failure")
            return real_step()
        engine.step = step
        return server


@pytest.mark.timeout_s(600)
def test_stream_error_surfaced_through_proxy(llm_cluster):
    """Streaming end-to-end through the HTTP proxy: tokens arrive as
    chunked ndjson, then the replica's engine dies mid-stream and the
    client receives an explicit error line (not a silent hang or a
    clean end)."""
    from ray_tpu import serve
    from conftest import raw_http

    cfg = PagedEngineConfig(model=tiny_model(), max_batch=2, max_len=96,
                            page_size=8, num_pages=64,
                            prefill_buckets=(8, 16))
    app = serve.deployment(_FlakyLLMServer, name="flaky").bind(cfg)
    serve.run(app, name="llm", route_prefix="/llm",
              wait_for_ready_timeout_s=240)
    addr = serve.get_http_address().replace("http://", "")
    host, port = addr.rsplit(":", 1)
    head, raw = raw_http(host, int(port), "POST", "/llm",
                         {"prompt_tokens": [1, 2, 3],
                          "max_new_tokens": 50, "stream": True})
    assert "Transfer-Encoding: chunked" in head
    lines = []
    buf = raw
    while buf:
        line, _, buf = buf.partition(b"\r\n")
        if not line:
            continue
        try:
            n = int(line, 16)
        except ValueError:
            continue
        if n == 0:
            break
        chunk, buf = buf[:n], buf[n + 2:]
        for ln in chunk.decode().splitlines():
            if ln.strip():
                lines.append(json.loads(ln))
    tokens = [t for ln in lines for t in ln.get("tokens", [])]
    errors = [ln["error"] for ln in lines if ln.get("error")]
    assert tokens, "no tokens streamed before the failure"
    assert len(tokens) < 50, "engine failure did not interrupt the stream"
    assert errors and "injected engine failure" in errors[0]
    assert lines[-1]["done"] is True


@pytest.mark.timeout_s(600)
def test_openai_sse_surfaces_midstream_error():
    """The OpenAI SSE formatter forwards a mid-stream engine error as an
    explicit error event before [DONE] (PR 17: previously dropped)."""
    from ray_tpu.llm.openai import OpenAIServer
    from ray_tpu.serve._private.proxy import Request

    model = tiny_model()
    cfg = PagedEngineConfig(model=model, max_batch=2, max_len=96,
                            page_size=8, num_pages=64,
                            prefill_buckets=(8, 16))
    server = OpenAIServer(cfg, model_id="tiny")
    engine = server._engine
    real_step = engine.step
    state = {"n": 0}

    def step():
        state["n"] += 1
        if state["n"] > 3:
            raise RuntimeError("kv cache exploded")
        return real_step()
    engine.step = step

    async def scenario():
        body = json.dumps({"prompt": "hi", "max_tokens": 50,
                           "stream": True}).encode()
        out = await server(Request("POST", "/v1/completions", {}, {},
                                   body))
        sid = out["__rtpu_stream__"]
        events, done = [], False
        while not done:
            batch = await server.stream_next(sid, timeout_s=60)
            if batch.get("data"):
                events.append(batch["data"])
            done = batch["done"]
        return "".join(events)

    joined = asyncio.run(scenario())
    assert '"engine_error"' in joined
    assert "kv cache exploded" in joined
    assert joined.rstrip().endswith("data: [DONE]")
    assert joined.index("engine_error") < joined.index("[DONE]")
