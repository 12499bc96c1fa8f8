"""What a row of a model keeps between calls, and how a prefill chunk and a
decode step reach it: ONE class a kind of cache, each a `PagedLLMEngine`
(`paged.py`: the scheduler, which names none of them).

`kind_of` decides, once, from the model's configuration; the engine made for
it (`PagedLLMEngine.__new__`) is the kind's class below. A kind owns its
refusals, its pools, its programs (`_kind_programs`; the five dense programs
are built for every kind and replaced by name where a kind has its own), its
counters and the host hooks the scheduler calls: what admission budgets and
shares (`_pages_to_admit`, `_match_prefix`), how a chunk is staged and what
is stored back (`_chunk_args` / `_chunk_done`), what a finished prompt does
(`_commit_prompt`, `_enter_decode`, `_after_prefill`), and what a decode
step accounts, is handed and hands back (`_account_decode`, `_step_args` /
`_step_taken`, `_after_dispatch`). `lower_chunk` / `lower_decode` lower from the same
`_chunk_args` / `_step_args` the tick calls. Each class's docstring is what a
configuration of its kind must provide; every configuration provides its
layer count, kv heads and head size, its type and its flax module
(`module()`).

A new architecture of a kind here costs no line of this package; a new kind
is one class here and an entry in `ENGINES`.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import init_kv_caches
from ..ops.latent_attention import (latent_kernel, pages_spared,
                                    share_schedule)
from ..ops.paged_attention import paged_kernel
from ..ops.sparse_attention import sparse_kernel
from . import reqtrace
from ._metrics import llm_metrics
from .paged import (_GAUGE_TAGS, _TAGS, GenerationRequest, PagedLLMEngine,
                    _Seq, chunk_logits, pool_copies)
from .sampling import (sample_tokens, sample_with_confidence, unmask_block,
                       unmask_count)

# what a configuration answers to, and the kind that makes it
_PROBES = (("state_shapes", "recurrent"), ("window_closes", "windowed"),
           ("latent_cache", "latent"), ("index_cache", "indexed"),
           ("block_length", "blockwise"))


def kind_of(cfg) -> str:
    """The one kind of cache a model configuration asks for: a key of
    `ENGINES`. The only place that probes a configuration for it; one that
    answers to two is refused here (the programs of whichever builder ran
    last were what it got before)."""
    kinds = sorted(kind for probe, kind in _PROBES if hasattr(cfg, probe))
    pooled = hasattr(cfg, "page_pool")
    if pooled and kinds != ["recurrent"]:
        raise NotImplementedError(
            "pools of a model's own shape are built for a model whose rows "
            "carry recurrent state and nothing else")
    if len(kinds) > 1:
        raise NotImplementedError(
            f"{type(cfg).__name__} answers to {' and '.join(kinds)}: a "
            "model configuration is of one kind of cache")
    return "pooled" if pooled else kinds[0] if kinds else "dense"


def layer_caches(cfg) -> Tuple[Tuple[bool, bool, bool], ...]:
    """Per layer, what it keeps between calls: (K/V pages, recurrent
    state, accumulators carried through a decode step). A configuration
    that does not say (`layer_caches()`) has layers of one kind: all attend,
    and all scan if its rows carry state at all."""
    if hasattr(cfg, "layer_caches"):
        return tuple(cfg.layer_caches())
    scans = kind_of(cfg) in ("recurrent", "pooled")
    return ((True, scans, False),) * cfg.num_layers


def _counters_of(cfg):
    """Accumulators a model carries through the decode step (an expert
    layer's per-expert counts; `init_counters()`), [] without them."""
    return cfg.init_counters() if hasattr(cfg, "init_counters") else []


class DenseEngine(PagedLLMEngine):
    """K and V of a row's whole context in its pages, `[kv_heads, pages,
    page_size, head_dim]` a layer: a `LlamaConfig`, or any configuration that
    answers to no other kind. A prefilling row stages a dense cache that its
    chunks extend (`seq.dense_caches`); a finished prompt's owned pages are
    scattered into the pools, a radix-shared prefix is gathered from them.
    Every host hook of the scheduler defaults to this kind's."""

    kind = "dense"

    @property
    def _staged_rows(self) -> int:
        """Positions of a prefilling row's dense cache. Covers the worst
        chunked-prefill write: the last chunk is bucket-rounded, so a
        prompt ending near max_len writes up to (largest_bucket - 1)
        tokens of padding past it. Without the slack,
        dynamic_update_slice would CLAMP the start index and silently
        corrupt earlier positions."""
        config = self.config
        return config.pages_per_seq * config.page_size \
            + config.prefill_buckets[-1]

    def _init_cache(self):
        super()._init_cache()
        # the cached rows the prefill chunks attended (a chunk's rows up to
        # its last real token), and the rows of the dense caches they
        # attended them in: all of which a chunk attended before
        # `ops.attention.attend_cache`
        self._prefill_ctx_rows = 0
        self._prefill_cache_rows = 0

    def _chunk_done(self, seq: _Seq, staged, chunk: int, take: int):
        seq.dense_caches = staged
        self._prefill_ctx_rows += seq.prefill_off + take
        self._prefill_cache_rows += self._staged_rows

    def _kind_counts(self):
        return {"prefill_ctx_rows": self._prefill_ctx_rows,
                "prefill_cache_rows": self._prefill_cache_rows}

    def _dense_programs(self):
        """`_decode`, `_chunk_prefill`, `_dense_zero_caches`,
        `_write_pages`, `_gather_pages`: built for every kind (the
        benchmark's harness reaches for them on every engine) and called by
        those that do not replace them."""
        config, cfg, model = self.config, self.config.model, self.model
        page_sharding = self._page_sharding

        def decode_step(params, k_pages, v_pages, block_tables, lengths,
                        tokens, rng, temperature, top_k, top_p):
            """`tokens`: the vector this step's `out` replaces, [rows] on
            the device: what the step before sampled for each row."""
            caches = [
                {"k": k_pages[i], "v": v_pages[i],
                 "block_tables": block_tables, "lengths": lengths}
                for i in range(cfg.num_layers)
            ]
            logits, new_caches = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None],
                kv_caches=caches, cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            nk = [c["k"] for c in new_caches]
            nv = [c["v"] for c in new_caches]
            if page_sharding is not None:
                # pin the updated pools to the kv-head sharding so the
                # donated-buffer layout is stable across steps
                nk = [jax.lax.with_sharding_constraint(a, page_sharding)
                      for a in nk]
                nv = [jax.lax.with_sharding_constraint(a, page_sharding)
                      for a in nv]
            return out.astype(jnp.int32), nk, nv

        self._decode = jax.jit(decode_step, donate_argnums=(1, 2))

        def chunk_prefill(params, tokens, positions, dense_caches, offset,
                          last=None):
            """One prefill chunk: write K/V for `tokens` into the dense
            caches at `offset`, attend causally over everything cached so
            far. Chunked prefill lifts the prompt cap to max_len — any
            prompt runs as ceil(n/bucket) chunks of one compiled shape
            per bucket (reference: vLLM chunked prefill, delegated by
            llm/_internal/serve/deployments/llm/vllm/). Returns the
            logits of row `last` alone, [1, vocab] float32 (`chunk_logits`:
            zeros, and no head, at -1); called without `last`, the logits
            of every position [1, chunk, vocab], a specialisation of its
            own that only the benchmark's parity check still compiles."""
            hidden, new_caches = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=dense_caches, cache_index=offset, head=False)
            return chunk_logits(model, params, hidden, last), new_caches

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

        staged_rows = self._staged_rows

        def _dense_zero_caches():
            return init_kv_caches(cfg, 1, staged_rows)

        self._dense_zero_caches = jax.jit(
            _dense_zero_caches,
            out_shardings=self._dense_sharding)  # None = default

        def write_pages(k_pages, v_pages, dense_caches, page_ids,
                        start_tok):
            """Scatter pages of a [1, kvh, L, hd] dense prefill cache
            into the pools at physical ids `page_ids`, starting at token
            offset `start_tok`. `page_ids` is padded to pages_per_seq
            with the null page so there is ONE compiled shape per
            dense-cache length (a per-sequence page count would compile
            a program per distinct tail size); clamped gathers send the
            pad lanes' garbage to the reserved null page, never a live
            one."""
            ps_ = config.page_size
            n = page_ids.shape[0]
            nk, nv = [], []
            for (kp, vp, (dk, dv)) in zip(k_pages, v_pages, dense_caches):
                # [1, kvh, L, hd] -> [kvh, n, ps, hd] page-major rows
                idx = start_tok + jnp.arange(n * ps_, dtype=jnp.int32)
                idx = jnp.minimum(idx, dk.shape[2] - 1)
                seg_k = jnp.take(dk[0], idx, axis=1)
                seg_v = jnp.take(dv[0], idx, axis=1)
                kvh_ = seg_k.shape[0]
                seg_k = seg_k.reshape(kvh_, n, ps_, -1)
                seg_v = seg_v.reshape(kvh_, n, ps_, -1)
                uk = kp.at[:, page_ids].set(seg_k.astype(kp.dtype))
                uv = vp.at[:, page_ids].set(seg_v.astype(vp.dtype))
                if page_sharding is not None:
                    uk = jax.lax.with_sharding_constraint(uk, page_sharding)
                    uv = jax.lax.with_sharding_constraint(uv, page_sharding)
                nk.append(uk)
                nv.append(uv)
            return nk, nv

        self._write_pages = jax.jit(write_pages, donate_argnums=(0, 1),
                                    static_argnums=())
        dense_sharding = self._dense_sharding

        def gather_pages(k_pages, v_pages, dense_caches, page_ids):
            """Inverse of write_pages: copy pooled pages into the head
            of a dense prefill cache, so a radix-shared prefix span is
            attended over without recomputing it (zero prefill FLOPs
            for the span). `page_ids` is padded to pages_per_seq with
            the null page for a single compiled shape; padded garbage
            lands at or after the first real tail position, so it is
            either overwritten by the tail chunks or causally masked."""
            out = []
            for (kp, vp, (dk, dv)) in zip(k_pages, v_pages, dense_caches):
                kvh_ = kp.shape[0]
                seg_k = kp[:, page_ids].reshape(
                    kvh_, -1, kp.shape[-1])[None]
                seg_v = vp[:, page_ids].reshape(
                    kvh_, -1, vp.shape[-1])[None]
                ndk = jax.lax.dynamic_update_slice_in_dim(
                    dk, seg_k.astype(dk.dtype), 0, axis=2)
                ndv = jax.lax.dynamic_update_slice_in_dim(
                    dv, seg_v.astype(dv.dtype), 0, axis=2)
                if dense_sharding is not None:
                    ndk = jax.lax.with_sharding_constraint(
                        ndk, dense_sharding)
                    ndv = jax.lax.with_sharding_constraint(
                        ndv, dense_sharding)
                out.append((ndk, ndv))
            return out

        self._gather_pages = jax.jit(gather_pages, donate_argnums=(2,))


class NoPrefix:
    """Beside a kind whose pages are not a prefix's whole K/V: the radix is
    neither asked nor told, and `_prefix_skipped` counts the prompts."""

    _prefix_skipped = 0

    def _match_prefix(self, prompt: List[int]) -> List[int]:
        self._prefix_skipped += 1
        return []

    def _register_prefix(self, prompt: List[int], pages: List[int]):
        pass


class RecurrentEngine(NoPrefix, DenseEngine):
    """Rows that carry recurrent state (a scan layer's) beside their pages
    (Falcon-H1, Nemotron-H). The configuration says in what shape and type
    (`state_shapes()`) and makes it (`init_state(rows)`): a scanning layer
    keeps the arrays `state_shapes()` names, in that order, each a pool of
    `max_batch` rows, row = slot index (a slot that is not decoding is masked
    out of the decode step, and an install overwrites a row whole): a
    convolution window and a scan state (`conv`, `ssm`), or a window alone.
    One whose layers are not all of one kind says what each keeps
    (`layer_caches()`), makes state for the layers that scan only, and may
    carry per-layer accumulators through the decode step
    (`init_counters()`: donated to it and returned by it, so only the
    stepping thread may touch them, between steps: `read_counters`).

    Its programs, in place of the dense three that would not know the state:
    the decode step takes and returns the state pools donated beside the
    page pools, a prefill chunk hands the scanning layers' state on in its
    staging pytree and is told how many of its tokens are real, and
    `write_state` installs a finished prefill's state into its slot. A layer
    is handed, and hands back, what its kind keeps and nothing else:
    `k_pages` / `v_pages` hold a pool per layer that attends, `state` a
    tuple per layer that scans, `counters` a tuple per layer that counts.
    Pages carry no recurrent state, so the radix holds no prefix of it."""

    kind = "recurrent"
    _takes_live = True
    _pads_told = True
    _not_shipped = (
        "{what} ships K/V only: a model whose rows carry recurrent state "
        "cannot be prefilled on another engine yet")
    _no_mesh = ("recurrent state over a tensor mesh is not built: the state "
                "pool is not sharded")

    def _init_cache(self):
        super()._init_cache()
        cfg = self.config.model
        self.state = cfg.init_state(self.config.max_batch)
        self.counters = _counters_of(cfg)
        # what the prompts that finished this visit left to install
        # (`_after_prefill`), and the installs made
        self._state_due: List[Tuple[int, Any]] = []
        self._state_installs = 0

    def _kind_programs(self):
        cfg, model = self.config.model, self.model
        kinds = layer_caches(cfg)
        names = tuple(cfg.state_shapes())

        def by_kind(new):
            """A model's per-layer tuples (k, v, state..., counters...),
            each holding its kind's part only, as the four lists."""
            nk, nv, nstate, ncount = [], [], [], []
            for (attends, scans, counts), kept in zip(kinds, new):
                kept = list(kept)
                if attends:
                    nk.append(kept.pop(0))
                    nv.append(kept.pop(0))
                if scans:
                    nstate.append(tuple(kept.pop(0) for _ in names))
                if counts:
                    ncount.append(tuple(kept))
            return nk, nv, nstate, ncount

        def decode_caches(k_pages, v_pages, state, counters, active,
                          block_tables, lengths):
            """What each layer is handed in a paged decode step."""
            pools = iter(zip(k_pages, v_pages))
            states, counts_of = iter(state), iter(counters)
            caches = []
            for attends, scans, counts in kinds:
                cache = {"active": active}
                if attends:
                    k, v = next(pools)
                    cache.update(k=k, v=v, block_tables=block_tables,
                                 lengths=lengths)
                if scans:
                    cache.update(zip(names, next(states)))
                if counts:
                    cache["pairs"], cache["steps"] = next(counts_of)
                caches.append(cache)
            return caches

        def chunk_caches(staged, table=()):
            """What each layer is handed in a prefill chunk (`table`: the
            row's block table behind a pooled model's pools, in a
            1-tuple)."""
            dense, states = iter(staged["kv"]), iter(staged["state"])
            return [(tuple(next(dense)) + table if attends else ())
                    + (tuple(next(states)) if scans else ())
                    for attends, scans, _ in kinds]

        # for a caller that applies the model itself (the benchmark's
        # parity check reads logits where the engine's step returns ids)
        self._by_kind, self._decode_caches = by_kind, decode_caches
        self._chunk_caches = chunk_caches

        def decode_step(params, k_pages, v_pages, state, active,
                        block_tables, lengths, tokens, rng, temperature,
                        top_k, top_p, counters=()):
            caches = decode_caches(k_pages, v_pages, state, counters,
                                   active, block_tables, lengths)
            logits, new = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None],
                kv_caches=caches, cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            return (out.astype(jnp.int32),) + by_kind(new)

        # `counters` is () for a model without any: no argument, no result
        self._decode = jax.jit(decode_step, donate_argnums=(1, 2, 3, 12))

        def chunk_prefill(params, tokens, positions, staged, offset, valid,
                          last=None):
            """One prefill chunk of one row. `staged`: {"kv": dense
            (k, v) per layer that attends, "state": what `state_shapes()`
            names per layer that scans}. Attention overwrites or masks the
            padded tail; a layer that scans (or counts) is told `valid`,
            the count of real tokens, and keeps the rest out of what it
            hands on. `last` and the logits returned: as the dense
            `chunk_prefill`'s (`chunk_logits`)."""
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=chunk_caches(staged), cache_index=offset,
                valid=valid, head=False)
            nk, nv, nstate, _ = by_kind(new)
            return chunk_logits(model, params, hidden, last), {
                "kv": list(zip(nk, nv)), "state": nstate}

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

        staged_rows = self._staged_rows

        def _staging_zero():
            shape = (1, cfg.num_kv_heads, staged_rows, cfg.head_dim_)
            return {"kv": [(jnp.zeros(shape, cfg.dtype),
                            jnp.zeros(shape, cfg.dtype))
                           for attends, _, _ in kinds if attends],
                    "state": cfg.init_state(1)}

        self._dense_zero_caches = jax.jit(_staging_zero)

        def write_state(state, staged, slot):
            """A finished prefill's state into row `slot` of every pool
            (the slot's earlier occupant is overwritten whole)."""
            return [tuple(jax.lax.dynamic_update_slice_in_dim(
                pool, new.astype(pool.dtype), slot, axis=0)
                for pool, new in zip(pools, news))
                for pools, news in zip(state, staged)]

        self._write_state = jax.jit(write_state, donate_argnums=(0,))

    def _chunk_args(self, seq: _Seq, chunk: int, take: int):
        # a scan layer must be told where the bucket's padding starts
        return seq.dense_caches, (jnp.asarray(take, jnp.int32),)

    def _commit_prompt(self, index: int, seq: _Seq):
        staged = seq.dense_caches
        self._state_due.append((index, staged["state"]))
        write_ids = seq.pages[seq.own_from:]
        if write_ids:
            self._write_owned_pages(staged["kv"], write_ids, seq.own_from)

    def _after_prefill(self, phase):
        """`write_state` for every prefill that finished this visit (the
        tick's `state` phase, in the visits that have one)."""
        if not self._state_due:
            return
        with phase("state"), self._mesh_scope():
            for slot, staged in self._state_due:
                self._dispatching()
                self.state = self._write_state(
                    self.state, staged, jnp.asarray(slot, jnp.int32))
                self._dispatched(self.state[0][0])
                self._state_installs += 1
        self._state_due.clear()

    def state_copies(self, compiled_text: str) -> int:
        """Whole-pool copies (`paged.pool_copies`) at the shape of the
        largest pool a scanning layer keeps: the scan state where a layer
        keeps `(conv, ssm)`, the convolution's window where it keeps that
        alone. The decode step must hold none: it updates the donated pool
        in place, one read and one write. A window beside a scan state is
        not counted: a few MB a layer, shifted whole every tick, which the
        TPU compiler stages through fast memory."""
        largest = max(self.state[0], key=lambda pool: pool.size)
        return pool_copies(compiled_text, largest.shape)

    def stats(self):
        return dict(super().stats(), state_installs=self._state_installs,
                    prefix_skipped_recurrent=self._prefix_skipped)

    def _step_args(self, live, rows):
        return (self.params, self.k_pages, self.v_pages, self.state, *live,
                *rows, self.counters)

    def _step_taken(self, out):
        (self._tokens, self.k_pages, self.v_pages, self.state,
         self.counters) = out


class PooledEngine(RecurrentEngine):
    """A recurrent model that lays its own K and V pools (LFM2) and says in
    what shape (`page_pool(pages, page_size)`: heads narrower than a lane
    tile stand side by side in a row, `ops.paged_attention`). Its prefill
    chunks take the page pools in their staging pytree's "kv" and the row's
    block table, and write and attend the row's pages where they lie: of a
    prefilling row only the scanning layers' state is staged."""

    kind = "pooled"

    def _refuse(self):
        super()._refuse()
        ps, buckets = self.config.page_size, self.config.prefill_buckets
        if buckets[-1] % ps or any(b % ps and ps % b for b in buckets):
            raise ValueError(
                f"prefill buckets {buckets} are not each whole pages "
                f"of {ps} or a part of one")

    def _pool_shape(self) -> Tuple[int, ...]:
        return self.config.model.page_pool(self.config.num_pages,
                                           self.config.page_size)

    def _kernel(self, reference: bool) -> str:
        return paged_kernel(self.config.model.head_dim_, reference,
                            self._pool_shape()[-1])

    def _kind_programs(self):
        super()._kind_programs()
        model, init_state = self.model, self.config.model.init_state
        by_kind, chunk_caches = self._by_kind, self._chunk_caches

        def chunk_prefill(params, tokens, positions, staged, offset, table,
                          valid, last=None):
            """The chunk of a pooled model: `staged["kv"]` holds the (k, v)
            page POOLS per layer that attends, `table` [pages_per_seq] the
            row's page ids (the null page where it holds none). The chunk's
            first `valid` K/V rows are written into the row's pages and
            attended there with everything cached before them; the rest as
            the recurrent chunk's."""
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=chunk_caches(staged, (table,)), cache_index=offset,
                valid=valid, head=False)
            nk, nv, nstate, _ = by_kind(new)
            return chunk_logits(model, params, hidden, last), {
                "kv": list(zip(nk, nv)), "state": nstate}

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

        def _staging_zero():
            # the pools stand in for "kv" when a chunk is dispatched
            return {"kv": [], "state": init_state(1)}

        self._dense_zero_caches = jax.jit(_staging_zero)

    def _init_cache(self):
        super()._init_cache()
        # prefill chunks (each wrote the row's pages)
        self._prefill_chunks_in_place = 0

    def _chunk_args(self, seq: _Seq, chunk: int, take: int):
        staged = dict(seq.dense_caches,
                      kv=list(zip(self.k_pages, self.v_pages)))
        return staged, (self._row_table(seq), jnp.asarray(take, jnp.int32))

    def _chunk_done(self, seq: _Seq, staged, chunk: int, take: int):
        self.k_pages = [k for k, _ in staged["kv"]]
        self.v_pages = [v for _, v in staged["kv"]]
        seq.dense_caches = dict(staged, kv=[])
        self._prefill_chunks_in_place += 1
        self._prefill_ctx_rows += seq.prefill_off + take

    def _commit_prompt(self, index: int, seq: _Seq):
        self._state_due.append((index, seq.dense_caches["state"]))

    def _kind_counts(self):
        # chunks that wrote their K/V into the row's pages themselves, the
        # prompt tokens they computed and the cached rows they attended
        return {"prefill_chunks_in_place": self._prefill_chunks_in_place,
                "prefill_computed_tokens": self._prefill_computed_tokens,
                "prefill_ctx_rows": self._prefill_ctx_rows}


class PagesInPlace(DenseEngine):
    """What the kinds share whose prefill chunks take the row's pools donated
    and its block table in place of a dense cache, and write the row's pages
    themselves: nothing of a row is staged, and a finished prompt leaves
    nothing to write. `_dense_zero_caches`, `_write_pages` and
    `_gather_pages` stay what the dense engine builds and are never called."""

    def _stage_prefill_cache(self, seq: _Seq):
        pass

    def _commit_prompt(self, index: int, seq: _Seq):
        pass


class WindowedEngine(NoPrefix, PagesInPlace):
    """Rows that do not keep the K/V of their whole context (EvaByte):
    summaries of closed windows beside the open one, so that pages leave a
    row while it lives. The configuration says what a row keeps instead: the
    rows of its pages a row of n positions holds and its next token attends
    (`cache_rows(n)`, which its own decode path applies to `lengths`), the
    pages that makes (`pages_held(n, page_size)`), the most a row holds on
    its way to n (`prefill_pages`: the admission budget, and at the longest
    row the block table's width), the rows of each kind a step attends
    (`attended_rows(lengths)`), whether a position closes a window
    (`window_closes(n)`: the engine then runs `compress_window_pages` on the
    row's open window and takes back the pages it emptied), and which page
    sizes and buckets it can live with (`check_pages`).

    The decode step is the dense one (the model turns `lengths` into rows of
    the table itself). A prefill chunk takes the page pools donated and the
    row's block table in place of a dense cache: nothing of a row is staged
    densely. A page of this model is no prefix's K/V once its window has
    closed, so the radix holds no prefix of it."""

    kind = "windowed"
    _not_shipped = (
        "{what} ships the K/V of a whole prompt: a model whose rows keep "
        "summaries of closed windows in their pages cannot be prefilled on "
        "another engine yet")
    _no_mesh = ("compressed windows over a tensor mesh are not built: the "
                "chunk over pages and the compression are not mapped over "
                "the heads")

    @staticmethod
    def _pages_per_seq(config) -> int:
        # a padded last chunk may run a bucket past max_len
        return config.model.prefill_pages(
            config.max_len + config.prefill_buckets[-1], config.page_size)

    def _refuse(self):
        super()._refuse()
        self.config.model.check_pages(self.config.page_size,
                                      self.config.prefill_buckets)

    def _init_cache(self):
        super()._init_cache()
        # windows compressed (by the phase the row was in), the pages that
        # gave back, and the rows of each kind the decode steps attended
        self._window_closes = {"prefill": 0, "decode": 0}
        self._pages_released = 0
        self._summary_rows = 0
        self._window_rows = 0

    def _kind_programs(self):
        model = self.model

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          last=None):
            """One prefill chunk of one row over its pages. `pools`: (k
            pools, v pools), a pair a layer; `table` [pages_per_seq] the
            row's page ids, the null page where it holds none. The
            chunk's K/V rows are written into the row's pages and
            attended there with what the row already keeps. `last` and
            the logits returned: as the dense `chunk_prefill`'s."""
            k_pages, v_pages = pools
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"k": k, "v": v, "table": table}
                           for k, v in zip(k_pages, v_pages)],
                cache_index=offset, head=False)
            return chunk_logits(model, params, hidden, last), (
                [c["k"] for c in new], [c["v"] for c in new])

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

        def compress_window(params, k_pages, v_pages, pages):
            """`pages` [window / page_size]: the page ids of one row's
            full window, in order. Its summaries replace the first of
            them in every layer's pools."""
            return self.config.model.compress_window_pages(
                params, k_pages, v_pages, pages)

        self._compress_window = jax.jit(compress_window,
                                        donate_argnums=(1, 2))

    def lower_compress(self):
        """`compress_window` lowered at this engine's shapes."""
        cfg = self.config
        like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        return self._compress_window.lower(
            *jax.tree_util.tree_map(
                like, (self.params, self.k_pages, self.v_pages)),
            jax.ShapeDtypeStruct(
                (cfg.model.window_size // cfg.page_size,), jnp.int32))

    def _pages_to_admit(self, prompt: List[int], tail_pages: int):
        # the budget is the most the row holds on its way through the
        # prompt; the chunks take their pages as they come to them
        # (`_chunk_pages`), so a window's are back before the next's
        if self.pool.num_free() < self.config.model.prefill_pages(
                len(prompt), self.config.page_size):
            return None
        return 0

    def _chunk_pages(self, index: int, seq: _Seq) -> bool:
        """The pages `seq`'s next chunk writes its real tokens into.
        Admission found the row's budget free, but the rows beside it have
        grown since: where the pool is short now, the row goes back to the
        front of the queue with what it had (False) and is admitted again
        when its budget is free."""
        cfg = self.config
        _, take = self._chunk_size(seq)
        # rows, not positions: a chunk that fills its window is written
        # whole before the window is compressed
        need = -(-(cfg.model.cache_rows(seq.prefill_off) + take)
                 // cfg.page_size)
        while len(seq.pages) < need:
            page = self._alloc_page()
            if page is None:
                self._preempt(index, reason="page_pressure")
                return False
            seq.pages.append(page)
        return True

    def _chunk_args(self, seq: _Seq, chunk: int, take: int):
        # chunks start at multiples of the largest bucket, which
        # divides the window: none straddles a close
        window, off = self.config.model.window_size, seq.prefill_off
        assert off // window == (off + chunk - 1) // window, (off, chunk)
        return (self.k_pages, self.v_pages), (self._row_table(seq),)

    def _chunk_done(self, seq: _Seq, staged, chunk: int, take: int):
        self.k_pages, self.v_pages = staged
        if self.config.model.window_closes(seq.prefill_off + take):
            self._close_window(seq, "prefill")

    def _lands_at(self, length: int) -> int:
        return self.config.model.cache_rows(length)

    def _account_decode(self, stage, active: List[int]):
        # the rows of each kind the step attends, from lengths alone
        summary, window = self.config.model.attended_rows(
            stage.lengths[stage.index])
        self._summary_rows += int(summary.sum())  # host-sync ok: numpy
        self._window_rows += int(window.sum())  # host-sync ok: numpy

    def _after_dispatch(self, stage, active: List[int], phase):
        closes = self.config.model.window_closes
        with phase("compress"):
            for i in active:
                if closes(self.seqs[i].length):
                    self._close_window(self.seqs[i], "decode")
                    stage.stale(i)

    def _close_window(self, seq: _Seq, where: str):
        """`seq`'s open window is full: `compress_window` turns its pages
        into summaries in every layer, in place in the first of them, and
        the rest go back to the pool. Dispatched in stream order behind
        the step (or chunk) that wrote the window's last position; the
        host knows from the row's length alone that it is due, so nothing
        is read and the step ahead stays ahead. A page released here is
        written by its next owner only in a program dispatched later."""
        cfg = self.config
        model = cfg.model
        full = model.window_size // cfg.page_size
        kept = model.window_summaries // cfg.page_size
        base = len(seq.pages) - full
        assert base >= 0 and base % kept == 0, (base, len(seq.pages))
        window = np.zeros((full,), np.int32)
        window[:] = seq.pages[base:]
        with self._mesh_scope():
            self._dispatching()
            self.k_pages, self.v_pages = self._compress_window(
                self.params, self.k_pages, self.v_pages, window)
            self._dispatched(self.k_pages[0])
        for page in seq.pages[base + kept:]:
            self.pool.decref(page)
        del seq.pages[base + kept:]
        self._window_closes[where] += 1
        self._pages_released += full - kept

    def _kind_counts(self):
        return {"window_closes_prefill": self._window_closes["prefill"],
                "window_closes_decode": self._window_closes["decode"],
                "pages_released": self._pages_released,
                "summary_rows": self._summary_rows,
                "window_rows": self._window_rows,
                "prefix_skipped_compressed": self._prefix_skipped}


class InPlaceEngine(PagesInPlace):
    """What the latent and the indexed kind share: prefill chunks that take
    the row's pools as ONE argument (`_row_pools`) and are told how many of
    their tokens are real; a decode step that takes `_row_pools` and the
    expert counters (`init_counters()`) donated; and a radix-shared prefix
    that is mapped in place (a radix node stays a page id; nothing is
    copied)."""

    _takes_live = True

    def _init_cache(self):
        super()._init_cache()
        self.counters = _counters_of(self.config.model)
        # the pages the decoding rows held a step, each once
        # (`_account_decode`)
        self._page_seen = np.zeros((self.config.num_pages,), bool)
        self._pages_distinct = 0

    def _chunk_args(self, seq: _Seq, chunk: int, take: int):
        return self._row_pools, (self._row_table(seq),
                                 jnp.asarray(take, jnp.int32))

    def _chunk_done(self, seq: _Seq, staged, chunk: int, take: int):
        self._row_pools = staged
        self._prefill_ctx_rows += seq.prefill_off + take

    def _step_args(self, live, rows):
        return (self.params, self._row_pools, *live, *rows, self.counters)

    def _step_taken(self, out):
        self._tokens, self._row_pools, self.counters = out

    def _rows_and_pages(self, stage, active: List[int]):
        """(The cached rows the step attends or scores, its own token's
        among them; the pages they lie in counted a row; the rows'
        lengths); and counts the pages those rows hold, each once."""
        index = stage.index
        lengths = stage.lengths[index]
        rows = len(active) + int(lengths.sum())  # host-sync ok: numpy
        pages = int(stage.held[index].sum())  # host-sync ok: numpy
        seen = self._page_seen
        seen[stage.tables[index].ravel()] = True
        seen[0] = False
        self._pages_distinct += np.count_nonzero(seen)
        seen[:] = False
        return rows, pages, lengths

    def _kind_counts(self):
        # what the radix gave and what the chunks computed and attended
        return {"prefix_shared_tokens": self._prefix_shared_tokens,
                "prefill_computed_tokens": self._prefill_computed_tokens,
                "prefill_ctx_rows": self._prefill_ctx_rows,
                "radix_evictions": self._radix_evictions,
                "radix_evict_walks": self.radix.walks}


class LatentEngine(InPlaceEngine):
    """Layers that cache ONE latent row a token, key and value at once
    (multi-head latent attention: Sarvam, Xing). The configuration says how
    wide (`latent_cache()`: lanes of a row in the pool, lanes of it that
    are the value): a layer keeps one pool `[1, pages, page_size, lanes]`
    in `k_pages`, and `v_pages` holds nothing."""

    kind = "latent"
    _kernel_key = "latent_kernel"
    _no_mesh = ("latent attention over a tensor mesh is not built: the "
                "heads would be split and the latent pool replicated")
    _not_shipped = (
        "{what} ships dense K/V: a model whose layers cache latent rows in "
        "their pages cannot be prefilled on another engine yet")

    def _kernel(self, reference: bool) -> str:
        return latent_kernel(self.config.model.latent_cache()[1], reference)

    def _init_pools(self):
        # a latent row is key and value at once: no second pool
        self.k_pages = self._zero_pools(self._pool_shape())
        self.v_pages = []

    def _init_cache(self):
        super()._init_cache()
        # cached rows the decode steps attended; pages the decoding rows
        # held a step, counted a row and as the kernel's schedule has it
        # copy them (a group's shared span once: between a row and once)
        self._latent_rows_attended = 0
        self._latent_pages_rowwise = 0
        self._latent_pages_copied = 0

    def _kind_programs(self):
        model = self.model
        kinds = layer_caches(self.config.model)

        def decode_caches(pools, counters, active, block_tables, lengths):
            """What each layer is handed in a paged decode step: the rows
            that attend a shared document together are found ONCE, for
            every layer's kernel."""
            with jax.named_scope("mla/attend"):
                schedule = share_schedule(block_tables, lengths,
                                          self.config.page_size)
            counts_of = iter(counters)
            caches = []
            for pool, (_, _, counts) in zip(pools, kinds):
                cache = {"pool": pool, "active": active,
                         "block_tables": block_tables, "lengths": lengths,
                         "schedule": schedule}
                if counts:
                    cache["pairs"], cache["steps"] = next(counts_of)
                caches.append(cache)
            return caches

        def by_kind(new):
            """A model's per-layer tuples (pool, counters...) as the
            pools and the counters of the layers that count."""
            return ([kept[0] for kept in new],
                    [tuple(kept[1:]) for kept in new if len(kept) > 1])

        def decode_step(params, pools, active, block_tables, lengths,
                        tokens, rng, temperature, top_k, top_p,
                        counters=()):
            logits, new = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None],
                kv_caches=decode_caches(pools, counters, active,
                                        block_tables, lengths),
                cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            return (out.astype(jnp.int32),) + by_kind(new)

        self._decode = jax.jit(decode_step, donate_argnums=(1, 10))

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          valid, last=None):
            """One prefill chunk of one row over its pages. `pools`: the
            latent pool of every layer; `table` [pages_per_seq] the row's
            page ids, shared prefix pages first, the null page where it
            holds none. The chunk's first `valid` rows are written into
            the row's pages and attended there with everything cached
            before them, a block of pages at a time. `last` and the logits
            returned: as the dense `chunk_prefill`'s."""
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"pool": pool, "table": table} for pool in pools],
                cache_index=offset, valid=valid, head=False)
            return chunk_logits(model, params, hidden, last), by_kind(new)[0]

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

    def _account_decode(self, stage, active: List[int]):
        rows, pages, _ = self._rows_and_pages(stage, active)
        self._latent_rows_attended += rows
        self._latent_pages_rowwise += pages
        self._poll()
        # what the kernel does not copy of them: a group's shared span for
        # every member but one, by the schedule the device makes of the
        # same arrays
        self._latent_pages_copied += pages - int(  # host-sync ok: numpy
            pages_spared(share_schedule(
                stage.tables, stage.lengths, self.config.page_size)))
        self._poll()

    def _kind_counts(self):
        return {"latent_rows_attended": self._latent_rows_attended,
                "latent_pages_rowwise": self._latent_pages_rowwise,
                "latent_pages_distinct": self._pages_distinct,
                "latent_pages_copied": self._latent_pages_copied,
                **super()._kind_counts()}


class IndexedEngine(InPlaceEngine):
    """Layers that SELECT what they attend by an indexer's scores (learned
    sparse attention: Keye). The configuration says how wide an index key
    stands in its pool (`index_cache()`: lanes) and how many tokens a query
    selects (`index_topk`): a layer keeps an index-key pool `[1, pages,
    page_size, lanes]` beside its K and V pools, which stand token-major
    (`[1, pages, page_size, kv_heads * head_dim]`: a selected token's K is
    one row), all three addressed by the same page ids and block table; a
    shared prefix is scored and attended where it lies."""

    kind = "indexed"
    _kernel_key = "sparse_kernel"
    _no_mesh = ("sparse attention over a tensor mesh is not built: the "
                "selection is a row's, and the token-major pools are not "
                "split over the kv heads")
    _not_shipped = (
        "{what} ships dense K/V: a model whose layers keep index keys "
        "beside them cannot be prefilled on another engine yet")

    @property
    def _row_pools(self):
        return (self.k_pages, self.v_pages, self.index_pages)

    @_row_pools.setter
    def _row_pools(self, pools):
        self.k_pages, self.v_pages, self.index_pages = pools

    def _pool_shape(self) -> Tuple[int, ...]:
        # the selected tokens' gather wants a token's kv heads in one row
        cfg, config = self.config.model, self.config
        return (1, config.num_pages, config.page_size,
                cfg.num_kv_heads * cfg.head_dim_)

    def _kernel(self, reference: bool) -> str:
        return sparse_kernel(reference, self.config.page_size,
                             self.config.model.index_cache())

    def _init_pools(self):
        super()._init_pools()
        cfg, config = self.config.model, self.config
        self.index_pages = [
            jnp.zeros((1, config.num_pages, config.page_size,
                       cfg.index_cache()), cfg.dtype)
            for _ in self.k_pages]

    def _init_cache(self):
        super()._init_cache()
        # cached index keys the decode steps scored (a row a step, whatever
        # the layers) and the pages of them counted a row; tokens the steps
        # selected, sum of min(context, topk), and the contexts they
        # selected from
        self._index_rows_scanned = 0
        self._index_pages_rowwise = 0
        self._sparse_rows_selected = 0
        self._sparse_rows_context = 0

    def _kind_programs(self):
        model = self.model

        def by_kind(new):
            """A model's per-layer tuples (k, v, index, counters...) as
            the three lists of pools and the counters."""
            return (tuple([kept[i] for kept in new] for i in range(3)),
                    [tuple(kept[3:]) for kept in new if len(kept) > 3])

        def decode_step(params, pools, active, block_tables, lengths,
                        tokens, rng, temperature, top_k, top_p,
                        counters=()):
            caches = [
                {"k": k, "v": v, "index": index, "active": active,
                 "block_tables": block_tables, "lengths": lengths,
                 "pairs": pairs, "steps": steps}
                for k, v, index, (pairs, steps) in zip(*pools, counters)]
            logits, new = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None], kv_caches=caches,
                cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            return (out.astype(jnp.int32),) + by_kind(new)

        self._decode = jax.jit(decode_step, donate_argnums=(1, 10))

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          valid, last=None):
            """One prefill chunk of one row over its pages. `pools`: (k,
            v, index) pools, a list a kind; `table` [pages_per_seq] the
            row's page ids, shared prefix pages first, the null page where
            it holds none. The chunk's first `valid` rows are written into
            the row's pages, scored against everything cached before them
            and attended under each query's threshold. `last` and the
            logits returned: as the dense `chunk_prefill`'s."""
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"k": k, "v": v, "index": index, "table": table}
                           for k, v, index in zip(*pools)],
                cache_index=offset, valid=valid, head=False)
            return chunk_logits(model, params, hidden, last), by_kind(new)[0]

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

    def _account_decode(self, stage, active: List[int]):
        rows, pages, lengths = self._rows_and_pages(stage, active)
        self._index_rows_scanned += rows
        self._index_pages_rowwise += pages
        self._sparse_rows_context += rows
        self._sparse_rows_selected += int(  # host-sync ok: numpy
            np.minimum(lengths + 1, self.config.model.index_topk).sum())
        self._poll()

    def _kind_counts(self):
        return {"index_rows_scanned": self._index_rows_scanned,
                "index_pages_rowwise": self._index_pages_rowwise,
                "index_pages_distinct": self._pages_distinct,
                "sparse_rows_selected": self._sparse_rows_selected,
                "sparse_rows_context": self._sparse_rows_context,
                **super()._kind_counts()}


class BlockwiseEngine(PagesInPlace):
    """A model that GENERATES BY DIFFUSION OVER BLOCKS (SDAR). The
    configuration says how long a block is and which id is the mask
    (`block_length`, `mask_token_id`), its own denoising rule
    (`denoising_steps`, `remasking`, `confidence_threshold`), the parameters
    a position's forward multiplies by (`active_params()`) and its expert
    counters (`init_counters()`). A row's step is one forward of the
    `block_length` positions of its open block, attended both ways, and
    yields 0 .. block_length tokens; a block whose last mask is gone is
    committed by one more forward, and only then does the row's cached
    length move (`_block_tick`); the prompt's whole blocks are prefilled in
    place under the same mask and its last `len % block_length` tokens open
    the first block.

    ONE block step serves a denoising forward and a commit alike: every
    live row's open block (its ids held on the device between steps, in the
    report the step before returned) is forwarded at positions `lengths` ..
    `lengths` + block_length - 1, its K/V rows written into the row's pages
    there (over what the forward before wrote: a denoising forward's K/V
    are not kept, and the forward of a block without a mask is its commit),
    attended with block_length queries a row over `lengths` + block_length
    positions, and the rule applied on the device to the logits of all
    rows' block positions. A prefill chunk takes the pools donated and the
    row's block table, writes and attends the row's pages under the block
    mask and returns no logits: nothing is sampled from a prompt. A shared
    prefix is attended where it lies (whole pages are whole blocks, and a
    block's K/V depend on nothing behind it)."""

    kind = "blockwise"
    _takes_live = True
    _samples_prompt = False
    _not_shipped = (
        "{what} ships dense K/V and the logits a first token is sampled "
        "from: a model that generates by diffusion over blocks prefills its "
        "pages in place and samples nothing from a prompt, and cannot be "
        "prefilled on another engine yet")
    _no_mesh = ("generation by diffusion over blocks over a tensor mesh is "
                "not built: the block step's pools and counters are not "
                "sharded")

    def _refuse(self):
        super()._refuse()
        config, L = self.config, self.config.model.block_length
        if config.page_size % L or config.max_len % L \
                or any(b % L for b in config.prefill_buckets):
            raise ValueError(
                f"page_size {config.page_size}, max_len "
                f"{config.max_len} and the prefill buckets "
                f"{config.prefill_buckets} are not each whole blocks of "
                f"{L} positions")

    def _init_cache(self):
        super()._init_cache()
        cfg = self.config.model
        self.counters = cfg.init_counters()
        # the same accumulators of the prefill chunks of the largest bucket
        # (tokens routed to each held expert, chunks that routed it any),
        # donated to the chunk beside the pools; `_chunks_counted`: how
        # many such chunks were dispatched
        self.chunk_counters = cfg.init_counters()
        self._chunks_counted = 0
        # what the block steps did: row-forwards dispatched, those of them
        # that were commits, tokens handed out, blocks the dynamic rule
        # finished ahead of the static count
        self._block_forwards = 0
        self._commit_forwards = 0
        self._block_tokens_out = 0
        self._blocks_early = 0
        self._block_metered = (0, 0)   # of the first two, in the metrics
        # the parameters a position's forward multiplies by (the step is
        # timed by them)
        self._active_params = cfg.active_params()

    def _report_shape(self) -> Tuple[int, ...]:
        # `_tokens` is the report of the last block step, [rows,
        # block_length + 2]: every row's block ids as the step left them
        # (the next step's input, as it stands), the masks the step found
        # in the block and the masks it left
        return (self.config.max_batch, self.config.model.block_length + 2)

    def _kind_programs(self):
        model, cfg = self.model, self.config.model
        L, mask_id = cfg.block_length, cfg.mask_token_id

        def block_caches(k_pages, v_pages, counters, live, block_tables,
                         lengths):
            """What each layer is handed in a block step."""
            return [{"k": k, "v": v, "active": live,
                     "block_tables": block_tables, "lengths": lengths,
                     "pairs": pairs, "steps": steps}
                    for k, v, (pairs, steps)
                    in zip(k_pages, v_pages, counters)]

        def by_kind(new):
            """A model's per-layer tuples (k, v, counters...) as the two
            lists of pools and the counters."""
            return ([kept[0] for kept in new], [kept[1] for kept in new],
                    [tuple(kept[2:]) for kept in new if len(kept) > 2])

        # for a caller that applies the model itself (the benchmark's
        # parity check reads logits where the engine's step returns ids)
        self._by_kind, self._block_caches = by_kind, block_caches

        def decode_step(params, k_pages, v_pages, live, block_tables,
                       lengths, report, opened, fresh, count, threshold,
                       rng, temperature, top_k, top_p, counters):
            """`report` [rows, block_length + 2]: what the step before
            returned (this step's returns it anew). `opened` [rows]: the
            row opens a block with the ids `fresh` [rows, block_length];
            `count`, `threshold` [rows]: `sampling.unmask_block`'s; the
            sampler's [rows] parameters hold for every position of a row."""
            ids = jnp.where(opened[:, None], fresh, report[:, :L])
            hidden, new = model.apply(
                {"params": params}, ids,
                positions=lengths[:, None] + jnp.arange(L),
                kv_caches=block_caches(k_pages, v_pages, counters, live,
                                       block_tables, lengths),
                cache_index=None, head=False)
            logits = chunk_logits(
                model, params, hidden.reshape(1, -1, hidden.shape[-1]),
                None)[0]                              # [rows * L, vocab]
            each = lambda a: jnp.repeat(a, L)         # noqa: E731
            with jax.named_scope("sdar/confidence"):
                # the mask's own id is never a candidate (a position that
                # took it would read as masked for ever)
                logits = jnp.where(
                    jnp.arange(logits.shape[-1]) == mask_id, -1e30, logits)
                candidates, confidence = sample_with_confidence(
                    rng, logits, each(temperature), each(top_k),
                    each(top_p))
            with jax.named_scope("sdar/unmask"):
                out, before, after = unmask_block(
                    ids, candidates.reshape(ids.shape),
                    confidence.reshape(ids.shape), mask_id, count,
                    threshold)
                out = jnp.where(live[:, None], out, ids)
                report = jnp.concatenate(
                    [out, before[:, None], after[:, None]], axis=1)
            return (report.astype(jnp.int32),) + by_kind(new)

        self._decode = jax.jit(decode_step, donate_argnums=(1, 2, 15))

        largest = self.config.prefill_buckets[-1]

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          valid):
            """One prefill chunk of one row over its pages. `pools`: (k
            pools, v pools, the chunks' expert counters); `table`
            [pages_per_seq] the row's page ids, shared prefix pages first,
            the null page where it holds none. The chunk's first `valid`
            rows (whole blocks) are written into the row's pages and
            attended there, with everything cached before them, under the
            block mask. A chunk of the largest bucket adds what it routed
            to the counters; a smaller one hands them on. Returns (a
            witness of the chunk's end, [1] float32: no logits, the head is
            not run; the pools)."""
            k_pages, v_pages, counters = pools
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[(k, v, table) for k, v in zip(k_pages, v_pages)],
                cache_index=offset, valid=valid, head=False)
            nk, nv, routed = by_kind(new)
            if tokens.shape[1] == largest:
                counters = [(pairs + got, steps + (got > 0).astype(jnp.int32))
                            for (pairs, steps), (got,)
                            in zip(counters, routed)]
            return hidden[:, -1, 0].astype(jnp.float32), (nk, nv, counters)

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

    # -- the scheduler's hooks ---------------------------------------------

    def _check_request(self, request: GenerationRequest):
        self._block_settings(request)    # raises on a rule it has not
        if self.config.model.mask_token_id in request.prompt_tokens:
            raise ValueError(
                "the prompt holds the mask's id: a position that stands "
                "for a token not yet generated")

    def _begin_prefill(self, index: int, request: GenerationRequest) -> bool:
        if not super()._begin_prefill(index, request):
            return False
        # the prompt's whole blocks are prefilled; the rest of it opens
        # the first block as fixed ids. After a preemption `prompt`
        # ends at a block's boundary (tokens are handed out by the
        # block, and the first block closes the prompt's last), so its
        # re-prefill under the block mask gives the K/V the commits
        # gave, and nothing is left over
        seq = self.seqs[index]
        prompt = seq.prompt
        whole = len(prompt) - len(prompt) % self.config.model.block_length
        seq.prompt, seq.block_tail = prompt[:whole], prompt[whole:]
        assert not (seq.resume and seq.block_tail), "resumed inside a block"
        return True

    def _chunk_args(self, seq: _Seq, chunk: int, take: int):
        return (self.k_pages, self.v_pages, self.chunk_counters), (
            self._row_table(seq), jnp.asarray(take, jnp.int32))

    def _chunk_done(self, seq: _Seq, staged, chunk: int, take: int):
        self.k_pages, self.v_pages, self.chunk_counters = staged
        self._prefill_ctx_rows += seq.prefill_off + take
        self._chunks_counted += chunk == self.config.prefill_buckets[-1]

    def _enter_decode(self, index: int, seq: _Seq):
        # nothing is sampled from a prompt: the row's first block opens
        # with the next block step
        seq.phase = "decode"
        seq.length = len(seq.prompt)
        seq.generated = []
        seq.dispatched = 0
        seq.block_at = -1
        seq.block_rule = self._block_settings(seq.request)

    def _exhausted(self, seq: _Seq) -> bool:
        """The last denoising forward of the row's last block is
        dispatched, as the static rule counts (that block's commit would
        be attended by nothing, and is not run)."""
        return seq.block_at >= 0 and not seq.block_masks and seq.block_last

    def _lands_at(self, length: int) -> int:
        # the last position of the block a row has open or opens next
        return length + self.config.model.block_length - 1

    def _row_shapes(self, vec):
        L = self.config.model.block_length
        return (vec(jnp.int32, self.config.pages_per_seq), vec(jnp.int32),
                vec(jnp.int32, L + 2), vec(jnp.bool_), vec(jnp.int32, L),
                vec(jnp.int32), vec(jnp.float32),
                jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype),
                vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))

    def _step_args(self, live, rows):
        return (self.params, self.k_pages, self.v_pages, *live, *rows,
                self.counters)

    def _step_taken(self, out):
        self._tokens, self.k_pages, self.v_pages, self.counters = out

    def _set_gauges(self):
        super()._set_gauges()
        llm_metrics().masks_in_flight.set(self._masks_in_flight(),
                                          tags=_GAUGE_TAGS)

    def _kind_counts(self):
        # row-forwards the block steps dispatched (`decode_rows` counts
        # the same), those that were commits, tokens handed out, blocks
        # the dynamic rule finished ahead of the static count; chunks
        # wrote the rows' pages themselves
        return {"block_forwards": self._block_forwards,
                "commit_forwards": self._commit_forwards,
                "block_tokens_out": self._block_tokens_out,
                "blocks_early": self._blocks_early,
                "prefix_shared_tokens": self._prefix_shared_tokens,
                "prefill_computed_tokens": self._prefill_computed_tokens,
                "prefill_ctx_rows": self._prefill_ctx_rows,
                # chunks of the largest bucket: those `chunk_expert_*` count
                "prefill_chunks_largest": self._chunks_counted}

    # -- the block tick ----------------------------------------------------

    def _block_settings(self, request: GenerationRequest):
        """(denoising forwards a block, the dynamic rule's threshold or a
        number no probability passes under the static rule) of a request,
        the model configuration's where it does not say."""
        model = self.config.model
        steps = getattr(request, "denoising_steps", None) \
            or model.denoising_steps
        rule = getattr(request, "remasking", None) or model.remasking
        if rule not in ("static", "dynamic"):
            raise ValueError(f"remasking rule {rule!r} is neither 'static' "
                             f"nor 'dynamic'")
        threshold = getattr(request, "confidence_threshold", None)
        if threshold is None:
            threshold = model.confidence_threshold
        return int(steps), (float(threshold) if rule == "dynamic" else 2.0)

    def _masks_in_flight(self) -> int:
        """Masks the open blocks hold, as the static rule counts them."""
        return sum(s.block_masks for s in self.seqs
                   if s.request is not None and s.block_at >= 0)

    def _plan_forward(self, seq: _Seq):
        """What the next block step does for `seq`, from what the host
        knows without reading anything: (the ids that open a block or
        None, the positions the static rule fixes in this forward; 0 for a
        commit). The static rule's yield is known here; the dynamic rule's
        is at least that, and `_emit_blocks` learns the rest a visit late."""
        model = self.config.model
        L = model.block_length
        fresh = None
        if seq.block_at < 0:
            fixed = seq.block_tail
            seq.block_tail = []
            seq.block_at, seq.block_fixed = seq.length, len(fixed)
            seq.block_masks, seq.block_step = L - len(fixed), 0
            budget = seq.request.max_new_tokens - len(seq.resume) \
                - seq.dispatched
            seq.block_take = min(seq.block_masks, budget)
            seq.block_last = seq.block_take >= budget \
                or seq.block_at + 2 * L > self.config.max_len
            seq.block_counted = False
            seq.block_opened_ts = time.monotonic()
            fresh = fixed + [model.mask_token_id] * seq.block_masks
            if not seq.blocks_done:
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="open", at=seq.block_at,
                                fixed=len(fixed))
        commit = not seq.block_masks
        count = 0
        if commit:
            if seq.length == len(seq.prompt):
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="commit", at=seq.block_at)
            seq.length += L
            self._commit_forwards += 1
        else:
            count = min(unmask_count(L, seq.block_rule[0], seq.block_step),
                        seq.block_masks)
            seq.block_masks -= count
            seq.block_step += 1
            if not seq.block_masks:
                seq.dispatched += seq.block_take
                seq.block_counted = True
        seq.flight.append((seq.block_at, seq.block_fixed, seq.block_take,
                           seq.block_last, seq.block_masks))
        if commit:
            seq.block_at = -1
        return fresh, count

    def _block_tick(self, phase):  # rtpu: hot-loop
        """`_decode_tick` for this kind: dispatch the next forward of every
        live row's block (a denoising forward, or the commit of a block
        whose last mask is gone, or the first forward of the block a row
        opens), THEN read the report of the step dispatched a visit earlier
        and hand out the tokens of the blocks it finished."""
        tick_start = time.monotonic()
        cfg = self.config
        B, L = cfg.max_batch, cfg.model.block_length
        with phase("grow"):
            active = self._ensure_decode_pages([
                i for i, s in enumerate(self.seqs)
                if s.request is not None and s.phase == "decode"
                and not s.cancelled and not self._exhausted(s)])
        if not active:
            self._drain("idle", phase)
            return
        with phase("stage"):
            trace = not reqtrace.reqtrace_disabled()
            if trace:
                trace_rids = [self.seqs[i].request.request_id
                              for i in active]
                compile_t0 = self._compile_total()
            stage, poll = self._stage, self._poll
            poll()
            # tables, triples and the rows that decode are kept between
            # visits as `_decode_tick`'s are; `lengths` is written below
            stage.sync(active, self.seqs, self._sampling)
            # what a block step takes besides, anew every forward
            lengths = stage.lengths
            opened = np.zeros((B,), bool)
            fresh = np.zeros((B, L), np.int32)
            counts = np.zeros((B,), np.int32)
            thresholds = np.full((B,), 2.0, np.float32)
            for n, i in enumerate(active):
                if not n & 7:
                    poll()
                seq = self.seqs[i]
                thresholds[i] = seq.block_rule[1]
                ids, counts[i] = self._plan_forward(seq)
                if ids is not None:
                    opened[i], fresh[i] = True, ids
                # where this forward's K/V rows go: the block's positions
                lengths[i] = seq.flight[-1][0]
            self._decode_rows += len(active)
            self._block_forwards += len(active)
            self._sampler_steps[stage.tier] += 1
            poll()
            self._rng, key = jax.random.split(self._rng)
        accel = self._accel
        timer = accel.StepTimer(
            "decode", tokens=L * len(active),
            flops=2.0 * self._active_params * L * len(active),
            sink=self._step_accum) \
            if accel is not None else None
        with timer if timer is not None else contextlib.nullcontext():
            with self._mesh_scope(), (timer.device() if timer is not None
                                      else contextlib.nullcontext()):
                with phase("stage"):
                    def send(name):
                        poll()
                        return stage.send(name)

                    def upload(array):
                        poll()
                        return jnp.asarray(array)
                    live = (send("live"),)
                    rows = (send("tables"), send("lengths"),
                            self._tokens, upload(opened), upload(fresh),
                            upload(counts), upload(thresholds), key,
                            send("temps"), send("top_ks"), send("top_ps"))
                with phase("dispatch"):
                    unread, report = self._unread, self._tokens
                    self._dispatching()
                    self._step_taken(
                        self._decode(*self._step_args(live, rows)))
                    self._dispatched(self._tokens)
                    self._tokens.copy_to_host_async()
                    stage.sent(advance=False)
                    self._unread = [(i, self.seqs[i]) for i in active]
                    del live, rows
                if unread:
                    self._lookahead_ticks += 1
                    with phase("wait"):
                        values = self._fetch(report)
            if unread:
                with phase("emit"):
                    self._emit_blocks(unread, values)
            with phase("gauges"):
                if trace:
                    compile_s = self._compile_total() - compile_t0
                    if compile_s > 1e-6:
                        for rid in trace_rids:
                            reqtrace.record(
                                rid, reqtrace.COMPILE,
                                compile_s=round(compile_s, 6),
                                phase="decode")
                metrics = llm_metrics()
                metrics.token_latency.observe(
                    time.monotonic() - tick_start, tags=_TAGS)
                forwards, commits = self._block_metered
                self._block_metered = (self._block_forwards,
                                       self._commit_forwards)
                commits = self._commit_forwards - commits
                metrics.block_forwards.inc(
                    commits, tags=dict(_TAGS, kind="commit"))
                metrics.block_forwards.inc(
                    self._block_forwards - forwards - commits,
                    tags=dict(_TAGS, kind="denoise"))

    _decode_tick = _block_tick

    def _emit_blocks(self, unread: List[Tuple[int, _Seq]], values):
        """`_emit_tokens` for this kind: `values` is a block step's report
        on the host, a row a slot (`block_step`). A row's forward that found
        masks and left none finished its block: the block's tokens are
        handed out in position order (the prompt's tail and what lies past
        the request's budget left out), and the row ends if the block was
        its last or held the EOS. Where the dynamic rule finished a block
        ahead of the static count, the forward dispatched behind it found no
        mask and WAS the block's commit: the row's account is set right
        here, a visit late, and no forward is spent twice."""
        L = self.config.model.block_length
        metrics = llm_metrics()
        for slot, seq in unread:
            if self.seqs[slot] is not seq:
                continue
            if seq.cancelled:
                self._end_request(seq.request, None, index=slot,
                                  where="decode")
                continue
            at, fixed, take, last, believed = seq.flight.popleft()
            row = values[slot]
            if not row[L] or row[L + 1]:
                continue      # a commit, or a block that still holds masks
            if believed:
                self._block_done_early(seq, at, take, last)
            seq.blocks_done += 1
            if seq.blocks_done == 1:
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="done", at=at, tokens=take,
                                forwards=seq.block_step, open_s=round(
                                    time.monotonic() - seq.block_opened_ts,
                                    6))
            handed = len(seq.generated)
            callback = getattr(seq.request, "_token_callback", None)
            ended = last
            for token in row[fixed:fixed + take]:
                seq.generated.append(token)
                if not handed and len(seq.generated) == 1:
                    self._note_first_token(seq)
                if callback is not None:
                    callback(seq.request, token)
                if token == self.config.eos_token:
                    ended = True
                    break
            handed = len(seq.generated) - handed
            self._tokens_generated += handed
            self._block_tokens_out += handed
            metrics.decode_tokens.inc(handed, tags=_TAGS)
            metrics.block_tokens_out.inc(handed, tags=_TAGS)
            if ended:
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="last", at=at, blocks=seq.blocks_done)
                self._finish(slot)

    _emit_tokens = _emit_blocks

    def _block_done_early(self, seq: _Seq, at: int, take: int, last: bool):
        """The dynamic rule emptied the block at `at` in a forward after
        which the static count still had masks in it."""
        self._blocks_early += 1
        if seq.block_at != at:
            return
        if not seq.block_counted:
            seq.dispatched += take
            seq.block_counted = True
        seq.block_masks = 0
        if seq.flight and not last:
            # the forward dispatched behind it found no mask: the commit
            seq.length += self.config.model.block_length
            seq.block_at = -1
            self._commit_forwards += 1


ENGINES = {cls.kind: cls for cls in (
    DenseEngine, RecurrentEngine, PooledEngine, WindowedEngine,
    LatentEngine, IndexedEngine, BlockwiseEngine)}
