"""Latent paged decode attention: one query token a row over the row's pages
of LATENT rows (multi-head latent attention, absorbed form).

A latent layer caches one row `[c ; k_rope]` a token (`width` wide: the
compressed K/V latent of `value_dim`, then the shared rotary key), and that
row is the token's key for EVERY head and (its first `value_dim` lanes) its
value. With the key and value up-projections absorbed into the query and
the output (models/sarvam_mla.py), all of a row's query heads attend ONE kv
head:

    s_h(u) = q_h . row(u)            q_h = [W_K,h^T q_h^nope ; q_h^rope], scaled
    o_h    = sum_u softmax_u(s_h) row(u)[:value_dim]

`latent_attend` is the decode step's entry point (`latent_attend_chunk`,
below it, a prefill chunk's: an XLA loop over blocks of pages). Two paths,
as ops/paged_attention.py:

1. A Pallas TPU kernel over the engine's latent pool `[1, pages, page_size,
   width]`. Rows whose block tables begin with the same pages (a document
   the radix mapped in place for each of them) form a GROUP of at most
   `_GROUP_ROWS` (`share_schedule`, from the tables and the lengths alone),
   and the group's shared span is copied ONCE and attended with the
   members' queries stacked, `[members * heads, width]` against the one
   copy of a page block; after it each member attends its own remaining
   pages with its own queries and its own slice of the statistics. A row
   that shares nothing is a group of one: no shared span. A page copied
   serves as key (all `width` lanes) and as value (the first
   `value_dim`); the copies of a block of pages are in flight while the
   block before is computed, across spans, rows and groups. A COMPUTE
   STEP takes `_CHUNK_TOKENS` tokens: `q . row^T`, the softmax's max, two
   `exp` and a sum, `P . C` and the update of the statistics and of the
   accumulator each wait for the one before and nothing of the next step
   runs under them, so the chain is paid once a step and the step is
   long (PERF.md section 6, PR 47: the probe that chose it). Two products
   a step against the one kv head: bf16 operands (the pool's type),
   float32 accumulation; the logits `[members * heads, step]`, the
   softmax statistics and the output accumulator are float32; the
   probabilities enter `P . C` in the pool's type. ONE step length for
   every group size, and a shared span is whole steps: a row's steps
   then fall where they would alone, its sums are taken in one order
   whatever rows share its document, and its output does not depend on
   its neighbours in the batch.
2. A gather fallback elsewhere (the CPU, a model whose `attention_impl` is
   "reference"): each row's pages materialised densely, float32; it takes
   no notice of groups.

`latent_kernel` names the path a decode program built here will hold.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, NUM_LANES, _interpret

F32 = jnp.float32
# What the kernel's page buffer (two slots) may take of a core's fast memory.
_BUFFER_BYTES = 4 << 20
# Tokens a compute step takes, whatever the group's size: float32 logits
# [members * heads, step], 256 KB at 64 heads alone and 1 MB at four members
# (past a core's vector registers either way: they stand in fast memory). The
# chain inside a step is paid once for so many tokens: 256 cost a call half as
# much again (PERF.md section 6, PR 47). A group's shared span is rounded down
# to whole steps (`share_schedule`), so a longer step shares a little less.
_CHUNK_TOKENS = 1024
# Rows that attend a shared span together at most: their queries stacked
# are one product's rows (PERF.md section 6, PR 46: the probe that chose it).
_GROUP_ROWS = 4


def latent_kernel(value_dim: int, reference: bool = False) -> str:
    """The path `latent_attend` takes here: "pallas" or "gather". The
    kernel slices a row's value lanes off its key lanes, so they must be
    whole lane tiles."""
    if (jax.default_backend() == "tpu" and not reference
            and value_dim % NUM_LANES == 0):
        return "pallas"
    return "gather"


def _chunk_pages(page_size: int) -> int:
    """Whole pages a compute chunk takes."""
    return max(1, _CHUNK_TOKENS // page_size)


class Schedule(NamedTuple):
    """`share_schedule`'s answer: four int32 [rows], by position in the
    order the kernel takes the rows."""
    order: jax.Array
    lead: jax.Array
    size: jax.Array
    shared: jax.Array


def share_schedule(tables, lengths, page_size: int,
                   group: int = _GROUP_ROWS) -> Schedule:
    """Which rows of a decode step attend a shared span together. tables
    [rows, pages_per_row] page ids, lengths [rows] tokens cached before
    this one: numpy arrays or jax's (one body: the decode program calls it
    once a step, the engine on the arrays it stages, for its counter).

    Rows are ordered so that equal leading pages stand together (by the
    ids at positions 0, 1, 2, 4, 8, ... of their whole pages); a LINK is
    the count of leading pages two neighbours hold in common, whole pages
    that both attend in full (`lengths // page_size`: a dead row's null
    pages and a row's own tail never link), rounded down to whole compute
    chunks. A row is cut from the row before it where that link is weaker
    than either neighbour's other link (nested prefixes: of A, B on 270
    pages and C on their first 120, A and B go together), so every link
    inside a run is the same count, which all the run's rows hold in
    common; a run longer than `group` is cut into even parts of at most
    `group`. Returns, by position in that order: `order` the caller's row,
    `lead` the position of its group's first row, `size` its group's rows,
    `shared` the leading pages the group attends once (0 alone)."""
    xp = jnp if isinstance(tables, jax.Array) else np
    rows, width = tables.shape
    unit = _chunk_pages(page_size)
    full = lengths // page_size
    probes = sorted({0} | {1 << k for k in range(width.bit_length())
                           if 1 << k < width})
    keys = xp.where(xp.asarray(probes)[None, :] < full[:, None],
                    tables[:, probes], 0)
    order = xp.lexsort(keys.T[::-1])
    held, full = tables[order], full[order]
    differ = held[1:] != held[:-1]
    common = xp.where(differ.any(axis=1), differ.argmax(axis=1), width)
    link = xp.minimum(common, xp.minimum(full[1:], full[:-1])) // unit * unit
    none = xp.zeros((1,), link.dtype)
    before = xp.concatenate([none, link])       # with the row before
    after = xp.concatenate([link, none])        # with the row after
    cut = (before == 0) | (before < after) \
        | (before < xp.concatenate([none, before[:-1]]))
    run = cut.cumsum()
    at = xp.arange(rows)
    same = run[:, None] == run[None, :]
    length = same.sum(axis=1)
    place = (same & (at[None, :] < at[:, None])).sum(axis=1)
    part = place * (-(-length // group)) // length
    mates = same & (part[:, None] == part[None, :])
    lead, size = mates.argmax(axis=1), mates.sum(axis=1)
    shared = xp.where(size > 1, after[lead], 0)
    return Schedule(*(a.astype("int32")
                      for a in (order, lead, size, shared)))


def pages_spared(schedule: Schedule):
    """The page copies a step's schedule spares the kernel in one layer:
    a group's shared span once for every member but its first."""
    follows = schedule.lead != np.arange(len(schedule.lead))
    return (schedule.shared * follows).sum()


def latent_attend(q, pool, lengths, tables, *, value_dim: int,
                  reference: bool = False, schedule: Schedule = None):
    """q [rows, heads, width], absorbed and SCALED, float32 or the pool's
    type; pool [1, pages, page_size, width]; lengths [rows] tokens cached
    BEFORE this one, whose row is already written at position
    lengths[row]; tables [rows, pages_per_row] physical page ids;
    `schedule` what `share_schedule` made of the two (a caller with many
    layers makes it once; made here if None). Row b attends positions
    0 .. lengths[b]. Returns [rows, heads, value_dim] float32: the attended
    latent, in front of the value up-projection."""
    if latent_kernel(value_dim, reference) == "pallas":
        return _latent_attend_pallas(
            q.astype(pool.dtype), pool, lengths + 1, tables, schedule,
            value_dim=value_dim).astype(F32)
    rows, page_size = q.shape[0], pool.shape[2]
    span = tables.shape[1] * page_size
    held = pool[0][tables].reshape(rows, span, pool.shape[-1]).astype(F32)
    logits = jnp.einsum("bhw,bkw->bhk", q.astype(F32), held)
    seen = jnp.arange(span)[None, :] <= lengths[:, None]
    probs = jax.nn.softmax(jnp.where(seen[:, None, :], logits, NEG_INF),
                           axis=-1)
    return jnp.einsum("bhk,bkv->bhv", probs, held[..., :value_dim])


def _block_pages(page_size: int, width: int, pages_per_row: int,
                 itemsize: int, chunk: int) -> int:
    """Pages a copy group moves: as many whole compute steps of `chunk`
    tokens as `_BUFFER_BYTES` holds in two slots (a row's lanes rounded up
    to whole tiles; never fewer than one step), and no more than a row
    has. At 640 lanes of bf16 a step of 1024 tokens is 2.5 MiB in two
    slots: the block is ONE step, 16 pages of 64 tokens."""
    chunk_pages = max(1, chunk // page_size)
    lanes = -(-width // NUM_LANES) * NUM_LANES
    chunk_bytes = 2 * chunk_pages * page_size * lanes * itemsize
    fit = max(1, _BUFFER_BYTES // chunk_bytes)
    need = -(-pages_per_row // chunk_pages)
    return min(fit, need) * chunk_pages


def _kernel(lengths_ref, tables_ref, order_ref, lead_ref, size_ref,
            shared_ref, q_ref, pool_hbm, o_ref, buf, sems, slot_ref, qs_ref,
            m_ref, l_ref, acc_ref, *, block_pages: int, chunk: int,
            pages_per_row: int, value_dim: int, group: int):
    """One row, by its position in the schedule's order; a group's first
    row attends the group's shared span for every member before its own.
    lengths_ref [rows] tokens to attend (>= 1), tables_ref [rows *
    pages_per_row], order_ref / lead_ref / size_ref / shared_ref [rows] the
    schedule, in SMEM; q_ref [rows, heads, width] every row's queries,
    o_ref [heads, value_dim] this row's; pool_hbm the pool; buf [2, block,
    width]; sems [2] (by slot); slot_ref [1] the slot the next block to
    compute is in; qs_ref [group * heads, width] a group's queries
    stacked; m_ref, l_ref [group * heads, 1] and acc_ref [group * heads,
    value_dim] the statistics of a group's members, one after another:
    the shared span fills them, and each member's own pages go on in the
    FIRST member's rows, its own moved there first."""
    at, rows = pl.program_id(0), pl.num_programs(0)
    heads = q_ref.shape[1]
    page_size = pool_hbm.shape[2]
    row = order_ref[at]
    length = lengths_ref[row]
    pages = pl.cdiv(length, page_size)
    shared = shared_ref[at]
    leads = (lead_ref[at] == at) & (shared > 0)

    def copies(r, first, count, slot, start: bool):
        """Start (or wait for) pages first .. first + count of row `r`'s
        table: ONE copy a page, which is its keys and its values."""
        base = r * pages_per_row + first

        def one(j, carry):
            # a wait needs the copy's shape, not its source
            page = tables_ref[base + j] if start else 0
            to = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            copy = pltpu.make_async_copy(
                pool_hbm.at[0, page], buf.at[slot, to], sems.at[slot])
            if start:
                copy.start()
            else:
                copy.wait()
            return carry
        jax.lax.fori_loop(0, count, one, None)

    def first_block(p):
        """(row, first page, pages) of the first block program `p` takes:
        of its group's shared span if it leads one, else of its own pages
        behind what its group's leader attended for it."""
        r, span = order_ref[p], shared_ref[p]
        leader = lead_ref[p] == p
        first = jnp.where(leader, 0, span)
        end = jnp.where(leader & (span > 0), span,
                        pl.cdiv(lengths_ref[r], page_size))
        return r, first, jnp.minimum(block_pages, end - first)

    @pl.when(at == 0)
    def _first():
        # a row's last chunk reads past its tokens: masked as keys, times
        # a probability of zero as values, which the buffer's first bits
        # may not survive (0 * nan)
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        copies(*first_block(0), 0, start=True)

    def attend_span(first, end, limit, stats, then):
        """Pages first .. end of this row's table, `limit` tokens of the
        row seen (None: every token of the span by every member), for the
        rows `stats` of the stacked queries and of the statistics; `then`
        says what to copy while the span's last block is computed: (is
        there one, row, first page, pages)."""
        blocks = pl.cdiv(end - first, block_pages)

        def attend_block(b, slot):
            begin = first + b * block_pages
            count = jnp.minimum(block_pages, end - begin)
            ends = b + 1 == blocks
            follows, r_next, first_next, count_next = then

            @pl.when(jnp.logical_not(ends) | follows)
            def _prefetch():
                copies(jnp.where(ends, r_next, row),
                       jnp.where(ends, first_next, begin + block_pages),
                       jnp.where(ends, count_next, jnp.minimum(
                           block_pages, end - begin - block_pages)),
                       1 - slot, start=True)

            copies(row, begin, count, slot, start=False)
            here = count * page_size if limit is None else jnp.minimum(
                count * page_size, limit - begin * page_size)

            def attend_chunk(c, carry):
                to = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                held = buf[slot, to, :]                    # [chunk, width]
                logits = jax.lax.dot_general(
                    qs_ref[stats, :], held, (((1,), (1,)), ((), ())),
                    preferred_element_type=F32)        # [queries, chunk]
                if limit is not None:
                    seen = (begin * page_size + c * chunk
                            + jax.lax.broadcasted_iota(
                                jnp.int32, (1, chunk), 1)) < limit
                    logits = jnp.where(seen, logits, NEG_INF)
                m_prev = m_ref[stats, :]                   # [queries, 1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(logits, axis=-1, keepdims=True))
                p = jnp.exp(logits - m_new)
                correction = jnp.exp(m_prev - m_new)
                m_ref[stats, :] = m_new
                l_ref[stats, :] = l_ref[stats, :] * correction + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_ref[stats, :] = acc_ref[stats, :] * correction \
                    + jax.lax.dot_general(
                        p.astype(held.dtype), held[:, :value_dim],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=F32)  # [queries, value_dim]
                return carry
            jax.lax.fori_loop(0, pl.cdiv(here, chunk), attend_chunk, None)
            return 1 - slot

        slot_ref[0] = jax.lax.fori_loop(0, blocks, attend_block,
                                        slot_ref[0])

    def reset(members):
        stats = pl.ds(0, members * heads)
        m_ref[stats, :] = jnp.full((members * heads, 1), NEG_INF, F32)
        l_ref[stats, :] = jnp.zeros((members * heads, 1), F32)
        acc_ref[stats, :] = jnp.zeros((members * heads, value_dim), F32)

    own = pl.ds(0, heads)
    qs_ref[own, :] = q_ref[row]
    # a product takes as many query rows as the group has members
    for members in range(2, group + 1):
        @pl.when(leads & (size_ref[at] == members))
        def _shared_span(members=members):
            stats = pl.ds(0, members * heads)
            for j in range(1, members):
                qs_ref[pl.ds(j * heads, heads), :] = q_ref[order_ref[at + j]]
            reset(members)
            attend_span(
                0, shared, None, stats,
                (True, row, shared,
                 jnp.minimum(block_pages, pages - shared)))

    @pl.when(shared == 0)
    def _alone():
        reset(1)

    @pl.when(lead_ref[at] != at)
    def _follows():
        # what the group's first row made of the shared span for this one
        made = pl.ds(pl.multiple_of((at - lead_ref[at]) * heads, heads),
                     heads)
        m_ref[own, :] = m_ref[made, :]
        l_ref[own, :] = l_ref[made, :]
        acc_ref[own, :] = acc_ref[made, :]

    after = jnp.minimum(at + 1, rows - 1)
    attend_span(shared, pages, length, own,
                (at + 1 < rows,) + first_block(after))
    o_ref[...] = (acc_ref[own, :] / l_ref[own, :]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("value_dim", "block_pages", "group"))
def _latent_attend_pallas(q, pool, lengths, tables, schedule=None, *,
                          value_dim: int, block_pages=None,
                          group: int = _GROUP_ROWS):
    """The kernel. q [rows, heads, width] SCALED and in the pool's type;
    lengths [rows] tokens to attend, >= 1 (a dead row: 1, on the null
    page); `schedule` what `share_schedule` made of the tables and of
    `lengths - 1` for groups of `group` at most (made here if None).
    `block_pages` is the tests' override of `_block_pages`. Jitted so that
    a model's layers share ONE trace of the kernel's body. Returns [rows,
    heads, value_dim] float32, in the caller's order."""
    rows, heads, width = q.shape
    _, _, page_size, _ = pool.shape
    pages_per_row = tables.shape[1]
    unit = _chunk_pages(page_size) * page_size
    if block_pages is None:
        block_pages = _block_pages(page_size, width, pages_per_row,
                                   pool.dtype.itemsize, unit)
    block = block_pages * page_size
    chunk = min(unit, block)
    # (a shared span is whole `unit`s, whatever the override made of them)
    if block % chunk or unit % chunk:
        raise ValueError(f"pages of {page_size} tokens do not tile chunks "
                         f"of {chunk} in a block of {block}")
    if schedule is None:
        schedule = share_schedule(tables, lengths - 1, page_size, group)
    stat = pltpu.VMEM((group * heads, 1), F32)
    return pl.pallas_call(
        functools.partial(_kernel, block_pages=block_pages, chunk=chunk,
                          pages_per_row=pages_per_row, value_dim=value_dim,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            # the schedule's order in, the caller's order out
            out_specs=pl.BlockSpec((None, heads, value_dim),
                                   lambda at, n, t, order, *_:
                                   (order[at], 0, 0)),
            grid=(rows,),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((group * heads, width), pool.dtype),
                stat, stat,
                pltpu.VMEM((group * heads, value_dim), F32)]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, value_dim), F32),
        # a span's last block starts the next span's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="latent_attention",
    # no row reads past its table, as none does in the gather fallback
    )(jnp.minimum(lengths, pages_per_row * page_size), tables.reshape(-1),
      *schedule, q, pool)


# Cached tokens a step of `latent_attend_chunk`'s loop takes.
_CHUNK_BLOCK_TOKENS = 512


def latent_attend_chunk(q, pool, table, start, *, value_dim: int):
    """One prefill chunk of ONE row over its pages. q [chunk, heads, width],
    absorbed and scaled, query i at position start + i, its own row already
    written; pool [1, pages, page_size, width]; table [pages_per_row] the
    row's page ids (the null page where it holds none). Query i attends
    positions 0 .. start + i. The cached rows are taken a block of pages
    at a time with running softmax statistics (float32; the two products
    take the pool's type and accumulate in float32), as many blocks as the
    chunk's last position reaches: neither the logits nor anything expanded
    from the cache ever stands whole. Returns [chunk, heads, value_dim]
    float32."""
    chunk, heads, width = q.shape
    page_size = pool.shape[2]
    block_pages = max(1, _CHUNK_BLOCK_TOKENS // page_size)
    block = block_pages * page_size
    # whole blocks: a slice that ran past the table would be moved back
    table = jnp.pad(table, (0, -table.shape[0] % block_pages))
    queries = q.reshape(chunk * heads, width).astype(pool.dtype)
    at = jnp.repeat(start + jnp.arange(chunk), heads)[:, None]

    def attend_block(b, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, b * block_pages,
                                           block_pages)
        held = pool[0][ids].reshape(block, width)
        logits = jax.lax.dot_general(
            queries, held, (((1,), (1,)), ((), ())),
            preferred_element_type=F32)
        seen = (b * block + jnp.arange(block))[None, :] <= at
        logits = jnp.where(seen, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        correction = jnp.exp(m - m_new)
        return (m_new, l * correction + p.sum(-1, keepdims=True),
                acc * correction + jax.lax.dot_general(
                    p.astype(pool.dtype), held[:, :value_dim],
                    (((1,), (0,)), ((), ())), preferred_element_type=F32))

    rows = chunk * heads
    _, l, acc = jax.lax.fori_loop(
        0, (start + chunk + block - 1) // block, attend_block,
        (jnp.full((rows, 1), NEG_INF, F32), jnp.zeros((rows, 1), F32),
         jnp.zeros((rows, value_dim), F32)))
    return (acc / l).reshape(chunk, heads, value_dim)
