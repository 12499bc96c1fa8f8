"""Paged decode attention: one query token a row over the row's K/V pages
(`paged_attend`), or the few positions of a row's open block, each
attending all of them (`paged_attend_block`: generation by diffusion over
blocks, models/sdar.py).

`paged_attend` is the one entry point of every model's paged-decode branch.
Its callers and the head sizes they bring: models/llama.py (Mistral, 128),
models/falcon_h1.py (128), models/nemotron_h.py (128), models/evabyte.py
(128), each over a pool `[kv_heads, pages, page_size, head_dim]`; and
models/lfm2.py (64) over a PACKED pool (below). Two paths:

1. A Pallas TPU kernel written for the engine's page pool, taken on a TPU
   when the pool's rows are whole lane tiles (`lanes % 128 == 0`): heads
   128 wide (or a multiple) in the plain pool, heads 64 wide (any width
   that divides 128) in a packed one. A program is a ROW; one asynchronous
   copy moves a page for ALL local kv heads (`kv_heads` runs of `page_size
   * lanes` elements); the copies of a block of pages are in flight while
   the block before is computed, across rows too. The products take the
   pool's (bf16) operands and accumulate in float32; the softmax
   statistics, the probabilities into `P . V` and the output accumulator
   are float32.
2. A gather fallback elsewhere (the CPU, toy head sizes in a plain pool, a
   model whose `attention_impl` is "reference"): each row's pages
   materialised densely.

A packed pool (`packed_pool_shape`) stands `128 / head_dim` kv heads side
by side in one 128-lane row, `[kv_heads * head_dim / 128, pages, page_size,
128]`: a 64-wide head in a plain pool is padded to 128 lanes in the chip's
memory, twice the bytes held and read. The kernel is the same one: the
queries of the kv heads that share a row are laid block-diagonally over its
lanes (zeros under the other heads' lanes), so `q . k` over 128 lanes is
each head's own product, and of `P . V`'s 128 lanes each query keeps its own
head's. `paged_attend_chunk` and `write_chunk_pages` are a prefill chunk's
two halves over either pool: the chunk's rows into the row's pages, and
the chunk's queries over the row's pages a block at a time.

`paged_kernel` names the path a decode program built here will hold.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, NUM_LANES, _interpret

# What the kernel's four K/V buffers (two slots each) may take of a core's
# fast memory; the block of pages a copy group moves is sized to it.
_BUFFER_BYTES = 4 << 20
# What a compute step's float32 logits [kv_heads, queries, tokens] may take:
# a quarter of the vector registers. A row pays for its tokens rounded up
# to such a chunk, not to the block.
_LOGIT_BYTES = 64 << 10
_SUBLANES = 8


def paged_kernel(head_dim: int, reference: bool = False,
                 lanes: int = 0) -> str:
    """The path `paged_attend` takes here for heads of `head_dim` over a
    pool whose rows are `lanes` wide (`head_dim` itself in a plain pool):
    "pallas" or "gather"."""
    if (jax.default_backend() == "tpu" and not reference
            and (lanes or head_dim) % NUM_LANES == 0):
        return "pallas"
    return "gather"


def packed_pool_shape(kv_heads: int, head_dim: int, pages: int,
                      page_size: int):
    """A page pool for heads narrower than a lane tile: as many kv heads
    side by side in a row as 128 lanes hold (and as divide `kv_heads`),
    kv head h in lanes (h % f) * head_dim .. of row h // f. Two heads of
    64 fill a row; a plain pool's shape where nothing packs."""
    side = math.gcd(kv_heads, NUM_LANES // head_dim) \
        if head_dim < NUM_LANES and NUM_LANES % head_dim == 0 else 1
    return (kv_heads // side, pages, page_size, side * head_dim)


def _side_by_side(pool, head_dim: int) -> int:
    """kv heads that share a row of `pool` (1: a plain pool)."""
    return pool.shape[-1] // head_dim


def paged_attend(q, k_pages, v_pages, lengths, tables, *,
                 reference: bool = False):
    """q [rows, heads, hd], unscaled; k_pages / v_pages [kv_heads, pages,
    page_size, hd] (LOCAL heads and kv heads under a tensor axis: attention
    is head-parallel, no collective), or packed (`packed_pool_shape`);
    lengths [rows] tokens cached BEFORE
    this one, which is already written at position lengths[row]; tables
    [rows, pages_per_row] physical page ids. Row b attends positions 0 ..
    lengths[b]. Returns [rows, heads, hd]."""
    hd = q.shape[-1]
    side = _side_by_side(k_pages, hd)
    if paged_kernel(hd, reference, k_pages.shape[-1]) == "pallas":
        scaled = (q * hd ** -0.5).astype(k_pages.dtype)
        if side == 1:
            return _paged_attend_pallas(scaled, k_pages, v_pages,
                                        lengths + 1, tables)
        return _paged_attend_packed(scaled, k_pages, v_pages, lengths + 1,
                                    tables)
    # Gather fallback: materialize each row's pages densely.
    # [B, pages_per_seq, kvh, ps, hd] -> [B, kvh, L, hd]
    rows, page_size = q.shape[0], k_pages.shape[2]
    gk = jnp.transpose(k_pages, (1, 0, 2, 3))[tables]
    gv = jnp.transpose(v_pages, (1, 0, 2, 3))[tables]
    if side > 1:
        # [B, pages, kvh / f, ps, f * hd] -> [B, pages, kvh, ps, hd]
        gk, gv = (jnp.moveaxis(g.reshape(g.shape[:4] + (side, hd)), 4, 3)
                  .reshape(g.shape[:2] + (-1, page_size, hd))
                  for g in (gk, gv))
    kv_heads = gk.shape[2]
    span = tables.shape[1] * page_size
    gk = jnp.transpose(gk, (0, 2, 1, 3, 4)).reshape(
        rows, kv_heads, span, hd)
    gv = jnp.transpose(gv, (0, 2, 1, 3, 4)).reshape(
        rows, kv_heads, span, hd)
    groups = q.shape[1] // kv_heads
    gk = jnp.repeat(gk, groups, axis=1)
    gv = jnp.repeat(gv, groups, axis=1)
    logits = jnp.einsum(
        "bhd,bhkd->bhk", q.astype(jnp.float32),
        gk.astype(jnp.float32)) * (hd ** -0.5)
    kv_pos = jnp.arange(span)[None, :]
    mask = kv_pos <= lengths[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", probs, gv.astype(jnp.float32))


def _paged_attend_packed(q, k_pages, v_pages, lengths, tables,
                         block_pages=None):
    """The kernel over a packed pool. q [rows, heads, hd] SCALED and in the
    pool's type; lengths as `_paged_attend_pallas` takes them. A row of the
    pool holds `side` kv heads: their queries stand together as that row's
    group, each under its own head's lanes and zero under the others', so
    the kernel's 128-lane products are the heads' own; of its 128-lane
    output each query keeps its head's lanes."""
    rows, heads, hd = q.shape
    side = _side_by_side(k_pages, hd)
    packed = k_pages.shape[0]
    groups = heads // (packed * side)
    q = q.reshape(rows, packed, side, groups, hd)
    wide = jnp.einsum("rpsgd,st->rpsgtd", q, jnp.eye(side, dtype=q.dtype))
    out = _paged_attend_pallas(
        wide.reshape(rows, heads, side * hd), k_pages, v_pages, lengths,
        tables, block_pages=block_pages)
    out = out.reshape(rows, packed, side, groups, side, hd)
    return jnp.stack([out[:, :, s, :, s] for s in range(side)],
                     axis=2).reshape(rows, heads, hd)


def paged_attend_block(q, k_pages, v_pages, lengths, tables, *,
                       reference: bool = False):
    """The positions of each row's open block over the row's pages, both
    ways inside the block. q [rows, block, heads, hd], unscaled, query j of
    row b at position lengths[b] + j; the block's K/V rows are written
    already (`write_block_rows`); lengths [rows] the tokens committed before
    the block. Every query of a row sees the same keys, positions 0 ..
    lengths[b] + block - 1, so the block's queries stand as `block` more
    members of each kv head's group: one call of `paged_attend`, the
    kernel's loop over pages as it is, `heads / kv_heads * block` queries a
    kv head. Returns [rows, block, heads, hd]."""
    rows, block, heads, hd = q.shape
    side = _side_by_side(k_pages, hd)
    kv_heads = k_pages.shape[0] * side
    groups = heads // kv_heads
    folded = jnp.transpose(q.reshape(rows, block, kv_heads, groups, hd),
                           (0, 2, 1, 3, 4)).reshape(rows, -1, hd)
    out = paged_attend(folded, k_pages, v_pages, lengths + block - 1,
                       tables, reference=reference)
    return jnp.transpose(out.reshape(rows, kv_heads, block, groups, hd),
                         (0, 2, 1, 3, 4)).reshape(rows, block, heads, hd)


def write_block_rows(pool, rows, block_tables, lengths):
    """The K (or V) rows of each batch row's open block into a plain page
    pool. pool [kv_heads, pages, page_size, hd]; rows [kv_heads, B, block,
    hd], position j of row b at lengths[b] + j. `lengths` and the page size
    are whole blocks, so a block lies in one page: (block_tables[b,
    lengths[b] // page_size], lengths[b] % page_size + j). The scatter
    indexes the three leading dimensions, as `models.llama.write_token_rows`
    does and for its reason. Rows that are not live write to the null page
    their table names and may collide there."""
    page_size, block = pool.shape[2], rows.shape[2]
    batch = jnp.arange(rows.shape[1])
    page_of = block_tables[batch, lengths // page_size]
    offset = (lengths % page_size)[:, None] + jnp.arange(block)
    heads = jnp.arange(pool.shape[0])[:, None, None]
    return pool.at[heads, page_of[None, :, None], offset[None]].set(
        rows.astype(pool.dtype))


# Cached tokens a step of `paged_attend_chunk`'s loop takes.
_CHUNK_BLOCK_TOKENS = 512


def write_chunk_pages(pool, rows, table, start, valid):
    """A prefill chunk's K (or V) rows into ONE row's pages. pool plain or
    packed; rows [chunk, kv_heads, hd], token i at position start + i;
    table [pages_per_row] the row's page ids. `start` is a whole number of
    chunks (the engine's chunks start at multiples of its largest bucket),
    and a chunk is whole pages or a part of one page: the pages are written
    as they stand, one update a page. A page that begins in the padded tail
    (token i >= valid) goes to the null page; the padded tokens of the page
    that holds the last real one stay in it, where the row's own later
    tokens overwrite them before anything attends them."""
    page_size = pool.shape[2]
    chunk = rows.shape[0]
    part = min(chunk, page_size)
    if chunk % part or page_size % part:
        raise ValueError(f"a chunk of {chunk} tokens is neither whole pages "
                         f"of {page_size} nor a part of one")
    # [chunk, kv_heads, hd] -> [packed, chunk, lanes]
    rows = jnp.transpose(rows.reshape(chunk, pool.shape[0], pool.shape[3]),
                         (1, 0, 2)).astype(pool.dtype)
    for j in range(chunk // part):
        at = start + j * part
        page = jnp.where(j * part < valid,
                         table[jnp.minimum(at // page_size,
                                           table.shape[0] - 1)], 0)
        pool = jax.lax.dynamic_update_slice(
            pool, rows[:, None, j * part:(j + 1) * part],
            (0, page, at % page_size, 0))
    return pool


def paged_attend_chunk(q, k_pages, v_pages, table, start,
                       block_length=None):
    """One prefill chunk of ONE row over its pages. q [chunk, heads, hd]
    SCALED, query i at position start + i, its own K/V already written
    (`write_chunk_pages`); the pools plain or packed; table [pages_per_row]
    the row's page ids (the null page where it holds none). Query i attends
    positions 0 .. start + i; with `block_length` (a power of two that
    divides `start` and the chunk), to the end of its block of that many
    positions: 0 .. (start + i) | (block_length - 1), the block-causal mask
    of a model that generates by diffusion over blocks. The cached rows are taken a block of pages at
    a time with running softmax statistics (float32; the two products take
    the pool's type and accumulate in float32), as many blocks as the
    chunk's last position reaches: neither the logits nor a dense copy of
    the row's cache ever stands whole. Returns [chunk, heads, hd] float32."""
    chunk, heads, hd = q.shape
    packed, _, page_size, lanes = k_pages.shape
    side = lanes // hd
    kv_heads = packed * side
    groups = heads // kv_heads
    block_pages = max(1, _CHUNK_BLOCK_TOKENS // page_size)
    block = block_pages * page_size
    # whole blocks: a slice that ran past the table would be moved back
    table = jnp.pad(table, (0, -table.shape[0] % block_pages))
    queries = q.reshape(chunk, packed, side, groups, hd).astype(k_pages.dtype)
    at = (start + jnp.arange(chunk))[:, None]
    if block_length is not None:
        if block_length & (block_length - 1) or chunk % block_length:
            raise ValueError(f"blocks of {block_length} positions are not a "
                             f"power of two that divides a chunk of {chunk}")
        at = at | (block_length - 1)

    def attend_block(b, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, b * block_pages,
                                           block_pages)
        held = lambda pool: pool[:, ids].reshape(  # noqa: E731
            packed, block, side, hd)
        keys, values = held(k_pages), held(v_pages)
        logits = jnp.einsum("qpsgd,ptsd->psgqt", queries, keys,
                            preferred_element_type=jnp.float32)
        seen = (b * block + jnp.arange(block))[None, :] <= at
        logits = jnp.where(seen, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        correction = jnp.exp(m - m_new)
        return (m_new, l * correction + p.sum(-1, keepdims=True),
                acc * correction + jnp.einsum(
                    "psgqt,ptsd->psgqd", p.astype(v_pages.dtype), values,
                    preferred_element_type=jnp.float32))

    stats = (packed, side, groups, chunk, 1)
    _, l, acc = jax.lax.fori_loop(
        0, (start + chunk + block - 1) // block, attend_block,
        (jnp.full(stats, NEG_INF, jnp.float32),
         jnp.zeros(stats, jnp.float32),
         jnp.zeros((packed, side, groups, chunk, hd), jnp.float32)))
    return jnp.transpose(acc / l, (3, 0, 1, 2, 4)).reshape(chunk, heads, hd)


def _chunk_tokens(kv_heads: int, queries: int) -> int:
    """Tokens a compute step takes: whole lanes of logits within
    `_LOGIT_BYTES`, 128 to 512 (256 at 8 kv heads x 8 padded queries; on
    the chip 128 cost chat's shapes 14 % and 1024 gained the 4 x 8 and
    2 x 16 shapes 3 % over 512: PERF.md §6, PR 37)."""
    fit = _LOGIT_BYTES // (4 * kv_heads * queries) // NUM_LANES * NUM_LANES
    return max(NUM_LANES, min(4 * NUM_LANES, fit))


def _block_pages(kv_heads: int, page_size: int, head_dim: int,
                 pages_per_row: int, itemsize: int, chunk: int) -> int:
    """Pages a copy group moves: as many whole compute chunks as
    `_BUFFER_BYTES` holds for this many kv heads, and no more than a row
    has (rounded up to whole chunks)."""
    chunk_pages = chunk // page_size
    chunk_bytes = 2 * 2 * kv_heads * chunk * head_dim * itemsize
    fit = max(1, _BUFFER_BYTES // chunk_bytes)
    need = -(-pages_per_row // chunk_pages)
    return min(fit, need) * chunk_pages


def _kernel(lengths_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
            block_pages: int, chunk: int, pages_per_row: int):
    """One row. lengths_ref [rows] tokens to attend (>= 1), tables_ref
    [rows * pages_per_row] in SMEM; q_ref / o_ref [kv_heads, G, hd] (the
    row's query heads by kv head, G padded to whole sublanes); k_hbm /
    v_hbm the pools; k_buf / v_buf [2, kv_heads, block, hd]; sems [2, 2]
    (K / V by slot); slot_ref [1] the slot the row's first block is in."""
    row, rows = pl.program_id(0), pl.num_programs(0)
    page_size = k_hbm.shape[2]
    block = block_pages * page_size
    length = lengths_ref[row]

    def copies(r, blk, slot, start: bool):
        """Start (or wait for) the pages of block `blk` of row `r`: per
        page ONE copy for K and one for V that covers every kv head."""
        pages = jnp.minimum(
            block_pages, pl.cdiv(lengths_ref[r] - blk * block, page_size))
        first = r * pages_per_row + blk * block_pages

        def one(j, carry):
            # a wait needs the copy's shape, not its source
            page = tables_ref[first + j] if start else 0
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for pool, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                   (v_hbm, v_buf, sems.at[1, slot])):
                copy = pltpu.make_async_copy(
                    pool.at[:, page], buf.at[slot, :, at], sem)
                if start:
                    copy.start()
                else:
                    copy.wait()
            return carry
        jax.lax.fori_loop(0, pages, one, None)

    @pl.when(row == 0)
    def _first():
        # a row's last chunk reads past its tokens: masked in K, times a
        # probability of zero in V, which the buffer's first bits may not
        # survive (0 * nan)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        copies(0, 0, 0, start=True)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    blocks = pl.cdiv(length, block)

    def attend_block(blk, slot):
        ends = blk + 1 == blocks
        next_row = jnp.where(ends, row + 1, row)

        @pl.when(next_row < rows)
        def _prefetch():
            copies(next_row, jnp.where(ends, 0, blk + 1), 1 - slot,
                   start=True)

        copies(row, blk, slot, start=False)
        here = jnp.minimum(block, length - blk * block)

        def attend_chunk(c, carry):
            # every kv head at once: one chain of products, reductions and
            # exponentials a chunk, kv_heads wide, not kv_heads chains
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            seen = (blk * block + c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, chunk), 2)) < length
            logits = jax.lax.dot_general(
                q_ref[...], k_buf[slot, :, at, :],
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)      # [kvh, G, chunk]
            logits = jnp.where(seen, logits, NEG_INF)
            m_prev = m_ref[...]                              # [kvh, G, 1]
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            correction = jnp.exp(m_prev - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * correction + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
                p, v_buf[slot, :, at, :].astype(jnp.float32),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)      # [kvh, G, hd]
            return carry
        jax.lax.fori_loop(0, pl.cdiv(here, chunk), attend_chunk, None)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, blocks, attend_block, slot_ref[0])
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_pages",))
def _paged_attend_pallas(q, k_pages, v_pages, lengths, tables,
                         block_pages=None):
    """The kernel. q [rows, heads, hd] SCALED and in the pool's type;
    lengths [rows] tokens to attend, >= 1 (a dead row: 1, on the null
    page). `block_pages` is the tests' override of `_block_pages`. Jitted so that a model's layers share ONE trace of
    the kernel's body: traced a layer at a time, 16 layers added 2.8 s to
    every start of the chat cell's replica (PERF.md §6, PR 37)."""
    rows, heads, hd = q.shape
    kv_heads, _, page_size, _ = k_pages.shape
    pages_per_row = tables.shape[1]
    groups = heads // kv_heads
    padded = -(-groups // _SUBLANES) * _SUBLANES
    chunk = _chunk_tokens(kv_heads, padded)
    if block_pages is None:
        block_pages = _block_pages(kv_heads, page_size, hd, pages_per_row,
                                   k_pages.dtype.itemsize, chunk)
    block = block_pages * page_size
    chunk = min(chunk, block)
    if chunk % page_size or block % chunk:
        raise ValueError(f"pages of {page_size} tokens do not tile chunks "
                         f"of {chunk} in a block of {block}")
    # by kv head, each group padded to whole sublanes: a slice per kv head
    # is then whole tiles
    q = jnp.pad(q.reshape(rows, kv_heads, groups, hd),
                ((0, 0), (0, 0), (0, padded - groups), (0, 0)))
    row_spec = pl.BlockSpec((None, kv_heads, padded, hd),
                            lambda r, *_: (r, 0, 0, 0))
    stat = pltpu.VMEM((kv_heads, padded, 1), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, block_pages=block_pages, chunk=chunk,
                          pages_per_row=pages_per_row),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[row_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec,
            grid=(rows,),
            scratch_shapes=[
                pltpu.VMEM((2, kv_heads, block, hd), k_pages.dtype),
                pltpu.VMEM((2, kv_heads, block, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                stat, stat,
                pltpu.VMEM((kv_heads, padded, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, k_pages.dtype),
        # a row's last block starts the next row's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="paged_attention",
    # no row reads past its table, as none does in the gather fallback
    )(jnp.minimum(lengths, pages_per_row * page_size), tables.reshape(-1),
      q, k_pages, v_pages)
    return out[:, :, :groups].reshape(rows, heads, hd)
