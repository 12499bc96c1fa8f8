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
   width]`. A program is a ROW; a page is copied ONCE and serves as key
   (all `width` lanes) and as value (the first `value_dim`); the copies of
   a block of pages are in flight while the block before is computed, across
   rows too. Two products a chunk with every query head against the one kv
   head: bf16 operands (the pool's type), float32 accumulation; the softmax
   statistics and the output accumulator are float32; the probabilities
   enter `P . C` in the pool's type.
2. A gather fallback elsewhere (the CPU, a model whose `attention_impl` is
   "reference"): each row's pages materialised densely, float32.

`latent_kernel` names the path a decode program built here will hold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, NUM_LANES, _interpret

F32 = jnp.float32
# What the kernel's page buffer (two slots) may take of a core's fast memory.
_BUFFER_BYTES = 4 << 20
# Tokens a compute step takes: [heads, chunk] float32 logits, 64 KB at 64
# heads.
_CHUNK_TOKENS = 256


def latent_kernel(value_dim: int, reference: bool = False) -> str:
    """The path `latent_attend` takes here: "pallas" or "gather". The
    kernel slices a row's value lanes off its key lanes, so they must be
    whole lane tiles."""
    if (jax.default_backend() == "tpu" and not reference
            and value_dim % NUM_LANES == 0):
        return "pallas"
    return "gather"


def latent_attend(q, pool, lengths, tables, *, value_dim: int,
                  reference: bool = False):
    """q [rows, heads, width], absorbed and SCALED, float32 or the pool's
    type; pool [1, pages, page_size, width]; lengths [rows] tokens cached
    BEFORE this one, whose row is already written at position
    lengths[row]; tables [rows, pages_per_row] physical page ids. Row b
    attends positions 0 .. lengths[b]. Returns [rows, heads, value_dim]
    float32: the attended latent, in front of the value up-projection."""
    if latent_kernel(value_dim, reference) == "pallas":
        return _latent_attend_pallas(
            q.astype(pool.dtype), pool, lengths + 1, tables,
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
    """Pages a copy group moves: as many whole compute chunks as
    `_BUFFER_BYTES` holds in two slots (a row's lanes rounded up to whole
    tiles), and no more than a row has."""
    chunk_pages = max(1, chunk // page_size)
    lanes = -(-width // NUM_LANES) * NUM_LANES
    chunk_bytes = 2 * chunk_pages * page_size * lanes * itemsize
    fit = max(1, _BUFFER_BYTES // chunk_bytes)
    need = -(-pages_per_row // chunk_pages)
    return min(fit, need) * chunk_pages


def _kernel(lengths_ref, tables_ref, q_ref, pool_hbm, o_ref, buf, sems,
            slot_ref, m_ref, l_ref, acc_ref, *, block_pages: int,
            chunk: int, pages_per_row: int, value_dim: int):
    """One row. lengths_ref [rows] tokens to attend (>= 1), tables_ref
    [rows * pages_per_row] in SMEM; q_ref [heads, width], o_ref [heads,
    value_dim]; pool_hbm the pool; buf [2, block, width]; sems [2] (by
    slot); slot_ref [1] the slot the row's first block is in."""
    row, rows = pl.program_id(0), pl.num_programs(0)
    page_size = pool_hbm.shape[2]
    block = block_pages * page_size
    length = lengths_ref[row]

    def copies(r, blk, slot, start: bool):
        """Start (or wait for) the pages of block `blk` of row `r`: ONE
        copy a page, which is its keys and its values."""
        pages = jnp.minimum(
            block_pages, pl.cdiv(lengths_ref[r] - blk * block, page_size))
        first = r * pages_per_row + blk * block_pages

        def one(j, carry):
            # a wait needs the copy's shape, not its source
            page = tables_ref[first + j] if start else 0
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            copy = pltpu.make_async_copy(
                pool_hbm.at[0, page], buf.at[slot, at], sems.at[slot])
            if start:
                copy.start()
            else:
                copy.wait()
            return carry
        jax.lax.fori_loop(0, pages, one, None)

    @pl.when(row == 0)
    def _first():
        # a row's last chunk reads past its tokens: masked as keys, times
        # a probability of zero as values, which the buffer's first bits
        # may not survive (0 * nan)
        buf[...] = jnp.zeros_like(buf)
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
            at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            held = buf[slot, at, :]                        # [chunk, width]
            seen = (blk * block + c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk), 1)) < length
            logits = jax.lax.dot_general(
                q_ref[...], held, (((1,), (1,)), ((), ())),
                preferred_element_type=F32)               # [heads, chunk]
            logits = jnp.where(seen, logits, NEG_INF)
            m_prev = m_ref[...]                            # [heads, 1]
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            correction = jnp.exp(m_prev - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * correction + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
                p.astype(held.dtype), held[:, :value_dim],
                (((1,), (0,)), ((), ())),
                preferred_element_type=F32)           # [heads, value_dim]
            return carry
        jax.lax.fori_loop(0, pl.cdiv(here, chunk), attend_chunk, None)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, blocks, attend_block, slot_ref[0])
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_dim", "block_pages"))
def _latent_attend_pallas(q, pool, lengths, tables, *, value_dim: int,
                          block_pages=None):
    """The kernel. q [rows, heads, width] SCALED and in the pool's type;
    lengths [rows] tokens to attend, >= 1 (a dead row: 1, on the null
    page). `block_pages` is the tests' override of `_block_pages`. Jitted
    so that a model's layers share ONE trace of the kernel's body. Returns
    [rows, heads, value_dim] float32."""
    rows, heads, width = q.shape
    _, _, page_size, _ = pool.shape
    pages_per_row = tables.shape[1]
    chunk = max(page_size, _CHUNK_TOKENS)
    if block_pages is None:
        block_pages = _block_pages(page_size, width, pages_per_row,
                                   pool.dtype.itemsize, chunk)
    block = block_pages * page_size
    chunk = min(chunk, block)
    if chunk % page_size or block % chunk:
        raise ValueError(f"pages of {page_size} tokens do not tile chunks "
                         f"of {chunk} in a block of {block}")
    stat = pltpu.VMEM((heads, 1), F32)
    return pl.pallas_call(
        functools.partial(_kernel, block_pages=block_pages, chunk=chunk,
                          pages_per_row=pages_per_row, value_dim=value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((None, heads, width),
                                   lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, heads, value_dim),
                                   lambda r, *_: (r, 0, 0)),
            grid=(rows,),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                stat, stat,
                pltpu.VMEM((heads, value_dim), F32)]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, value_dim), F32),
        # a row's last block starts the next row's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="latent_attention",
    # no row reads past its table, as none does in the gather fallback
    )(jnp.minimum(lengths, pages_per_row * page_size), tables.reshape(-1),
      q, pool)


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
