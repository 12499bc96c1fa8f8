"""The latent decode kernel as it stood before rows on one document
attended it together (PR 45): a program a ROW, every page of every row
copied; its compute step's length is the module's (`_CHUNK_TOKENS`, and
the loop body is the grouped kernel's), so a row's sums are taken in the
grouped kernel's order. What `tests/test_sarvam_mla.py` holds the grouped
kernel against, bit for bit; nothing else imports it."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, _interpret
from ray_tpu.ops.latent_attention import _CHUNK_TOKENS, F32, _block_pages


def _rowwise_kernel(lengths_ref, tables_ref, q_ref, pool_hbm, o_ref, buf, sems,
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
def rowwise_latent_attend(q, pool, lengths, tables, *, value_dim: int,
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
        functools.partial(_rowwise_kernel, block_pages=block_pages,
                          chunk=chunk, pages_per_row=pages_per_row,
                          value_dim=value_dim),
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
