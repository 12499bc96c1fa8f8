"""Learned sparse attention over a paged cache: an indexer scores every
cached token of a row, the `top_k` largest scores are selected, and the
softmax attends the selected tokens alone (DeepSeek-Sparse-Attention's
lightning indexer on a grouped-query layer).

    I[t, s] = sum_h w[t, h] relu(qI[t, h] . kI[s])          s <= t
    S_t     = the top_k positions of largest I[t, .], ties to the earlier
              position; every position while t < top_k
    o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, g(h)]) v[s, g(h)]

Three pools a layer, all addressed by one block table: K and V token-major,
`[1, pages, page_size, kv_heads * head_dim]` (a token's K is ONE contiguous
row, so a gather of selected tokens moves one piece a token and not one a
kv head), and the index keys `[1, pages, page_size, lanes]`, `lanes` the
index key in whole 128-lane tiles (the pad lanes zero in keys and queries).

What is here, each with a `reference=True` form in plain whole-array jnp
where the two differ:

(a) `paged_index_scores`: a decode batch's scores over each row's pages.
    On the chip ONE Pallas kernel, a program a row: it walks the row's
    block table as far as the row's own length, copies the pages of a
    block from the pool in HBM into one of two slots of fast memory (a
    copy a page; the next block's in flight while this one is scored,
    across blocks and across rows) and scores the block where it lands,
    `q . keys^T` a step of `_SCORE_BLOCK_TOKENS` with the tokens on the
    lanes, so a row's scores are written lane-dense. Elsewhere (the CPU)
    an XLA loop over blocks of gathered pages, as many as the longest row
    reaches. `sparse_kernel` names the path a decode program holds.
(b) `select_top_k`: the exact selection of a decode batch. The `top_k`-th
    largest score of a row is found by bisection on the scores' bit
    patterns (32 counting passes, no sort), ties at it are kept in order of
    position, and the kept positions are compacted by a running count.
    Exact: `approx_max_k` would be another model.
(c) `sparse_attend`: the selected tokens' K and V gathered from the pools a
    token a piece, `[rows, top_k, kv_heads * head_dim]`, and the softmax
    over them with a kv head read as a slice of `head_dim` lanes of the
    rows as the gather leaves them: the gathered rows are never split into
    `[rows, top_k, kv_heads, head_dim]`, which the chip would relay out,
    a whole gathered pool a layer. The rows' places in the pools come from
    the block table by a one-hot product (`pool_rows`), not by a gather of
    page ids. XLA gathers of the rows, not a kernel: seen as
    `[tokens, kv_heads * head_dim]` a bf16 pool stands in HBM in tiles of
    8 token rows, the chip's compiler refuses a copy of one row, and a
    kernel that copies the aligned 8 rows a selected token lies in (8 KB
    for 1 KB wanted, 20 ns a copy) is slower than the gather (PERF.md
    section 6, PR 51: the probe).
(d) `sparse_attend_chunk`: a prefill chunk of one row. A chunk's queries
    cannot gather `top_k` tokens each (chunk x top_k x 2 KB), so the chunk
    is scored against the row's pages, each query's `top_k`-th score is its
    threshold, and the pages are attended in blocks under that mask with
    running softmax statistics.

All but (a)'s kernel is XLA: one program for rows of any length (a row
under `top_k` tokens selects all it has, by data)."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret

F32 = jnp.float32
NUM_LANES = 128
NEG_INF = -1e30
# cached tokens a step takes: of index keys (the XLA loop's block and the
# kernel's product: float32 scores [heads, step], 32 vector registers at 16
# heads), and of K and V under a chunk's mask (whose logits [chunk, heads,
# block] stand whole)
_SCORE_BLOCK_TOKENS = 2048
_ATTEND_BLOCK_TOKENS = 1024
# Scoring steps a copy block of the kernel holds in one of its two slots
# (1 MB a slot at 128 lanes of bf16; PERF.md section 6, PR 50: the probe
# that chose it).
_COPY_BLOCK_STEPS = 2
# Rows of a packed bf16 tile: a page lands on whole tiles of a slot, and
# the queries' heads are whole tiles.
_PACKED_ROWS = 16
# Rows of a float32 tile: so many rows' scores leave the kernel together.
_SUBLANES = 8
# Groups of 128 columns up to which `positions_of` takes a slot's row of
# running counts by a one-hot product and not by a gather. In the Keye
# cell's decode step on the chip, 48 rows x 2048 slots, `dsa/select` a
# layer, gather -> product: 0.397 -> 0.295 ms at 528 groups (the cell's
# 66,304 tokens of table), 0.795 -> 0.749 at 1056, 0.843 -> 0.835 at 1536
# (PERF.md section 6, PR 57): the product's gain, 0.102, 0.046 and 0.008
# ms, is spent near 1,600 groups.
_ONE_HOT_GROUPS = 1536


def sparse_kernel(reference: bool, page_size: int, lanes: int) -> str:
    """The path a decode step built with `reference` holds for index pools
    of such pages: "reference", "pallas" or "xla". The kernel copies whole
    pages into whole tiles and scores them in steps of
    `_SCORE_BLOCK_TOKENS`. It is the scoring's path: the gather-and-attend
    behind it is the same XLA form on every backend."""
    if reference:
        return "reference"
    if (jax.default_backend() == "tpu" and lanes % NUM_LANES == 0
            and page_size % _PACKED_ROWS == 0
            and _SCORE_BLOCK_TOKENS % page_size == 0):
        return "pallas"
    return "xla"


def _pad_pages(tables, block_pages: int):
    """Whole blocks of pages along the last axis (the null page behind):
    a slice that ran past the table would be moved back."""
    pad = -tables.shape[-1] % block_pages
    return jnp.pad(tables, [(0, 0)] * (tables.ndim - 1) + [(0, pad)])


def _weighted_relu(products, w):
    """sum_h w[.., h] relu(products[.., h, s]) -> [.., s], float32."""
    return (jax.nn.relu(products) * w[..., None]).sum(-2)


def paged_index_scores(q, w, pool, lengths, tables, *,
                       reference: bool = False):
    """(a) q [rows, heads, lanes], w [rows, heads] float32 (the scale
    folded in), pool [1, pages, page_size, lanes], lengths [rows] the
    cached tokens a row scores (its newest among them), tables [rows,
    pages_per_row]. Returns [rows, ctx] float32, ctx the table in whole
    blocks; what stands at positions >= lengths means nothing (the
    selection is told the lengths). The products take the pool's type and
    accumulate in float32."""
    rows, _, lanes = q.shape
    page_size = pool.shape[2]
    block_pages = max(1, _SCORE_BLOCK_TOKENS // page_size)
    block = block_pages * page_size
    tables = _pad_pages(tables, block_pages)
    q = q.astype(pool.dtype)
    if sparse_kernel(reference, page_size, lanes) == "pallas":
        return _index_scores_pallas(q, w, pool, lengths, tables)

    def scores_of(ids):
        keys = pool[0][ids].reshape(rows, -1, lanes)
        return _weighted_relu(jnp.einsum(
            "rhd,rtd->rht", q, keys, preferred_element_type=F32), w)

    if reference:
        return scores_of(tables)

    def score_block(b, scores):
        ids = jax.lax.dynamic_slice_in_dim(tables, b * block_pages,
                                           block_pages, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            scores, scores_of(ids), b * block, axis=1)

    return jax.lax.fori_loop(
        0, (lengths.max() + block - 1) // block, score_block,
        jnp.zeros((rows, tables.shape[1] * page_size), F32))


def _scores_kernel(lengths_ref, tables_ref, q_ref, w_ref, pool_hbm, o_ref,
                   buf, sems, slot_ref, *, block_pages: int, step: int,
                   pages_per_row: int):
    """One row. lengths_ref [rows] tokens to score (>= 1), tables_ref [rows *
    pages_per_row] in SMEM; q_ref [heads, lanes] and w_ref [heads, 1] the
    row's; pool_hbm the pool; o_ref [8, ctx] the scores of the eight rows
    this one stands among (it stays in fast memory while they run: a row
    writes its own sublane, the first of them zeroes the eight); buf [2,
    block, lanes]; sems [2] (by slot); slot_ref [1] the slot the row's first
    block is in."""
    row, rows = pl.program_id(0), pl.num_programs(0)
    page_size = pool_hbm.shape[2]
    block = block_pages * page_size
    own = pl.ds(row % _SUBLANES, 1)

    def pages_of(r):
        return pl.cdiv(lengths_ref[r], page_size)

    pages = pages_of(row)
    blocks = pl.cdiv(pages, block_pages)

    def page_copy(page, j, slot):
        to = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
        return pltpu.make_async_copy(pool_hbm.at[0, page], buf.at[slot, to],
                                     sems.at[slot])

    def each_page(count, one):
        """`one(j)` for the pages 0 .. count of a block; a whole block's
        unrolled, so that its copies are issued back to back."""
        @pl.when(count == block_pages)
        def _whole():
            jax.lax.fori_loop(0, block_pages, one, None, unroll=True)

        @pl.when(count != block_pages)
        def _part():
            jax.lax.fori_loop(0, count, one, None)

    def start(r, first, count, slot):
        """Start the copies of pages first .. first + count of row `r`'s
        table."""
        base = r * pages_per_row + first
        each_page(count, lambda j, _: page_copy(
            tables_ref[base + j], j, slot).start())

    def wait(count, slot):
        # (a wait needs the copy's size, not its source: a whole block's
        # copies are waited for as one of a slot's size)
        @pl.when(count == block_pages)
        def _whole():
            pltpu.make_async_copy(buf.at[1 - slot], buf.at[slot],
                                  sems.at[slot]).wait()

        @pl.when(count != block_pages)
        def _part():
            jax.lax.fori_loop(
                0, count, lambda j, _: page_copy(0, j, slot).wait(), None)

    @pl.when(row == 0)
    def _first():
        # a row's last step reads past its pages: what stands there is
        # written as scores that mean nothing, and must be finite
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, jnp.minimum(block_pages, pages), 0)

    @pl.when(row % _SUBLANES == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    q, w = q_ref[...], w_ref[...]
    after = jnp.minimum(row + 1, rows - 1)
    follows = row + 1 < rows
    count_after = jnp.minimum(block_pages, pages_of(after))

    def score_block(b, slot):
        begin = b * block_pages
        count = jnp.minimum(block_pages, pages - begin)
        ends = b + 1 == blocks

        @pl.when(jnp.logical_not(ends) | follows)
        def _prefetch():
            start(jnp.where(ends, after, row),
                  jnp.where(ends, 0, begin + block_pages),
                  jnp.where(ends, count_after, jnp.minimum(
                      block_pages, pages - begin - block_pages)),
                  1 - slot)

        wait(count, slot)

        def score_step(c, carry):
            at = pl.multiple_of(c * step, step)
            products = jax.lax.dot_general(
                q, buf[slot, pl.ds(at, step), :], (((1,), (1,)), ((), ())),
                preferred_element_type=F32)                # [heads, step]
            o_ref[own, pl.ds(pl.multiple_of(b * block + at, step), step)] = (
                jnp.maximum(products, 0.0) * w).sum(0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(count * page_size, step), score_step,
                          None)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, blocks, score_block, slot_ref[0])


@jax.jit
def _index_scores_pallas(q, w, pool, lengths, tables):
    """The kernel. q [rows, heads, lanes] in the pool's type, w [rows,
    heads] float32, lengths [rows] tokens to score, tables [rows,
    pages_per_row] in whole scoring blocks of pages. Jitted so that a
    model's layers share ONE trace of the kernel's body. Returns [rows,
    pages_per_row * page_size] float32: zero behind a row's last step."""
    rows, heads, lanes = q.shape
    page_size = pool.shape[2]
    pages_per_row = tables.shape[1]
    ctx = pages_per_row * page_size
    block = _COPY_BLOCK_STEPS * _SCORE_BLOCK_TOKENS
    # (heads in whole tiles: a head of zeros with a weight of zero adds 0)
    pad = ((0, 0), (0, -heads % _PACKED_ROWS))
    q, w = jnp.pad(q, pad + ((0, 0),)), jnp.pad(w.astype(F32), pad)
    heads = q.shape[1]
    return pl.pallas_call(
        functools.partial(_scores_kernel, block_pages=block // page_size,
                          step=_SCORE_BLOCK_TOKENS,
                          pages_per_row=pages_per_row),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((None, heads, lanes),
                                   lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec((None, heads, 1), lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((_SUBLANES, ctx),
                                   lambda r, *_: (r // _SUBLANES, 0)),
            grid=(rows,),
            scratch_shapes=[pltpu.VMEM((2, block, lanes), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct(
            (-(-rows // _SUBLANES) * _SUBLANES, ctx), F32),
        # a row's last block starts the next row's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="dsa_index_scores",
    # no row reads past its table, as none does in the XLA loop
    )(jnp.clip(lengths, 1, ctx), tables.reshape(-1), q, w[..., None],
      pool)[:rows]


def ordered_bits(scores):
    """float32 -> uint32 that orders as the floats do (0.0 and -0.0 alike),
    above 0 for every finite score: 0 is left for "no candidate"."""
    bits = jax.lax.bitcast_convert_type(scores.astype(F32), jnp.int32)
    bits = jnp.where(scores == 0, 0, bits)
    bits = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def kth_largest(u, k: int):
    """u [n, m] uint32, 0 where there is no candidate. The largest T with
    count(u >= T) >= k, bit by bit from the top: 32 passes that compare
    and count; 0 for a row with fewer than k candidates. [n] uint32."""
    def settle_bit(i, t):
        bit = jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        enough = (u >= (t | bit)[:, None]).sum(-1) >= k
        return jnp.where(enough, t | bit, t)

    return jax.lax.fori_loop(0, 32, settle_bit,
                             jnp.zeros(u.shape[:1], jnp.uint32))


def _group_counts(flags):
    """bool [n, m] in groups of 128 lanes: (the inclusive running count
    within each group [n, groups, 128], the groups' totals [n, groups]),
    float32. A product with a triangle of ones (exact: the operands are 0
    and 1, the sums float32 under 2^24): the TPU has no fast scan over 66k
    lanes."""
    n, m = flags.shape
    groups = jnp.pad(flags, ((0, 0), (0, -m % NUM_LANES))).reshape(
        n, -1, NUM_LANES)
    triangle = (jnp.arange(NUM_LANES)[:, None]
                <= jnp.arange(NUM_LANES)[None, :]).astype(jnp.bfloat16)
    within = jnp.einsum("ngl,lm->ngm", groups.astype(jnp.bfloat16),
                        triangle, preferred_element_type=F32)
    return within, within[..., -1]


def running_count(flags):
    """Inclusive running count of bool [n, m] along m, int32."""
    within, totals = _group_counts(flags)
    before = jnp.cumsum(totals, axis=-1) - totals
    return (within + before[..., None]).astype(jnp.int32).reshape(
        flags.shape[0], -1)[:, :flags.shape[1]]


class Threshold(NamedTuple):
    """A row's selection as a rule over its scores' bits: keep u > t, and
    of those with u == t the `need` earliest."""
    t: jax.Array        # [n, 1] uint32
    need: jax.Array     # [n, 1] int32


def threshold_of(u, k: int) -> Threshold:
    t = kth_largest(u, k)[:, None]
    return Threshold(t, k - (u > t).sum(-1, keepdims=True).astype(jnp.int32))


def kept(u, rule: Threshold, ties_before=0):
    """The candidates the rule keeps, bool like u; `ties_before` [n, 1]:
    candidates at the threshold that stand before u's first column. The
    running count is made only where some row has more ties than it
    needs."""
    tied = (u == rule.t) & (u > 0)
    spare = tied.sum(-1, keepdims=True) + ties_before > rule.need
    return (u > rule.t) | jax.lax.cond(
        spare.any(),
        lambda: tied & (running_count(tied) + ties_before <= rule.need),
        lambda: tied)


def candidate_bits(scores, lengths):
    """`ordered_bits` of a decode batch's scores [rows, ctx], 0 at and
    behind each row's length."""
    seen = jnp.arange(scores.shape[1])[None, :] < lengths[:, None]
    return jnp.where(seen, ordered_bits(scores), jnp.uint32(0))


def positions_of(keep, k: int):
    """The first k kept columns of bool [n, m] as positions [n, k] int32,
    ascending; m where a row keeps fewer. Slot j's group of 128 lanes is
    the count of groups whose running total is <= j, its lane the count of
    that group's lanes whose running count is <= j's rank in the group:
    comparisons, sums and the group's 128-lane row of running counts a
    slot (a binary search a slot gathers single elements, 17 times, and
    took 17 ms a layer on the chip where this takes under one). The row is
    taken by a product of the slot's one-hot over the groups with the
    counts (exact: a count is <= 128, whole in bf16, and each sum holds
    one of them) up to `_ONE_HOT_GROUPS` groups, by ONE gather of a row a
    slot above: the product grows with the groups, the gather does not."""
    n, m = keep.shape
    within, totals = _group_counts(keep)
    upto = jnp.cumsum(totals, axis=-1)                       # [n, groups]
    groups = upto.shape[1]
    slot = jnp.arange(k, dtype=F32)[None, :, None]
    passed = upto[:, None, :] <= slot                        # [n, k, groups]
    group = jnp.minimum(passed.sum(-1), groups - 1)
    rank = slot[..., 0] - jnp.where(passed, totals[:, None, :], 0).sum(-1)
    if groups <= _ONE_HOT_GROUPS:
        here = group[..., None] == jnp.arange(groups)
        rows = jnp.einsum("nkg,ngl->nkl", here.astype(jnp.bfloat16),
                          within.astype(jnp.bfloat16),
                          preferred_element_type=F32)        # [n, k, 128]
    else:
        rows = within.reshape(n * groups, NUM_LANES)[
            group + jnp.arange(n)[:, None] * groups]
    at = group * NUM_LANES + (rows <= rank[..., None]).sum(-1)
    return jnp.where(slot[..., 0] < upto[:, -1:], at, m).astype(jnp.int32)


def select_top_k(scores, lengths, k: int, *, reference: bool = False):
    """(b) scores [rows, ctx] float32, lengths [rows] the positions that
    are candidates. Returns (positions [rows, k'] int32 ascending, count
    [rows] int32), k' = min(k, ctx): the `count` = min(length, k) selected
    positions first, 0 behind them."""
    rows, ctx = scores.shape
    k = min(k, ctx)
    count = jnp.minimum(lengths, k).astype(jnp.int32)
    slot = jnp.arange(k)[None, :] < count[:, None]
    if reference:
        seen = jnp.arange(ctx)[None, :] < lengths[:, None]
        # `top_k` puts the lower index first among equals; the candidates
        # come before the -inf of a row shorter than k
        _, at = jax.lax.top_k(jnp.where(
            seen, jnp.where(scores == 0, 0.0, scores), -jnp.inf), k)
        at = jnp.sort(jnp.where(slot, at, ctx), axis=-1)
    else:
        u = candidate_bits(scores, lengths)
        at = positions_of(kept(u, threshold_of(u, k)), k)
    return jnp.where(slot, at, 0).astype(jnp.int32), count


def pool_rows(tables, positions, page_size: int, tokens: int):
    """tables [rows, pages_per_row] page ids, positions [rows, k] in [0,
    pages_per_row * page_size): the row of a pool of `tokens` token rows
    (pages * page_size) at which each position's token stands, `tables[r,
    p // page_size] * page_size + p % page_size`, [rows, k] int32, with no
    gather. The table is read as groups of 128 lanes: a position's lane by
    ONE product of its one-hot `[rows, k, 128]` (bf16) with the table's
    first rows, byte by byte, the groups side by side (a byte is exact in
    bf16, a sum of one byte and zeros exact in float32; as many bytes as
    `tokens - 1` has, so exact for every page a pool of that shape holds),
    its group by a compare over the groups. One form for every width: a
    table 1036 wide is 9 groups x 3 bytes = 27 columns of one pass of the
    matrix unit at K = 128, one 4096 wide 96. `take_along_axis`, the plain
    form `sparse_attend(reference=True)` keeps, is a gather of single
    int32 elements, 98,304 a layer at 10 ns each on the chip: 1.00 ms a
    layer in the Keye cell's decode step where this takes 0.07, the step
    29.66 -> 24.14 ms; a compare-and-sum over the table's whole width read
    24.08 and a one-hot over it 24.12 at this width, both k x width; one
    flat `take` 27.87 (the cell's program under each form on the chip;
    PERF.md section 6, PR 57)."""
    rows, k = positions.shape
    count = max(1, -(-(tokens - 1).bit_length() // 8))
    first = jnp.pad(tables * page_size,
                    ((0, 0), (0, -tables.shape[1] % NUM_LANES))).reshape(
                        rows, -1, NUM_LANES)                  # [rows, g, 128]
    groups = first.shape[1]
    pieces = jnp.stack([(first >> (8 * i)) & 255 for i in range(count)], -1)
    pieces = jnp.transpose(pieces, (0, 2, 1, 3)).reshape(
        rows, NUM_LANES, groups * count).astype(jnp.bfloat16)
    page = positions // page_size
    lane = (page % NUM_LANES)[..., None] == jnp.arange(NUM_LANES)
    group = (page // NUM_LANES)[..., None] == jnp.arange(groups)
    found = jnp.einsum("rkl,rlc->rkc", lane.astype(jnp.bfloat16), pieces,
                       preferred_element_type=F32).reshape(
                           rows, k, groups, count)
    found = jnp.where(group[..., None], found, 0).sum(-2).astype(jnp.int32)
    return sum(found[..., i] << (8 * i) for i in range(count)) \
        + positions % page_size


def sparse_attend(q, k_pool, v_pool, positions, count, tables, *,
                  kv_heads: int, reference: bool = False):
    """(c) q [rows, heads, head_dim] scaled; pools [1, pages, page_size,
    kv_heads * head_dim]; positions [rows, k] of which the first `count`
    [rows] are the row's selection (what stands behind them takes no part;
    a row of `count` 0 gives finite numbers); tables [rows, pages_per_row].
    Each selected token's K and V rows are gathered whole from the pools,
    `[rows, k, kv_heads * head_dim]`, and the softmax runs over them
    (float32; the products take the pools' type and accumulate in float32).
    A kv head is read as a slice of `head_dim` lanes of the rows as the
    gather leaves them; `reference` splits the rows into `[rows, k,
    kv_heads, head_dim]` for ONE product over all kv heads instead, which
    on the chip relays out every gathered row (PERF.md section 6, PR 51).
    A position's row of the pools is found through the block table by
    `pool_rows` (a one-hot product, no gather; one form for every table
    width and every pool), under `reference` by `take_along_axis` on the
    page ids, a gather of single elements: the same int32 to the last
    element. The same sums either way. Returns [rows, heads, head_dim]
    float32."""
    rows, heads, head_dim = q.shape
    page_size = k_pool.shape[2]
    if reference:
        page = jnp.take_along_axis(tables, positions // page_size, axis=1)
        token = page * page_size + positions % page_size      # [rows, k]
    else:
        token = pool_rows(tables, positions, page_size,
                          k_pool.shape[1] * page_size)
    keys, values = (pool.reshape(-1, pool.shape[-1])[token]
                    for pool in (k_pool, v_pool))
    queries = q.reshape(rows, kv_heads, heads // kv_heads,
                        head_dim).astype(k_pool.dtype)
    live = jnp.arange(positions.shape[1])[None, :] < count[:, None]

    def probs_of(logits, live):
        return jax.nn.softmax(jnp.where(live, logits, NEG_INF),
                              axis=-1).astype(v_pool.dtype)

    if reference:
        keys, values = (x.reshape(rows, -1, kv_heads, head_dim)
                        for x in (keys, values))
        probs = probs_of(jnp.einsum("rgjd,rkgd->rgjk", queries, keys,
                                    preferred_element_type=F32),
                         live[:, None, None])
        out = jnp.einsum("rgjk,rkgd->rgjd", probs, values,
                         preferred_element_type=F32)
    else:
        def head(g):
            lanes = slice(g * head_dim, (g + 1) * head_dim)
            probs = probs_of(jnp.einsum(
                "rjd,rkd->rjk", queries[:, g], keys[..., lanes],
                preferred_element_type=F32), live[:, None])
            return jnp.einsum("rjk,rkd->rjd", probs, values[..., lanes],
                              preferred_element_type=F32)

        out = jnp.stack([head(g) for g in range(kv_heads)], axis=1)
    return out.reshape(rows, heads, head_dim)


def chunk_candidates(qi, w, index_pool, table, start):
    """A prefill chunk's scores against its row's pages, as candidate
    bits: qi [chunk, heads, lanes], w [chunk, heads] float32, table
    [pages_per_row], query i at position start + i (its own key already
    written). Returns u [chunk, ctx] uint32, `ordered_bits` of I[i, s] at
    s <= start + i and 0 elsewhere; a block of pages at a time, as many as
    the chunk's last position reaches."""
    chunk, _, lanes = qi.shape
    page_size = index_pool.shape[2]
    block_pages = max(1, _SCORE_BLOCK_TOKENS // page_size)
    block = block_pages * page_size
    table = _pad_pages(table, block_pages)
    qi = qi.astype(index_pool.dtype)
    at = start + jnp.arange(chunk)[:, None]

    def score_block(b, u):
        ids = jax.lax.dynamic_slice_in_dim(table, b * block_pages,
                                           block_pages)
        keys = index_pool[0][ids].reshape(block, lanes)
        scores = _weighted_relu(jnp.einsum(
            "qhd,td->qht", qi, keys, preferred_element_type=F32), w)
        seen = (b * block + jnp.arange(block))[None, :] <= at
        return jax.lax.dynamic_update_slice_in_dim(
            u, jnp.where(seen, ordered_bits(scores), jnp.uint32(0)),
            b * block, axis=1)

    return jax.lax.fori_loop(
        0, (start + chunk + block - 1) // block, score_block,
        jnp.zeros((chunk, table.shape[0] * page_size), jnp.uint32))


def sparse_attend_chunk(q, u, k_pool, v_pool, table, start, *, top_k: int,
                        kv_heads: int):
    """(d) One prefill chunk of ONE row over its pages. q [chunk, heads,
    head_dim] scaled, query i at position start + i, its own K, V and
    index rows already written; u [chunk, ctx] its candidate bits
    (`chunk_candidates`). Query i attends the `top_k` candidates of largest
    score (`threshold_of`: its top_k-th score the threshold, ties to the
    earlier position; every candidate while it has no more than top_k). K
    and V are taken a block of pages at a time under that mask with
    running softmax statistics (float32): nothing gathered a query, no
    logits of the whole context. Returns [chunk, heads, head_dim]
    float32."""
    chunk, heads, head_dim = q.shape
    page_size = k_pool.shape[2]
    block_pages = max(1, _ATTEND_BLOCK_TOKENS // page_size)
    block = block_pages * page_size
    table = _pad_pages(table, block_pages)
    # (the candidates' width is the table's in whole scoring blocks)
    u = jnp.pad(u, ((0, 0), (0, -u.shape[1] % block)))
    rule = threshold_of(u, top_k)
    group = heads // kv_heads
    queries = q.reshape(chunk, kv_heads, group, head_dim).astype(k_pool.dtype)

    def attend_block(b, carry):
        m, l, acc, ties = carry
        ids = jax.lax.dynamic_slice_in_dim(table, b * block_pages,
                                           block_pages)
        keys = k_pool[0][ids].reshape(block, kv_heads, head_dim)
        values = v_pool[0][ids].reshape(block, kv_heads, head_dim)
        here = jax.lax.dynamic_slice_in_dim(u, b * block, block, axis=1)
        keep = kept(here, rule, ties)[:, None, None, :]
        logits = jnp.where(keep, jnp.einsum(
            "qgjd,tgd->qgjt", queries, keys, preferred_element_type=F32),
            NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(logits - m_new), 0.0)
        correction = jnp.exp(m - m_new)
        tied = ((here == rule.t) & (here > 0)).sum(-1, keepdims=True)
        return (m_new, l * correction + p.sum(-1, keepdims=True),
                acc * correction + jnp.einsum(
                    "qgjt,tgd->qgjd", p.astype(v_pool.dtype), values,
                    preferred_element_type=F32),
                ties + tied.astype(jnp.int32))

    stats = (chunk, kv_heads, group, 1)
    _, l, acc, _ = jax.lax.fori_loop(
        0, (start + chunk + block - 1) // block, attend_block,
        (jnp.full(stats, NEG_INF, F32), jnp.zeros(stats, F32),
         jnp.zeros((chunk, kv_heads, group, head_dim), F32),
         jnp.zeros((chunk, 1), jnp.int32)))
    return (acc / l).reshape(chunk, heads, head_dim)


def dense_selection(scores, k: int):
    """A whole sequence with nothing cached: scores [s, s] float32, query
    t's candidates the positions <= t. The mask [s, s] of what each query
    attends, by the same rule."""
    s = scores.shape[0]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    u = jnp.where(causal, ordered_bits(scores), jnp.uint32(0))
    return kept(u, threshold_of(u, k))
