"""Grouped products: rows sorted by group, each group's matrix read once and
applied to that group's rows alone.

What the routed experts of `models.moe` want above the chip's ridge (a
prefill chunk of hundreds of tokens): the (token, expert) pairs sorted by
expert are the rows, an expert's matrices the group's. `grouped_experts` is
two kernels over one plan of visits (`plan_visits`):

  * `grouped_hidden`: relu(rows @ w_in[g])^2 or, gated, silu(rows @
    w_gate[g]) * (rows @ w_in[g]): gate and up from ONE pass over the rows,
    the activation where the sums land, the result in the matrices' type;
  * `grouped_out`: rows @ w_out[g] in float32.

The kernel's grid is (column tile, visit). A visit is one (group, row tile)
that share a row; a row tile that two groups share is visited by both, each
storing its own rows (a select, never a product by zero). The visits of one
group follow each other, so a group's matrix is copied once a column tile;
the column tile is the whole matrix where two of them fit the kernel's fast
memory, so the rows too are copied once a visit (megablox `gmm`, under the
default 16 MiB, halves the matrix and copies the rows twice: PERF.md section
6, PR 53). No group is ever padded, no row dropped; rows past the last
group's are left as they were found and mean nothing.

Off the TPU the products are `lax.ragged_dot` (`grouped_kernel` says
which), which the chip runs slower than the dense form it would replace.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NUM_LANES, _interpret

F32 = jnp.float32
# Rows of a visit. PERF.md section 6, PR 53: 128 against 64 at the Xing
# chunk's shapes.
ROW_TILE = 128
# What a kernel may take of a v5e's 128 MiB of fast memory, and what of that
# the double-buffered matrices may: the rest is the rows, the result and the
# float32 sums beside them.
_VMEM_LIMIT = 64 << 20
_MATRIX_BUDGET = 40 << 20


def kernel_takes(width: int, mlp_dim: int) -> bool:
    """Matrices [width, mlp_dim] and back that the kernels take: their
    blocks are whole lane tiles."""
    return width % NUM_LANES == 0 and mlp_dim % NUM_LANES == 0


def grouped_kernel(width: int, mlp_dim: int) -> str:
    """The path the grouped products take here for matrices [width,
    mlp_dim] and back: "pallas" or "xla"."""
    if jax.default_backend() == "tpu" and kernel_takes(width, mlp_dim):
        return "pallas"
    return "xla"


class Visits(NamedTuple):
    """`plan_visits`' answer, int32: the row each group starts at (and, last,
    where the last ends) [groups + 1]; each visit's group and row tile
    [visits]; the visits there are [1]. Visits past the count repeat the
    last one: no block moves and nothing is computed."""
    offsets: jax.Array
    group: jax.Array
    tile: jax.Array
    count: jax.Array


def plan_visits(sizes, rows: int) -> Visits:
    """The (group, row tile) pairs that share a row, in the groups' order,
    for groups of `sizes` [groups] rows lying one behind the other from row
    0 of `rows` (whole `ROW_TILE`s; the sizes sum to `rows` at most). There
    are at most rows / ROW_TILE + groups - 1 of them."""
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // ROW_TILE
    tiles = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    count = visit_ends[-1]
    at = jnp.minimum(jnp.arange(rows // ROW_TILE + groups - 1),
                     jnp.maximum(count - 1, 0))
    group = jnp.minimum((visit_ends[None, :] <= at[:, None]).sum(1),
                        groups - 1)
    tile = first[group] + at - (visit_ends - tiles)[group]
    return Visits(
        jnp.concatenate([jnp.zeros((1,), jnp.int32), ends.astype(jnp.int32)]),
        group.astype(jnp.int32), tile.astype(jnp.int32),
        count.astype(jnp.int32).reshape(1))


def _column_tile(depth: int, columns: int, matrices: int,
                 itemsize: int) -> int:
    """The widest whole-lane divisor of `columns` at which `matrices`
    blocks [depth, tile], double-buffered, stay in the budget."""
    best = NUM_LANES
    for tile in range(NUM_LANES, columns + 1, NUM_LANES):
        if columns % tile == 0 and \
                2 * matrices * depth * tile * itemsize <= _MATRIX_BUDGET:
            best = tile
    return best


def _activate(hidden, gate=None):
    """relu(hidden)^2, or silu(gate) * hidden: float32 in, float32 out."""
    if gate is None:
        return jnp.square(jax.nn.relu(hidden))
    return jax.nn.silu(gate) * hidden


def _store_own_rows(out_ref, value, offsets, group, tile):
    """The visit's group's rows of the tile take `value`; the others keep
    what the tile held (another group's, or nothing yet)."""
    row = tile * out_ref.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 0)
    own = (row >= offsets[group]) & (row < offsets[group + 1])
    out_ref[...] = jnp.where(own, value, out_ref[...].astype(F32)).astype(
        out_ref.dtype)


def _hidden_kernel(offsets, group, tile, count, rows_ref, w_in_ref, *refs):
    *w_gate_ref, out_ref = refs       # (w_gate_ref,) or ()
    visit = pl.program_id(1)

    @pl.when(visit < count[0])
    def _():
        rows = rows_ref[...]
        hidden = _activate(*(
            jnp.dot(rows, w_ref[...], preferred_element_type=F32)
            for w_ref in (w_in_ref, *w_gate_ref)))
        _store_own_rows(out_ref, hidden, offsets, group[visit], tile[visit])


def _out_kernel(offsets, group, tile, count, rows_ref, w_ref, out_ref):
    visit = pl.program_id(1)

    @pl.when(visit < count[0])
    def _():
        _store_own_rows(
            out_ref, jnp.dot(rows_ref[...], w_ref[...],
                             preferred_element_type=F32),
            offsets, group[visit], tile[visit])


def _call(kernel, name, rows, matrices, visits: Visits, out_dtype):
    """`kernel` over (column tile, visit): `rows` [n, depth] in row tiles,
    each of `matrices` [groups, depth, columns] in blocks [depth, column
    tile] of the visit's group, the result [n, columns] in the rows' tiles."""
    n, depth = rows.shape
    columns = matrices[0].shape[2]
    column_tile = _column_tile(depth, columns, len(matrices),
                               matrices[0].dtype.itemsize)
    matrix = pl.BlockSpec(
        (None, depth, column_tile),
        lambda c, v, offsets, group, tile, count: (group[v], 0, c))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec(
                (ROW_TILE, depth),
                lambda c, v, offsets, group, tile, count: (tile[v], 0))]
            + [matrix] * len(matrices),
            out_specs=pl.BlockSpec(
                (ROW_TILE, column_tile),
                lambda c, v, offsets, group, tile, count: (tile[v], c)),
            grid=(columns // column_tile, visits.group.shape[0])),
        out_shape=jax.ShapeDtypeStruct((n, columns), out_dtype),
        # a row tile's visits follow each other and store into one block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=name,
    )(*visits, rows, *matrices)


def _hidden_pallas(rows, w_in, w_gate, visits: Visits):
    matrices = (w_in,) if w_gate is None else (w_in, w_gate)
    return _call(_hidden_kernel, "grouped_hidden", rows, matrices, visits,
                 w_in.dtype)


def _out_pallas(rows, w_out, visits: Visits):
    return _call(_out_kernel, "grouped_out", rows, (w_out,), visits, F32)


def grouped_experts(rows, w_in, w_out, sizes, w_gate=None):
    """rows [n, l] sorted by group (n whole `ROW_TILE`s), w_in (and w_gate)
    [groups, l, f], w_out [groups, f, l], sizes [groups] int32 that sum to n
    at most. Returns [n, l] float32: row r of group g holds act_g(rows[r])
    w_out[g], with act_g(x) = relu(x w_in[g])^2 or, with `w_gate`,
    silu(x w_gate[g]) * (x w_in[g]), summed and activated in float32 and
    rounded to the matrices' type before `w_out`. Rows past the groups' mean
    nothing."""
    if grouped_kernel(w_in.shape[1], w_in.shape[2]) == "pallas":
        visits = plan_visits(sizes, rows.shape[0])
        hidden = _hidden_pallas(rows, w_in, w_gate, visits)
        return _out_pallas(hidden.astype(w_out.dtype), w_out, visits)
    hidden = _activate(*(
        jax.lax.ragged_dot(rows, w, sizes, preferred_element_type=F32)
        for w in ((w_in,) if w_gate is None else (w_in, w_gate))))
    return jax.lax.ragged_dot(hidden.astype(w_out.dtype), w_out, sizes,
                              preferred_element_type=F32)
