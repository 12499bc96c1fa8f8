"""Attention kernels.

The hot op of every transformer in the framework. Tiers:

1. `attention_reference` — naive O(S^2)-memory jnp implementation; the
   numerical ground truth for tests.
2. `attention_chunked` — blockwise online-softmax attention via lax.scan
   (memory-efficient attention): O(S * chunk) memory, differentiable,
   runs on any backend.
3. Pallas TPU flash attention, forward AND backward:
   - forward: tiled online softmax, fp32 accumulators in VMEM scratch,
     causal block skipping, GQA via kv-head index mapping; emits the
     per-row logsumexp (LSE) residual.
   - backward: two-pass flash backward — kernel A recomputes P per tile and
     accumulates dK/dV over the query blocks; kernel B accumulates dQ over
     the kv blocks. No S^2 tensor is ever materialized.
   On non-TPU backends the same kernels run in Pallas interpret mode for
   tests; `flash_attention` dispatches to (2) when shapes don't fit the
   kernel constraints or offsets are used (ring attention's rotating chunks
   handle their own masking).
4. `attend_cache` — new positions over a DENSE cache written at a scalar
   index (a prefill chunk, a single-sequence decode): only the blocks the
   cache has filled, a kv head's query heads as rows of one product; a
   Pallas kernel on the TPU for the shapes it takes, an XLA loop elsewhere.

All functions take q/k/v as [batch, heads, seq, head_dim] (BHSD), GQA as
fewer kv heads (num_q_heads % num_kv_heads == 0). `q_offset`/`kv_offset`
shift the causal mask for sequence-parallel callers.
"""

from __future__ import annotations

import collections
import functools
import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Mosaic requires the last dim of every block to be a multiple of the 128-lane
# vector register (or equal the array dim). Per-row statistics (m, l, lse,
# delta) are therefore carried lane-padded as [rows, 128] with all lanes equal
# — the same convention as jax.experimental.pallas.ops.tpu.flash_attention.
NUM_LANES = 128


def _lane_tile(x128, width: int):
    """Expand an all-lanes-equal [rows, 128] stat to [rows, width]."""
    if width % NUM_LANES == 0:
        reps = width // NUM_LANES
        return x128 if reps == 1 else jnp.tile(x128, (1, reps))
    if width < NUM_LANES:
        return x128[:, :width]
    raise NotImplementedError(f"width {width} not a multiple of {NUM_LANES}")


def _validate(q, k, v):
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("q/k/v must be [batch, heads, seq, head_dim]")
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            f"q heads {q.shape[1]} not a multiple of kv heads {k.shape[1]}")


def _expand_kv(q, k, v):
    groups = q.shape[1] // k.shape[1]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    return k, v


def attention_reference(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        q_offset: int = 0, kv_offset: int = 0):
    _validate(q, k, v)
    k, v = _expand_kv(q, k, v)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[2])[:, None]
        k_pos = kv_offset + jnp.arange(k.shape[2])[None, :]
        logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights,
                      v.astype(jnp.float32)).astype(q.dtype)


def attention_chunked(q, k, v, causal: bool = True,
                      sm_scale: Optional[float] = None,
                      q_offset: int = 0, kv_offset: int = 0,
                      chunk_size: int = 512):
    """Blockwise attention: scan over KV chunks with running (m, l, acc)."""
    _validate(q, k, v)
    k, v = _expand_kv(q, k, v)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    chunk = min(chunk_size, sk)
    if sk % chunk != 0:
        return attention_reference(q, k, v, causal, sm_scale, q_offset,
                                   kv_offset)
    n_chunks = sk // chunk
    kc = k.reshape(b, h, n_chunks, chunk, d)
    vc = v.reshape(b, h, n_chunks, chunk, d)
    qf = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inputs):
        m, l, acc = carry
        idx, k_blk, v_blk = inputs
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf,
                            k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = kv_offset + idx * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(logits - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, d), jnp.float32))
    kc_t = jnp.moveaxis(kc, 2, 0)
    vc_t = jnp.moveaxis(vc, 2, 0)
    (m, l, acc), _ = jax.lax.scan(
        step, init, (jnp.arange(n_chunks), kc_t, vc_t))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# Cached positions, and at most how many query rows, a step of
# `attend_cache` takes (on the chip, Mistral's chunk of 256: 1,024 rows a step
# 0.38 ms a 16-layer chunk, 512 rows 0.46, 256 rows 0.60: PERF.md §6, PR 61)
_CACHE_BLOCK = 512
_CACHE_ROWS = 1024


def attend_cache(q, ck, cv, cache_index, positions):
    """`s` new positions of every row over a DENSE cache written at the
    scalar `cache_index`: a prefill chunk, or a single-sequence decode
    (`s` = 1). q [b, heads, s, d], unscaled; ck / cv [b, kv_heads,
    capacity, d], the new rows already written; positions [b, s] (or [s],
    every row's): query i attends the cache positions <= positions[.., i].
    The cache is taken a block of positions at a time with running softmax
    statistics (float32; q · K takes the cache's type and accumulates in
    float32), as many blocks as `cache_index + s` reaches, a value: one
    program whatever the cache has filled. A kv head's group of query
    heads stands as rows of one product against that head's block, so
    neither the logits, nor K or V expanded to the query heads, nor
    anything past the filled span's last block is ever built or read.
    On the TPU, for the shapes `_cache_blocks` takes, the blocks are the
    grid of a kernel (`attend_cache`), which rounds the probabilities to
    the cache's type for P · V as the paged kernel does; elsewhere an XLA
    loop, the plain form, which keeps them float32 (a tiny model's greedy
    tokens are held to the float32 reference's). Returns [b, heads, s, d]
    in q's type."""
    _validate(q, ck, cv)
    b, heads, s, d = q.shape
    kv_heads = ck.shape[1]
    groups = heads // kv_heads
    queries = q.reshape(b, kv_heads, groups * s, d).astype(ck.dtype)
    at = jnp.tile(jnp.broadcast_to(positions, (b, s)), (1, groups))
    blocks = None if _interpret() or _on_many_devices() \
        else _cache_blocks(groups, s, ck.shape[2], d)
    if blocks is None:
        out = _attend_cache_loop(queries, ck, cv, at, cache_index + s)
    else:
        out = _attend_cache_pallas(queries, ck, cv, at, cache_index + s,
                                   *blocks)
    return out.reshape(q.shape).astype(q.dtype)


def _on_many_devices() -> bool:
    """Under a kernel mesh of several devices GSPMD partitions the XLA loop
    on its own and cannot partition a Mosaic call."""
    from ..parallel.mesh import current_kernel_mesh
    mesh = current_kernel_mesh()
    return mesh is not None and mesh.size > 1


def _cache_blocks(groups: int, s: int, capacity: int, d: int
                  ) -> Optional[Tuple[int, int]]:
    """(Query rows, cached positions) a step of the kernel takes: whole
    groups of `s` rows up to `_CACHE_ROWS`, and the longest of
    `_CACHE_BLOCK`, its half and its quarter that the capacity is whole
    blocks of. None for shapes the kernel does not take (a decode token's
    few rows, heads that are no whole lanes)."""
    if d % NUM_LANES or s % 16 or s > _CACHE_ROWS:
        return None
    fits = [n for n in (_CACHE_BLOCK, _CACHE_BLOCK // 2, _CACHE_BLOCK // 4)
            if n % NUM_LANES == 0 and capacity % n == 0]
    if not fits:
        return None
    whole = max(k for k in range(1, groups + 1)
                if groups % k == 0 and k * s <= _CACHE_ROWS)
    return whole * s, fits[0]


def _attend_cache_loop(queries, ck, cv, at, filled):
    """queries [b, kv_heads, rows, d] in the cache's type, at [b, rows] the
    last position each row attends, `filled` the positions written:
    [b, kv_heads, rows, d] float32."""
    b, kv_heads, rows, d = queries.shape
    capacity = ck.shape[2]
    block = min(_CACHE_BLOCK, capacity)
    at = at[:, None, :, None]

    def attend_block(i, carry):
        m, l, acc = carry
        # the last block of a capacity that is no whole number of blocks
        # is moved back over positions the block before it took
        start = jnp.minimum(i * block, capacity - block)
        at_k = start + jnp.arange(block)
        keys = jax.lax.dynamic_slice_in_dim(ck, start, block, axis=2)
        values = jax.lax.dynamic_slice_in_dim(cv, start, block, axis=2)
        # what lies behind the filled span is no number to multiply by 0
        values = jnp.where((at_k < filled)[:, None], values, 0)
        logits = jnp.einsum("bgrd,bgkd->bgrk", queries, keys,
                            preferred_element_type=jnp.float32) * d ** -0.5
        seen = (at_k <= at) & (at_k >= i * block)
        logits = jnp.where(seen, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        correction = jnp.exp(m - m_new)
        return (m_new, l * correction + p.sum(-1, keepdims=True),
                acc * correction + jnp.einsum(
                    "bgrk,bgkd->bgrd", p, values.astype(jnp.float32)))

    stats = (b, kv_heads, rows, 1)
    _, l, acc = jax.lax.fori_loop(
        0, (filled + block - 1) // block, attend_block,
        (jnp.full(stats, NEG_INF, jnp.float32),
         jnp.zeros(stats, jnp.float32),
         jnp.zeros((b, kv_heads, rows, d), jnp.float32)))
    return acc / l


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512


def _interpret():
    """Pallas `interpret=` argument: off on real TPU, TPU-interpreter off-TPU.

    The plain HLO interpreter (`interpret=True`) cannot lower `program_id`
    on CPU; `pltpu.InterpretParams` simulates the Mosaic grid/DMA
    semantics on any backend and is the supported test path.
    """
    if jax.default_backend() == "tpu":
        return False
    return pltpu.InterpretParams()


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scratch, l_scratch,
                acc_scratch, *, sm_scale, causal, block_q, block_k):
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        d = v.shape[-1]
        m_prev = m_scratch[:]                               # [bq, 128]
        m_blk = jnp.max(logits, axis=-1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, m_blk)                  # [bq, 128]
        p = jnp.exp(logits - _lane_tile(m_new, block_k))
        correction = jnp.exp(m_prev - m_new)                # [bq, 128]
        m_scratch[:] = m_new
        l_scratch[:] = l_scratch[:] * correction + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * _lane_tile(correction, d) + \
            jax.lax.dot(p, v, preferred_element_type=jnp.float32)

    if causal:
        @pl.when(kb * block_k <= qb * block_q + block_q - 1)
        def _go():
            _compute()
    else:
        _compute()

    @pl.when(kb == nk - 1)
    def _finalize():
        d = o_ref.shape[-1]
        l_final = jnp.maximum(l_scratch[:], 1e-30)          # [bq, 128]
        o_ref[0] = (acc_scratch[:] / _lane_tile(l_final, d)).astype(
            o_ref.dtype)
        lse_ref[0] = m_scratch[:] + jnp.log(l_final)        # [bq, 128]


def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_scratch, dv_scratch,
                   *, sm_scale, causal, block_q, block_k):
    """Grid (bh, nk, nq): for one kv tile, accumulate dK/dV over q tiles."""
    qb = pl.program_id(2)
    nq = pl.num_programs(2)

    kb = pl.program_id(1)

    @pl.when(qb == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)        # [bq, d]
        lse = _lane_tile(lse_ref[0], block_k)     # [bq, bk]
        delta = _lane_tile(delta_ref[0], block_k)  # [bq, bk]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                       # [bq, bk]
        dv_scratch[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # p^T do -> [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bk]
        ds = p * (dp - delta) * sm_scale
        dk_scratch[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # ds^T q -> [bk, d]

    if causal:
        @pl.when(qb * block_q + block_q - 1 >= kb * block_k)
        def _go():
            _compute()
    else:
        _compute()

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_q_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, dq_scratch, *, sm_scale, causal, block_q, block_k):
    """Grid (bh, nq, nk): for one q tile, accumulate dQ over kv tiles."""
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = _lane_tile(lse_ref[0], block_k)
        delta = _lane_tile(delta_ref[0], block_k)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scratch[:] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32)

    if causal:
        @pl.when(kb * block_k <= qb * block_q + block_q - 1)
        def _go():
            _compute()
    else:
        _compute()

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scratch[:].astype(dq_ref.dtype)


def _cache_kernel(filled_ref, q_ref, at_ref, k_ref, v_ref, o_ref, m_scratch,
                  l_scratch, acc_scratch, *, sm_scale, block_k):
    """One (kv head, block of query rows) over the cache's blocks in turn:
    `_fwd_kernel`'s statistics, the products in the cache's type, the mask
    from each row's own last position, and nothing computed for a block
    behind the filled span (its index maps to the last one needed, so
    nothing is copied for it either)."""
    kb = pl.program_id(2)
    filled = filled_ref[0]

    @pl.when(kb == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    @pl.when(kb * block_k < filled)
    def _compute():
        k, v = k_ref[0], v_ref[0]
        logits = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(k_pos <= at_ref[0], logits, NEG_INF)
        # what lies behind the filled span is no number to multiply by 0
        written = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, v.shape, 0) < filled
        v = jnp.where(written, v, jnp.zeros_like(v))
        d = v.shape[-1]
        m_prev = m_scratch[:]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - _lane_tile(m_new, block_k))
        correction = jnp.exp(m_prev - m_new)
        m_scratch[:] = m_new
        l_scratch[:] = l_scratch[:] * correction + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * _lane_tile(correction, d) + \
            jax.lax.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        d = o_ref.shape[-1]
        o_ref[0] = (acc_scratch[:] / _lane_tile(l_scratch[:], d)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def _attend_cache_pallas(queries, ck, cv, at, filled, block_q: int,
                         block_k: int):
    """`_attend_cache_loop`'s arguments and result (in the cache's type),
    as a kernel over (row x kv head, blocks of `block_q` query rows, blocks
    of `block_k` cached positions). Jitted so that a model's layers share
    ONE trace of the kernel's body, as `_paged_attend_pallas`: traced a
    layer at a time, 16 layers x 4 buckets added ~5 s to every start of
    the chat cell's replica (PERF.md §6, PR 61)."""
    b, kv_heads, rows, d = queries.shape
    capacity = ck.shape[2]

    def q_index(h, qb, kb, filled):
        return (h, qb, 0)

    def kv_index(h, qb, kb, filled):
        return (h, jnp.minimum(kb, (filled[0] - 1) // block_k), 0)

    out = pl.pallas_call(
        functools.partial(_cache_kernel, sm_scale=d ** -0.5,
                          block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * kv_heads, rows // block_q, capacity // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_index),
                pl.BlockSpec((1, block_q, 1), lambda h, qb, kb, filled:
                             (h // kv_heads, qb, 0)),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, d), kv_index)],
            out_specs=pl.BlockSpec((1, block_q, d), q_index),
            scratch_shapes=[
                pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b * kv_heads, rows, d), ck.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="attend_cache",
    )(jnp.reshape(filled, (1,)).astype(jnp.int32),
      queries.reshape(b * kv_heads, rows, d),
      at.astype(jnp.int32)[:, :, None],
      ck.reshape(b * kv_heads, capacity, d),
      cv.reshape(b * kv_heads, capacity, d))
    return out.reshape(b, kv_heads, rows, d)


def _kernel_params(sq: int, sk: int, d: int):
    block_q = min(DEFAULT_BLOCK_Q, sq)
    block_k = min(DEFAULT_BLOCK_K, sk)
    return block_q, block_k


def _pallas_ok(q, k) -> bool:
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _kernel_params(sq, sk, d)
    return (sq % block_q == 0 and sk % block_k == 0
            and block_q % 128 == 0 and block_k % 128 == 0
            and (d % NUM_LANES == 0 or (d < NUM_LANES and d % 8 == 0)))


def _flash_fwd_pallas(q, k, v, causal, sm_scale
                      ) -> Tuple[jax.Array, jax.Array]:
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    groups = h // hk
    block_q, block_k = _kernel_params(sq, sk, d)
    grid = (b * h, sq // block_q, sk // block_k)

    def q_index(bh, qb, kb):
        return (bh, qb, 0)

    def kv_index(bh, qb, kb):
        return ((bh // h) * hk + (bh % h) // groups, kb, 0)

    def lse_index(bh, qb, kb):
        return (bh, qb, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, NUM_LANES), lse_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, NUM_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
            pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="flash_fwd",
    )(q.reshape(b * h, sq, d), k.reshape(b * hk, sk, d),
      v.reshape(b * hk, sk, d))
    return out.reshape(b, h, sq, d), lse[..., 0]


def _flash_bwd_pallas(q, k, v, out, lse, g, causal, sm_scale):
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    groups = h // hk
    block_q, block_k = _kernel_params(sq, sk, d)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hk, sk, d)
    vf = v.reshape(b * hk, sk, d)
    dof = g.reshape(b * h, sq, d)
    of = out.reshape(b * h, sq, d)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)  # [bh, sq]
    # Lane-pad per-row stats to [bh, sq, 128] for legal Mosaic block tiles.
    lse = jnp.broadcast_to(lse[..., None], (b * h, sq, NUM_LANES))
    delta = jnp.broadcast_to(delta[..., None], (b * h, sq, NUM_LANES))

    def q_index(bh, a, c):
        return (bh, a if _Q_MAJOR else c, 0)

    # -- dK/dV pass: grid (bh, nk, nq) ----------------------------------
    def kv_pass():
        def qi(bh, kb, qb):
            return (bh, qb, 0)

        def kvi(bh, kb, qb):
            return ((bh // h) * hk + (bh % h) // groups, kb, 0)

        def li(bh, kb, qb):
            return (bh, qb, 0)

        def dkvi(bh, kb, qb):
            return (bh, kb, 0)

        dk, dv = pl.pallas_call(
            functools.partial(_bwd_kv_kernel, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k),
            grid=(b * h, sk // block_k, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), qi),
                pl.BlockSpec((1, block_k, d), kvi),
                pl.BlockSpec((1, block_k, d), kvi),
                pl.BlockSpec((1, block_q, d), qi),
                pl.BlockSpec((1, block_q, NUM_LANES), li),
                pl.BlockSpec((1, block_q, NUM_LANES), li),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), dkvi),
                pl.BlockSpec((1, block_k, d), dkvi),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
                jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
            name="flash_bwd_kv",
        )(qf, kf, vf, dof, lse, delta)
        return dk, dv

    # -- dQ pass: grid (bh, nq, nk) -------------------------------------
    def q_pass():
        def qi(bh, qb, kb):
            return (bh, qb, 0)

        def kvi(bh, qb, kb):
            return ((bh // h) * hk + (bh % h) // groups, kb, 0)

        def li(bh, qb, kb):
            return (bh, qb, 0)

        dq = pl.pallas_call(
            functools.partial(_bwd_q_kernel, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k),
            grid=(b * h, sq // block_q, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), qi),
                pl.BlockSpec((1, block_k, d), kvi),
                pl.BlockSpec((1, block_k, d), kvi),
                pl.BlockSpec((1, block_q, d), qi),
                pl.BlockSpec((1, block_q, NUM_LANES), li),
                pl.BlockSpec((1, block_q, NUM_LANES), li),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), qi),
            out_shape=jax.ShapeDtypeStruct((b * h, sq, d), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
            name="flash_bwd_q",
        )(qf, kf, vf, dof, lse, delta)
        return dq

    dk, dv = kv_pass()
    dq = q_pass()
    dq = dq.reshape(b, h, sq, d).astype(q.dtype)
    # GQA: per-q-head dK/dV reduce over the group.
    dk = dk.reshape(b, hk, groups, sk, d).sum(axis=2).astype(k.dtype)
    dv = dv.reshape(b, hk, groups, sk, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


_Q_MAJOR = True  # documentation aid for q_index above


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_tpu(q, k, v, causal: bool, sm_scale: float):
    out, _ = _flash_fwd_pallas(q, k, v, causal, sm_scale)
    return out


def _flash_tpu_fwd(q, k, v, causal, sm_scale):
    out, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale)
    return out, (q, k, v, out, lse)


def _flash_tpu_bwd(causal, sm_scale, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal, sm_scale)


_flash_tpu.defvjp(_flash_tpu_fwd, _flash_tpu_bwd)


_KERNEL_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r'op_name="[^"]*[/(]([A-Za-z_]\w*)\)*/pallas_call"')


def pallas_kernels(compiled_text: str) -> Dict[str, int]:
    """Mosaic kernels in a compiled program, counted by the name their
    `pallas_call` was given (`compiled.as_text()` of a program built for
    the TPU). The branches below and in `models.llama` give way to jnp
    paths by shape without a word; this is the trace of which one a
    program really took."""
    return dict(collections.Counter(_KERNEL_CALL.findall(compiled_text)))


def flash_uses_pallas(q, k) -> bool:
    """Will `flash_attention(q, k, ...)` (no offsets) run the Pallas
    kernel here? True on the TPU backend for shapes the kernel takes."""
    return not _interpret() and _pallas_ok(q, k)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    q_offset: int = 0, kv_offset: int = 0,
                    force_pallas: bool = False):
    """Dispatching flash attention, differentiable everywhere."""
    _validate(q, k, v)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if (q_offset == 0 and kv_offset == 0
            and (force_pallas or not _interpret()) and _pallas_ok(q, k)):
        return _flash_tpu(q, k, v, causal, scale)
    return attention_chunked(q, k, v, causal, scale, q_offset, kv_offset)
