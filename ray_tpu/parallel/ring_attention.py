"""Ring attention: sequence-parallel exact attention over the ICI ring.

Absent from the reference (SURVEY §2d verifies no ring/context/sequence
parallelism exists there); first-class here. Q/K/V are sharded over the
`sequence` mesh axis; each step every device attends its local Q block
against the K/V block currently in hand, accumulates with the online-softmax
merge (numerically exact), then rotates K/V to its ring neighbor with
`ppermute` — overlapping the rotation with compute is XLA's job (the
collective-permute is async on TPU and latency-hides behind the matmuls).

Memory: O(S_local) per device — sequence length scales linearly with ring
size. Causal masking uses global position offsets so the sharded result is
bit-comparable to single-device attention (tests assert this).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _merge_block(q, k, v, m, l, acc, causal, q_off, kv_off, scale):
    """One online-softmax accumulation of q against the (k, v) block.
    q: [b,h,sq,d]; k/v: [b,h,sk,d]; m,l: [b,h,sq]; acc: [b,h,sq,d]."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        q_pos = q_off + jnp.arange(q.shape[2])[:, None]
        k_pos = kv_off + jnp.arange(k.shape[2])[None, :]
        logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
    m_blk = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(logits - m_new[..., None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sequence",
                   causal: bool = True, sm_scale: Optional[float] = None):
    """q/k/v: [batch, heads, seq, head_dim], sharded over seq on `axis_name`.
    Returns attention output with the same sharding. GQA: pass k/v with
    fewer heads; they are expanded before the ring."""
    groups = q.shape[1] // k.shape[1]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    n = mesh.shape[axis_name]
    if n == 1:
        from ..ops.attention import attention_chunked
        return attention_chunked(q, k, v, causal, scale)

    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def _ring(q_blk, k_blk, v_blk):
        b, h, s_local, d = q_blk.shape
        rank = jax.lax.axis_index(axis_name)
        q_off = rank * s_local
        perm = [(j, (j + 1) % n) for j in range(n)]

        def step(i, carry):
            k_cur, v_cur, m, l, acc = carry
            # After i rotations we hold the block produced by rank - i.
            src = (rank - i) % n
            kv_off = src * s_local
            m, l, acc = _merge_block(q_blk, k_cur, v_cur, m, l, acc,
                                     causal, q_off, kv_off, scale)
            k_next = jax.lax.ppermute(k_cur, axis_name, perm)
            v_next = jax.lax.ppermute(v_cur, axis_name, perm)
            return (k_next, v_next, m, l, acc)

        init = (k_blk, v_blk,
                jnp.full((b, h, s_local), NEG_INF, jnp.float32),
                jnp.zeros((b, h, s_local), jnp.float32),
                jnp.zeros((b, h, s_local, d), jnp.float32))
        _, _, m, l, acc = jax.lax.fori_loop(0, n, step, init)
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_blk.dtype)

    return _ring(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "sequence",
                      causal: bool = True,
                      sm_scale: Optional[float] = None):
    """Ulysses/DeepSpeed-style sequence parallelism: all-to-all swaps the
    sharded axis from sequence to heads, runs full-sequence attention
    locally, and swaps back. Two all-to-alls instead of a ring — better when
    heads >> ring size and the interconnect favors bulk all-to-all."""
    groups = q.shape[1] // k.shape[1]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    n = mesh.shape[axis_name]
    if n == 1:
        from ..ops.attention import attention_chunked
        return attention_chunked(q, k, v, causal, scale)
    if q.shape[1] % n != 0:
        raise ValueError(f"heads {q.shape[1]} must divide the "
                         f"{axis_name} axis size {n}")

    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def _ulysses(q_blk, k_blk, v_blk):
        # [b, H, S/n, d] -> [b, H/n, S, d]
        def swap_in(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                      concat_axis=2, tiled=True)

        def swap_out(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                      concat_axis=1, tiled=True)

        from ..ops.attention import attention_chunked
        out = attention_chunked(swap_in(q_blk), swap_in(k_blk),
                                swap_in(v_blk), causal, scale)
        return swap_out(out)

    return _ulysses(q, k, v)
