"""Attention kernel + sequence-parallel correctness tests (CPU 8-dev mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (attend_cache, attention_chunked,
                                   attention_reference, flash_attention)
from ray_tpu.parallel.mesh import MeshConfig
from ray_tpu.parallel.ring_attention import ring_attention, ulysses_attention


def _qkv(b=2, h=4, s=256, d=32, kv_heads=None, seed=0):
    rng = np.random.RandomState(seed)
    kv_heads = kv_heads or h
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(b, kv_heads, s, d), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(b, kv_heads, s, d), jnp.float32) * 0.3
    return q, k, v


def test_chunked_matches_reference_causal():
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=True)
    out = attention_chunked(q, k, v, causal=True, chunk_size=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_chunked_matches_reference_noncausal_gqa():
    q, k, v = _qkv(h=8, kv_heads=2)
    ref = attention_reference(q, k, v, causal=False)
    out = attention_chunked(q, k, v, causal=False, chunk_size=32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_offsets_shift_causal_mask():
    q, k, v = _qkv(s=64)
    # With q_offset = seq, every q position sees all of k.
    ref = attention_reference(q, k, v, causal=True, q_offset=64)
    full = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(ref, full, atol=1e-5)


def test_flash_dispatcher_differentiable():
    q, k, v = _qkv(s=128)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert all(jnp.isfinite(g).all() for g in grads)


def test_pallas_fwd_matches_reference_interpret():
    q, k, v = _qkv(b=1, h=2, s=256, d=32)
    out = flash_attention(q, k, v, True, None, force_pallas=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pallas_fwd_gqa_noncausal_interpret():
    q, k, v = _qkv(b=1, h=4, kv_heads=2, s=256, d=32)
    out = flash_attention(q, k, v, False, None, force_pallas=True)
    ref = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pallas_bwd_matches_reference_interpret():
    q, k, v = _qkv(b=1, h=2, s=256, d=32)

    def loss_pallas(q, k, v):
        return (flash_attention(q, k, v, True, None,
                                force_pallas=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_pallas_bwd_gqa_interpret():
    q, k, v = _qkv(b=1, h=4, kv_heads=2, s=128, d=32)

    def loss_pallas(q, k, v):
        return (flash_attention(q, k, v, True, None,
                                force_pallas=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


# (query heads a kv head, new positions, where they start, rows, the cache's
# type): blocks of 64 positions; a capacity of 5 blocks and 40 (6 and 40 under
# 256 new positions), so the last block is moved back over the one before it
_CACHE_CASES = [
    (1, 1, "start", 1, jnp.float32), (4, 1, "mid", 2, jnp.float32),
    (16, 1, "last", 1, jnp.bfloat16), (1, 1, "mid", 1, jnp.bfloat16),
    (4, 32, "start", 1, jnp.bfloat16), (1, 32, "mid", 2, jnp.bfloat16),
    (16, 32, "last", 2, jnp.float32), (4, 32, "last", 1, jnp.bfloat16),
    (4, 256, "start", 1, jnp.float32), (16, 256, "mid", 1, jnp.bfloat16),
    (1, 256, "last", 2, jnp.float32), (4, 256, "mid", 2, jnp.bfloat16),
]


def _cache_case(groups, s, where, b, dtype, d=16, capacity=None):
    """q, caches whose first start + s positions are filled and the rest
    NaN, and start."""
    capacity = capacity or (6 if s == 256 else 5) * 64 + 40
    start = {"start": 0, "mid": 101, "last": capacity - s}[where]
    kv_heads = 1 if groups == 16 else 2
    rng = np.random.RandomState(groups + s + start)
    q = jnp.asarray(rng.randn(b, kv_heads * groups, s, d), dtype)
    filled = start + s
    caches = []
    for _ in range(2):
        cache = np.full((b, kv_heads, capacity, d), np.nan, np.float32)
        cache[:, :, :filled] = rng.randn(b, kv_heads, filled, d)
        caches.append(jnp.asarray(cache, dtype))
    return q, caches[0], caches[1], start


def _assert_attends_the_filled_span(got, q, ck, cv, start):
    filled = start + q.shape[2]
    want = attention_reference(q, ck[:, :, :filled], cv[:, :, :filled],
                               q_offset=start)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # float32: the order of summation alone; bf16 (operands in the cache's
    # type, float32 accumulation): the paged kernel's own limit
    limit = 2e-5 if q.dtype == jnp.float32 else 2 ** -6
    assert np.abs(got - want).max() <= limit * np.abs(want).max()


@pytest.mark.parametrize(
    "groups,s,where,b,dtype", _CACHE_CASES,
    ids=[f"g{g}-s{s}-{w}-b{b}-{np.dtype(t).name}"
         for g, s, w, b, t in _CACHE_CASES])
def test_attend_cache_matches_reference_over_the_filled_span(
        monkeypatch, groups, s, where, b, dtype):
    """Query i attends positions 0 .. start + i of a dense cache and reads
    nothing behind start + s as a number (it is NaN there): a single decode
    token, a chunk inside a block and one across blocks, the first block,
    one in the middle and the capacity's last, which is no whole block."""
    monkeypatch.setattr(attention, "_CACHE_BLOCK", 64)
    q, ck, cv, start = _cache_case(groups, s, where, b, dtype)
    positions = jnp.broadcast_to(start + jnp.arange(s), (b, s))
    got = attend_cache(q, ck, cv, jnp.int32(start), positions)
    _assert_attends_the_filled_span(got, q, ck, cv, start)


# ..., and the query rows a step of the kernel takes of them (whole groups,
# up to 512 here)
_KERNEL_CASES = [
    (4, 32, "start", 1, jnp.float32, 128),
    (4, 256, "mid", 1, jnp.bfloat16, 512),
    (16, 32, "last", 2, jnp.bfloat16, 512),
    (5, 32, "mid", 2, jnp.float32, 160),
    (1, 256, "last", 1, jnp.bfloat16, 256),
    (16, 128, "start", 1, jnp.bfloat16, 512),
]


@pytest.mark.parametrize(
    "groups,s,where,b,dtype,rows", _KERNEL_CASES,
    ids=[f"g{g}-s{s}-{w}-b{b}-{np.dtype(t).name}"
         for g, s, w, b, t, _ in _KERNEL_CASES])
def test_attend_cache_kernel_matches_reference_over_the_filled_span(
        monkeypatch, groups, s, where, b, dtype, rows):
    """The kernel the chip runs for these shapes (heads of 128, whole
    groups of query rows a step, here in interpret mode) over a capacity of
    five blocks of 128 positions: the same answers as the loop's, nothing
    behind start + s read as a number, and the blocks behind the filled
    span neither copied nor computed."""
    monkeypatch.setattr(attention, "_CACHE_BLOCK", 256)   # 640: of 128
    monkeypatch.setattr(attention, "_CACHE_ROWS", 512)
    q, ck, cv, start = _cache_case(groups, s, where, b, dtype, d=128,
                                   capacity=640)
    kv_heads = ck.shape[1]
    blocks = attention._cache_blocks(groups, s, 640, 128)
    assert blocks == (rows, 128)
    queries = q.reshape(b, kv_heads, groups * s, 128)
    at = jnp.tile(jnp.broadcast_to(start + jnp.arange(s), (b, s)),
                  (1, groups))
    got = attention._attend_cache_pallas(
        queries, ck, cv, at, jnp.int32(start + s), *blocks)
    _assert_attends_the_filled_span(got.reshape(q.shape), q, ck, cv, start)
    loop = attention._attend_cache_loop(queries, ck, cv, at,
                                        jnp.int32(start + s))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(loop, np.float32),
        atol=2e-5 if dtype == jnp.float32 else 2 ** -6)


def test_attend_cache_is_one_program_whatever_the_cache_has_filled(
        monkeypatch):
    """The trip count is a value: two offsets, one trace, and a loop whose
    bound is no constant of the program; every row's positions may be given
    as one [s] vector."""
    monkeypatch.setattr(attention, "_CACHE_BLOCK", 64)
    traces = []

    @jax.jit
    def chunk(q, ck, cv, start):
        traces.append(start)
        return attend_cache(q, ck, cv, start,
                            start + jnp.arange(q.shape[2]))

    for where in ("start", "last"):
        q, ck, cv, start = _cache_case(4, 32, where, 2, jnp.float32)
        got = chunk(q, ck, cv, jnp.int32(start))
        want = attention_reference(
            q, ck[:, :, :start + 32], cv[:, :, :start + 32], q_offset=start)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert len(traces) == 1
    text = chunk.lower(q, ck, cv, jnp.int32(0)).as_text()
    assert "stablehlo.while" in text
    # nothing of the capacity's length is built: not the logits, not K or
    # V in float32 or repeated to the query heads
    assert "x360xf32>" not in text and "x8x360x" not in text


def test_ring_attention_matches_reference():
    mesh = MeshConfig(data=1, sequence=8).build()
    q, k, v = _qkv(s=256)
    ref = attention_reference(q, k, v, causal=True)
    with mesh:
        out = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh, "sequence", True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_gqa_noncausal():
    mesh = MeshConfig(data=1, sequence=4).build(jax.devices()[:4])
    q, k, v = _qkv(h=8, kv_heads=4, s=128)
    ref = attention_reference(q, k, v, causal=False)
    with mesh:
        out = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh, "sequence", False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_matches_reference():
    mesh = MeshConfig(data=1, sequence=4).build(jax.devices()[:4])
    q, k, v = _qkv(h=8, s=128)
    ref = attention_reference(q, k, v, causal=True)
    with mesh:
        out = jax.jit(lambda a, b, c: ulysses_attention(
            a, b, c, mesh, "sequence", True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
