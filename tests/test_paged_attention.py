"""ops.paged_attention: the paged decode kernel under the TPU interpreter
(`pltpu.InterpretParams`, as ops/attention.py::_interpret gives the flash
kernels off the chip) against the gather fallback in float32, and the one
call site the three model files share."""

import os
import re

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.ops import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, HD = 16, 128
# 4 pages = 64 tokens a block, so a few pages show every edge of a block
BLOCK_PAGES = 4
BLOCK = BLOCK_PAGES * PAGE
DEAD = -1   # a row the engine is not decoding: length 0 on the null page

# id: (kv heads, queries per kv head, tokens cached before this one per row
# (DEAD = a dead row), table width in pages, block override, pool type)
CASES = {
    # the cells' groupings (chat, hybrid chat, expert) and chat's per shard
    # under tensor=4, each over a dead row, a row inside its first block
    # and a row of several blocks
    "grouping-8x4": (8, 4, [DEAD, 40, 150], 10, BLOCK_PAGES, jnp.float32),
    "grouping-4x5": (4, 5, [DEAD, 40, 150], 10, BLOCK_PAGES, jnp.float32),
    "grouping-2x16": (2, 16, [DEAD, 40, 150], 10, BLOCK_PAGES, jnp.float32),
    "grouping-2x4-per-shard": (2, 4, [DEAD, 40, 150], 10, BLOCK_PAGES,
                               jnp.float32),
    # lengths; the row attends `cached + 1` tokens, and a live neighbour
    # takes the copies it starts for the next row
    "dead-row-on-the-null-page": (8, 4, [DEAD, 70], 8, BLOCK_PAGES,
                                  jnp.float32),
    "page-less-one-token": (8, 4, [PAGE - 2, 70], 8, BLOCK_PAGES,
                            jnp.float32),
    "exactly-one-page": (8, 4, [PAGE - 1, 70], 8, BLOCK_PAGES, jnp.float32),
    "block-less-one-token": (8, 4, [BLOCK - 2, 70], 8, BLOCK_PAGES,
                             jnp.float32),
    "exactly-one-block": (8, 4, [BLOCK - 1, 70], 8, BLOCK_PAGES,
                          jnp.float32),
    "block-and-one-token": (8, 4, [BLOCK, 70], 8, BLOCK_PAGES, jnp.float32),
    "full-table": (8, 4, [8 * PAGE - 1, 8 * PAGE - 1], 8, BLOCK_PAGES,
                   jnp.float32),
    # 8 x 4 computes in chunks of 256 tokens, two to this block of 32
    # pages: a chunk less one token, exactly one, one and a token
    "chunk-edges-inside-a-block": (8, 4, [254, 255, 256, 600], 40, 32,
                                   jnp.float32),
    # 10 pages a row in blocks of 4: the last block of a full row is short
    "table-not-whole-blocks": (4, 5, [10 * PAGE - 1, 9 * PAGE + 3], 10,
                               BLOCK_PAGES, jnp.float32),
    "every-row-dead": (4, 5, [DEAD, DEAD, DEAD], 6, BLOCK_PAGES,
                       jnp.float32),
    # the block and the chunk the shapes give (no override): 2 x 16 takes
    # chunks of 512 tokens, two to a block of the 96-page table
    "derived-block-of-two-chunks": (2, 16, [1100, 511, 512, DEAD], 96, None,
                                    jnp.float32),
    # the pools' own type: bf16 operands, float32 accumulation, bf16 out
    "bf16-pool": (8, 4, [DEAD, 100, 300], 24, None, jnp.bfloat16),
}


def _inputs(kv_heads, groups, cached, width, dtype, seed):
    """Pools with every page random (so a read of a wrong page shows),
    row r's pages scattered, unused table entries on the null page 0."""
    rows = len(cached)
    rng = np.random.RandomState(seed)
    pages = 1 + rows * width
    k_pages = jnp.asarray(rng.randn(kv_heads, pages, PAGE, HD), dtype)
    v_pages = jnp.asarray(rng.randn(kv_heads, pages, PAGE, HD), dtype)
    q = jnp.asarray(rng.randn(rows, kv_heads * groups, HD), dtype)
    free = 1 + rng.permutation(rows * width)
    tables = np.zeros((rows, width), np.int32)
    for r, n in enumerate(cached):
        if n != DEAD:
            held = n // PAGE + 1
            tables[r, :held] = free[r * width:r * width + held]
    lengths = np.asarray([max(n, 0) for n in cached], np.int32)
    return q, k_pages, v_pages, jnp.asarray(lengths), jnp.asarray(tables)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_agrees_with_the_gather_fallback(case):
    kv_heads, groups, cached, width, block_pages, dtype = CASES[case]
    q, k_pages, v_pages, lengths, tables = _inputs(
        kv_heads, groups, cached, width, dtype, seed=len(case))
    want = pa.paged_attend(q, k_pages, v_pages, lengths, tables)
    assert pa.paged_kernel(HD) == "gather"          # the CPU's path
    got = pa._paged_attend_pallas(
        (q * HD ** -0.5).astype(k_pages.dtype), k_pages, v_pages,
        lengths + 1, tables, block_pages=block_pages)
    assert got.shape == q.shape and got.dtype == k_pages.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # float32 pools: the order of summation alone; bf16: the output's
    # rounding and the scaled query's (the kernel's q is scaled, then
    # rounded, as the stock kernel's was)
    limit = 2e-5 if dtype == jnp.float32 else 2 ** -6
    assert np.abs(got - want).max() <= limit * np.abs(want).max()


def test_block_and_chunk_follow_the_shapes():
    """No option and no key: the cells' three shapes and chat's per shard
    give their own chunk (whole lanes of float32 logits within a quarter
    of the registers) and block (whole chunks within the buffers' share of
    fast memory, no more than a row's table)."""
    got = {}
    for name, (kv_heads, groups, width) in {
            "chat": (8, 4, 144), "hybrid": (4, 5, 96),
            "expert": (2, 16, 96), "shard": (2, 4, 144)}.items():
        padded = -(-groups // 8) * 8
        chunk = pa._chunk_tokens(kv_heads, padded)
        block = PAGE * pa._block_pages(kv_heads, PAGE, HD, width, 2, chunk)
        assert block % chunk == 0 and chunk % 128 == 0
        assert 2 * 2 * kv_heads * block * HD * 2 <= pa._BUFFER_BYTES
        got[name] = (chunk, block)
    assert got == {"chat": (256, 512), "hybrid": (512, 1024),
                   "expert": (512, 1536), "shard": (512, 2048)}


def test_models_reach_paged_attention_through_the_one_function():
    """models/llama.py, models/falcon_h1.py and models/nemotron_h.py call
    ops.paged_attention.paged_attend, and nothing in the tree imports the
    stock kernel beside it."""
    from ray_tpu.models import falcon_h1, llama, nemotron_h
    for module in (llama, falcon_h1, nemotron_h):
        assert module.paged_attend is pa.paged_attend
        with open(module.__file__) as f:
            text = f.read()
        assert len(re.findall(r"\bpaged_attend\(", text)) == 1
        assert "_paged_attend" not in text
    stock = "pallas.ops.tpu." + "paged_attention"
    holders = []
    for top in ("ray_tpu", "benchmarks", "tests"):
        for folder, _, files in os.walk(os.path.join(REPO, top)):
            holders += [os.path.join(folder, name) for name in files
                        if name.endswith(".py")]
    holders += [os.path.join(REPO, name) for name in os.listdir(REPO)
                if name.endswith(".py")]
    assert len(holders) > 100
    for path in holders:
        with open(path) as f:
            assert stock not in f.read(), path
