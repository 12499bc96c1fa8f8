"""Keye-VL-2.0-30B-A3B's language model (learned sparse attention: an
indexer, the top-k of the context; softmax-routed SwiGLU experts) on the
CPU, seeded random weights, a tiny config in the published ratios: the
model's three paths, the selection, and the paged engine's index-key pools
against the plain float32 reference (benchmarks/reference/keye_dsa_ref.py).
Logits, never tokens; `topk` 16 so that contexts of 40-200 select."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness.builders_keye_dsa import keye_dsa_model  # noqa: E402
from benchmarks.reference import keye_dsa_ref  # noqa: E402
from plain_greedy import plain_greedy, rowwise  # noqa: E402
from ray_tpu.llm import GenerationRequest  # noqa: E402
from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine  # noqa: E402
from ray_tpu.models import keye_dsa, moe  # noqa: E402
from ray_tpu.models.keye_dsa import KeyeDSAConfig  # noqa: E402
from ray_tpu.ops import sparse_attention as sa  # noqa: E402
from ray_tpu.ops.paged_attention import paged_attend  # noqa: E402

# Published key names at toy widths, in the published ratios: 2 query heads
# a kv head (8 published), an index key half a head wide, 2 experts a token
# of 16, an eighth of them held, two layers.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 16, "num_experts": 2, "num_local_experts": 2,
    "held_experts": [2, 2], "published": {"num_experts": 16},
    "num_experts_per_tok": 2, "norm_topk_prob": True, "mlp_only_layers": [],
    "decoder_sparse_step": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 16},
    "max_position_embeddings": 262144}

# Everything here is float32 on the CPU, the system's arithmetic and the
# reference's alike; they differ in the order of their sums (a blocked
# running softmax against a dense one, every held expert on every token
# against each token's chosen experts). A selection that differed by one
# token would read ~1e-1.
TOLERANCE = 3e-5


def tiny_model(**overrides) -> KeyeDSAConfig:
    return dataclasses.replace(
        keye_dsa_model(TINY), dtype=jnp.float32, param_dtype=jnp.float32,
        **overrides)


def tiny_engine(params=None, num_pages=96, **model_overrides):
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(**model_overrides), max_batch=3, max_len=256,
        page_size=8, num_pages=num_pages, prefill_buckets=(16, 32)),
        params=params)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def keys_of(**overrides):
    return dict(TINY, **overrides)


def prompt_of(seed: int, n: int):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], n)


def spread(logits) -> float:
    return float(np.asarray(logits).std(-1).mean())


def close(got, want, tolerance=TOLERANCE):
    return np.abs(np.asarray(got) - np.asarray(want)).max() \
        < tolerance * spread(want)


# -- (i) the model's whole-sequence forward against the reference ---------

@pytest.mark.parametrize("length", [1, 7, 16, 17, 90])
def test_forward_matches_the_reference(engine, length):
    tokens = prompt_of(length, length)
    got = engine.model.apply({"params": engine.params}, tokens[None])[0]
    assert close(got, keye_dsa_ref.logits(engine.params, tokens, keys_of()))


def test_mrope_with_three_equal_streams_is_the_plain_map():
    at = np.asarray([0, 1, 5, 1000, 65535])
    plain = keye_dsa_ref.rotary_angles(1e7, 16, at)
    three = keye_dsa_ref.mrope_angles(1e7, 16, [2, 3, 3], np.stack([at] * 3))
    assert np.array_equal(np.asarray(plain), np.asarray(three))
    # and with streams that differ, each pair reads its own section's
    apart = keye_dsa_ref.mrope_angles(
        1e7, 16, [2, 3, 3], np.stack([at, at + 1, at + 2]))
    each = [keye_dsa_ref.rotary_angles(1e7, 16, at + i) for i in range(3)]
    want = np.concatenate([each[0][:, :2], each[1][:, 2:5], each[2][:, 5:]],
                          axis=1)
    assert np.array_equal(np.asarray(apart), np.asarray(want))


# -- (ii) chunks into pages, then decode through the cache ----------------

def _row_table(engine, pages):
    table = np.zeros((engine.config.pages_per_seq,), np.int32)
    table[:len(pages)] = pages
    return table


def _fresh_pools(engine):
    return jax.tree_util.tree_map(jnp.zeros_like, engine._row_pools)


def _chunks(engine, prompt, pools, table):
    """`prompt` through the engine's chunk program, a bucket at a time;
    the finishing chunk's logits and the pools."""
    off = 0
    while off < len(prompt):
        rem = len(prompt) - off
        size = engine._bucket(min(rem, engine.config.prefill_buckets[-1]))
        take = min(rem, size)
        tokens = np.zeros((1, size), np.int32)
        tokens[0, :take] = prompt[off:off + take]
        last = take - 1 if off + take == len(prompt) else -1
        logits, pools = engine._chunk_prefill(
            engine.params, jnp.asarray(tokens),
            jnp.asarray(np.arange(off, off + size, dtype=np.int32)[None]),
            pools, jnp.asarray(off, jnp.int32), jnp.asarray(table),
            jnp.asarray(take, jnp.int32), jnp.asarray(last, jnp.int32))
        off += take
    return np.asarray(logits[0]), pools


def _decode_apply(engine, pools, tables, lengths, tokens, active):
    """One decode step through the model itself: every row's logits, the
    pools, and what the attention layers sowed."""
    caches = [{"k": k, "v": v, "index": index, "active": jnp.asarray(active),
               "block_tables": jnp.asarray(tables),
               "lengths": jnp.asarray(lengths), "pairs": pairs,
               "steps": steps}
              for k, v, index, (pairs, steps) in zip(
                  *pools, engine.config.model.init_counters())]
    (logits, new), sown = engine.model.apply(
        {"params": engine.params}, jnp.asarray(tokens)[:, None],
        positions=jnp.asarray(lengths)[:, None], kv_caches=caches,
        mutable=["intermediates"])
    pools = tuple([kept[i] for kept in new] for i in range(3))
    return np.asarray(logits[:, 0]), pools, sown["intermediates"]


def test_rows_under_and_over_topk_decode_in_one_batch(engine):
    """Chunked prefill into pages, then decode steps through the cache, a
    row of 9 tokens (under topk 16) beside rows of 37 and 150: every
    position's logits are the reference's full forward."""
    B = engine.config.max_batch
    prompts = [prompt_of(31, 9), prompt_of(32, 150), prompt_of(33, 37)]
    fed = [prompt_of(41 + r, 6) for r in range(B)]
    pools = _fresh_pools(engine)
    tables, page = [], 1
    for prompt in prompts:
        n = -(-(len(prompt) + 6) // 8)
        tables.append(_row_table(engine, list(range(page, page + n))))
        page += n
    want = [np.asarray(keye_dsa_ref.logits(
        engine.params, np.concatenate([p, f]), keys_of()))
        for p, f in zip(prompts, fed)]
    for r, prompt in enumerate(prompts):
        last, pools = _chunks(engine, prompt, pools, tables[r])
        assert close(last, want[r][len(prompt) - 1])
    for step in range(6):
        lengths = np.asarray([len(p) + step for p in prompts], np.int32)
        tokens = np.asarray([f[step] for f in fed], np.int32)
        logits, pools, _ = _decode_apply(
            engine, pools, np.stack(tables), lengths, tokens,
            np.ones((B,), bool))
        for r in range(B):
            assert close(logits[r], want[r][lengths[r]]), (r, step)


# -- (iii) the selection ---------------------------------------------------

def test_the_decode_steps_select_what_the_reference_selects(engine):
    prompt, fed = prompt_of(51, 120), prompt_of(52, 3)
    pools = _fresh_pools(engine)
    table = _row_table(engine, list(range(1, 17)))
    _, pools = _chunks(engine, prompt, pools, table)
    tokens = np.concatenate([prompt, fed])
    _, more = keye_dsa_ref.logits(
        engine.params, tokens, keys_of(), rows=np.arange(120, 123),
        details=(0, 1))
    tables = np.zeros((3, len(table)), np.int32)
    tables[1] = table
    for step in range(3):
        lengths = np.asarray([0, 120 + step, 0], np.int32)
        _, pools, sown = _decode_apply(
            engine, pools, tables, lengths,
            np.asarray([0, fed[step], 0], np.int32),
            np.asarray([False, True, False]))
        for layer in (0, 1):
            chosen, count = sown[f"layer_{layer}"]["attn"]["selected"][0]
            assert int(count[1]) == 16
            want = np.flatnonzero(np.asarray(more[layer]["selected"][step]))
            assert np.asarray(chosen[1]).tolist() == want.tolist()
            scores = sown[f"layer_{layer}"]["attn"]["index_scores"][0][1]
            ref = np.asarray(more[layer]["index_scores"][step])
            n = 121 + step
            assert np.abs(np.asarray(scores)[:n] - ref[:n]).max() \
                < 1e-5 * ref[:n].std()


def _scores(seed, rows, ctx):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(rows, ctx)),
                       jnp.float32)


@pytest.mark.parametrize("ctx,k", [(64, 16), (300, 16), (300, 128),
                                   (1000, 200), (40, 64)])
def test_the_bisected_selection_is_top_k(ctx, k):
    scores = _scores(ctx + k, 5, ctx)
    lengths = jnp.asarray([1, 7, k, ctx // 2, ctx], jnp.int32)
    fast = sa.select_top_k(scores, lengths, k)
    plain = sa.select_top_k(scores, lengths, k, reference=True)
    assert np.array_equal(np.asarray(fast[0]), np.asarray(plain[0]))
    assert np.array_equal(np.asarray(fast[1]), np.asarray(plain[1]))
    for row, n in enumerate(np.asarray(lengths)):
        want = np.sort(np.argsort(-np.asarray(scores[row, :n]),
                                  kind="stable")[:k])
        assert np.asarray(fast[0][row, :len(want)]).tolist() == want.tolist()


@pytest.mark.parametrize("reference", [False, True])
def test_ties_at_the_threshold_go_to_the_earlier_position(reference):
    """Eight scores above, then five equal ones of which two fit: the two
    that stand earliest; zeros of both signs tie with each other."""
    scores = np.full((2, 40), -3.0, np.float32)
    scores[0, [3, 9, 11, 17, 20, 25, 31, 38]] = 5.0
    scores[0, [1, 14, 15, 29, 33]] = 2.0
    scores[1, [2, 4, 6, 8, 10, 12, 14, 16]] = 1.0
    scores[1, [30, 5]] = -0.0
    scores[1, [7, 22]] = 0.0
    chosen, count = sa.select_top_k(
        jnp.asarray(scores), jnp.asarray([40, 40]), 10, reference=reference)
    assert np.asarray(count).tolist() == [10, 10]
    assert np.asarray(chosen[0]).tolist() \
        == [1, 3, 9, 11, 14, 17, 20, 25, 31, 38]
    assert np.asarray(chosen[1]).tolist() \
        == [2, 4, 5, 6, 7, 8, 10, 12, 14, 16]
    mask = keye_dsa_ref._select(
        jnp.asarray(scores), jnp.ones((2, 40), bool),
        keye_dsa_ref.shape_of(keys_of())._replace(topk=10))
    assert [np.flatnonzero(row).tolist() for row in np.asarray(mask)] \
        == np.asarray(chosen).tolist()


def test_a_chunk_over_blocks_keeps_the_earliest_ties():
    """`kept` with ties carried across blocks (what the chunk's loop
    does) is the whole row's rule."""
    scores = np.round(np.random.default_rng(3).normal(size=(4, 96)), 1)
    u = sa.ordered_bits(jnp.asarray(scores, jnp.float32))
    rule = sa.threshold_of(u, 20)
    whole = np.asarray(sa.kept(u, rule))
    ties, parts = jnp.zeros((4, 1), jnp.int32), []
    for at in range(0, 96, 32):
        here = u[:, at:at + 32]
        parts.append(np.asarray(sa.kept(here, rule, ties)))
        ties = ties + ((here == rule.t) & (here > 0)).sum(
            -1, keepdims=True).astype(jnp.int32)
    assert np.array_equal(np.concatenate(parts, axis=1), whole)
    assert whole.sum(-1).tolist() == [20] * 4
    for row in range(4):
        want = np.argsort(-scores[row], kind="stable")[:20]
        assert np.flatnonzero(whole[row]).tolist() == np.sort(want).tolist()


@pytest.mark.parametrize("m", [1, 127, 128, 129, 700])
def test_the_running_count_is_a_cumsum(m):
    flags = np.random.default_rng(m).random((3, m)) < 0.3
    assert np.array_equal(np.asarray(sa.running_count(jnp.asarray(flags))),
                          np.cumsum(flags, axis=-1))


def _positions_by_a_gathered_row(keep, k):
    """`positions_of` as it stood before PR 57, kept here to compare with:
    a slot's 128-lane row of running counts by ONE gather."""
    n, m = keep.shape
    within, totals = sa._group_counts(keep)
    upto = jnp.cumsum(totals, axis=-1)
    groups = upto.shape[1]
    slot = jnp.arange(k, dtype=jnp.float32)[None, :, None]
    passed = upto[:, None, :] <= slot
    group = jnp.minimum(passed.sum(-1), groups - 1)
    rank = slot[..., 0] - jnp.where(passed, totals[:, None, :], 0).sum(-1)
    rows = within.reshape(n * groups, 128)[
        group + jnp.arange(n)[:, None] * groups]
    at = group * 128 + (rows <= rank[..., None]).sum(-1)
    return jnp.where(slot[..., 0] < upto[:, -1:], at, m).astype(jnp.int32)


EDGE = sa._ONE_HOT_GROUPS * 128


@pytest.mark.parametrize("m,k", [
    (1, 4), (127, 8), (128, 8), (129, 8), (700, 16), (3000, 64),
    (EDGE, 8),              # the last width whose row is taken by a product
    (EDGE + 1, 8),          # the first whose row is gathered
    (EDGE + 700, 16)])
def test_the_kept_positions_are_the_gathered_rows_positions(m, k):
    """Rows that keep nothing, one column, fewer than k, exactly k, more
    than k (scattered, all in one group, the table's last columns) and
    every column: the positions of `positions_of` are the former form's
    (a gathered row a slot) and the first k kept columns, m behind them,
    on both sides of the static rule that picks how a slot's row is
    taken."""
    rng = np.random.default_rng(m + k)
    keep = np.zeros((7, m), bool)
    keep[1, rng.integers(m)] = True
    keep[2, rng.choice(m, min(m, k - 1), replace=False)] = True
    keep[3, rng.choice(m, min(m, k), replace=False)] = True
    keep[4] = rng.random(m) < 0.4
    keep[5, max(0, m - 3 * k):] = True
    keep[6] = True
    want = np.full((7, k), m, np.int32)
    for row, flags in enumerate(keep):
        first = np.flatnonzero(flags)[:k]
        want[row, :len(first)] = first
    got = np.asarray(jax.jit(sa.positions_of, static_argnums=1)(
        jnp.asarray(keep), k))
    assert got.dtype == np.int32 and np.array_equal(got, want)
    assert np.array_equal(got, np.asarray(
        _positions_by_a_gathered_row(jnp.asarray(keep), k)))


def test_how_a_slots_row_is_taken_follows_the_groups_alone():
    """No gather up to `_ONE_HOT_GROUPS` groups of 128 columns, one
    above: the choice is the shape's."""
    def gathers(m):
        return jax.jit(sa.positions_of, static_argnums=1).lower(
            jax.ShapeDtypeStruct((2, m), jnp.bool_), 8).as_text().count(
                "stablehlo.gather")

    assert gathers(EDGE) == 0 and gathers(EDGE + 1) > 0


# (page size, each row's tokens to score, the table's pages a row): what
# the non-reference scoring paths must give as the whole gather does. DEAD
# is a row the engine holds no request in: it scores one token, on the
# null page.
STEP = sa._SCORE_BLOCK_TOKENS
BLOCK = sa._COPY_BLOCK_STEPS * STEP
DEAD = 0
SCORE_CASES = {
    "three-rows-over-blocks": (64, [5, 2100, 4300], 80),
    "a-row-under-one-page": (64, [37], 40),
    "a-row-ends-on-a-steps-edge": (64, [STEP, 2 * STEP], 70),
    "a-row-ends-on-a-copy-blocks-edge": (64, [BLOCK, 700], 70),
    "one-token-beside-many-blocks": (64, [1, 2 * BLOCK + STEP + 70], 170),
    "a-dead-row-between": (64, [BLOCK + 9, DEAD, 300], 80),
    "a-table-padded-with-the-null-page": (64, [130, 64 * 33 + 1], 100),
    "pages-of-16": (16, [15, STEP + 17, BLOCK + 300], 300),
    "pages-of-16-one-token": (16, [1, 16, 17], 140),
    "nine-rows-two-groups-of-eight": (
        64, [40, 70, 1, 200, 64, 65, 128, 2049, 90], 40),
}


def score_case(case, dtype=jnp.float32):
    """(q, w, pool, lengths, tables) of a case: every page random (a read
    of a wrong page shows), rows' pages scattered, the entries of a table
    behind a row's pages on the null page, which is zeros."""
    page_size, tokens, width = SCORE_CASES[case]
    rows = len(tokens)
    rng = np.random.default_rng(len(case))
    held = [-(-n // page_size) for n in tokens]
    pool = rng.normal(size=(1, 1 + sum(held), page_size, 128))
    pool[0, 0] = 0
    free = 1 + rng.permutation(sum(held))
    tables = np.zeros((rows, width), np.int32)
    for r, pages in enumerate(held):
        tables[r, :pages] = free[:pages]
        free = free[pages:]
    return (jnp.asarray(rng.normal(size=(rows, 2, 128)), dtype),
            jnp.asarray(rng.normal(size=(rows, 2)), jnp.float32),
            jnp.asarray(pool, dtype),
            jnp.asarray(np.maximum(tokens, 1), jnp.int32),
            jnp.asarray(tables))


def scores_by(path, q, w, pool, lengths, tables):
    """A decode batch's scores by a non-reference path: "xla" the loop the
    CPU's programs hold, "pallas" the chip's kernel under the TPU
    interpreter."""
    if path == "xla":
        return sa.paged_index_scores(q, w, pool, lengths, tables)
    pages = max(1, STEP // pool.shape[2])
    return sa._index_scores_pallas(q.astype(pool.dtype), w, pool, lengths,
                                   sa._pad_pages(tables, pages))


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(SCORE_CASES))
def test_the_blocked_scores_are_the_whole_gathers(case, path):
    """(a) a block of pages at a time = the whole table at once: every
    cached token's score, finite and of one width whatever the path."""
    q, w, pool, lengths, tables = score_case(case)
    whole = np.asarray(sa.paged_index_scores(q, w, pool, lengths, tables,
                                             reference=True))
    blocked = np.asarray(scores_by(path, q, w, pool, lengths, tables))
    assert blocked.dtype == np.float32 and np.isfinite(blocked).all()
    assert blocked.shape == (len(lengths), -(-whole.shape[1] // STEP) * STEP)
    for row, n in enumerate(np.asarray(lengths)):
        assert np.abs(blocked[row, :n] - whole[row, :n]).max() < 1e-4
    if path == "pallas":
        # nothing is scored behind a row's last step, and nothing is read
        # past a row's own pages but the slot's leftovers
        for row, n in enumerate(np.asarray(lengths)):
            assert not blocked[row, -(-n // STEP) * STEP:].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_rows_scores_do_not_depend_on_the_rows_beside_it(dtype):
    """The same row alone, first and last in a batch: bit for bit, over
    the tokens it has."""
    q, w, pool, lengths, tables = score_case("three-rows-over-blocks", dtype)
    n = int(lengths[2])

    def batch(*order):
        at = np.asarray(order)
        return np.asarray(scores_by("pallas", q[at], w[at], pool,
                                    lengths[at], tables[at]))

    alone = batch(2)[0, :n]
    assert np.abs(alone).max() > 1
    assert np.array_equal(batch(2, 0, 1)[0, :n], alone)
    assert np.array_equal(batch(0, 1, 2)[2, :n], alone)
    assert np.array_equal(batch(1, 2, 0, 1, 1, 0, 0, 1, 2)[8, :n], alone)


def test_the_kernel_takes_the_products_in_the_pools_type():
    """bf16 operands, float32 sums: against the same products in float32
    on the bf16 values, and told apart from float32 operands."""
    q, w, pool, lengths, tables = score_case("three-rows-over-blocks",
                                             jnp.bfloat16)
    got = np.asarray(scores_by("pallas", q, w, pool, lengths, tables))
    as_f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    want = np.asarray(sa.paged_index_scores(
        as_f32(q), w, as_f32(pool), lengths, tables, reference=True))
    exact = np.asarray(sa.paged_index_scores(
        *score_case("three-rows-over-blocks")[:3], lengths, tables,
        reference=True))
    n = int(lengths[2])
    assert np.abs(got[2, :n] - want[2, :n]).max() < 1e-3
    assert np.abs(got[2, :n] - exact[2, :n]).max() > 1e-2


def test_the_scoring_path_is_named_by_backend_and_shape(monkeypatch):
    """`sparse_kernel`: the XLA loop off the chip; on it the kernel for
    pages that fill whole tiles and divide a scoring step, by the shapes."""
    assert sa.sparse_kernel(False, 64, 128) == "xla"
    assert sa.sparse_kernel(True, 64, 128) == "reference"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sa.sparse_kernel(False, 64, 128) == "pallas"
    assert sa.sparse_kernel(False, 16, 256) == "pallas"
    assert sa.sparse_kernel(True, 64, 128) == "reference"
    for page_size, lanes in ((8, 128), (48, 128), (4096, 128), (64, 64),
                             (64, 192)):
        assert sa.sparse_kernel(False, page_size, lanes) == "xla"


# -- (iii') the gather-and-attend reads the rows as the gather leaves them --

# page_size, kv heads, queries a kv head, k, and per row (context, count,
# the positions selected: None = a random `count` of the context)
EVERY_RESIDUE = [9 * i for i in range(16)]            # 9 i % 8 = i % 8
ATTEND_CASES = {
    "count-is-k": (16, 2, 2, 32, [(40, 32, None), (200, 32, None),
                                  (33, 32, None)]),
    "count-under-k-zeros-behind": (16, 2, 2, 32, [
        (5, 5, None), (31, 31, None), (200, 32, None)]),
    "a-dead-row-between": (16, 2, 2, 32, [(90, 32, None), (0, 0, None),
                                          (50, 32, None)]),
    "every-residue-of-a-group-of-eight-both-halves-of-a-packed-pair": (
        16, 2, 2, 16, [(150, 16, EVERY_RESIDUE),
                       (150, 16, [p + 3 for p in EVERY_RESIDUE])]),
    "two-selected-tokens-in-one-group": (16, 2, 2, 8, [
        (64, 8, [16, 17, 18, 23, 40, 41, 62, 63])]),
    "a-tables-first-page-and-its-last": (16, 2, 2, 8, [
        (12 * 16, 8, [0, 1, 15, 16, 100, 176, 190, 191])]),
    "pages-of-64": (64, 2, 2, 32, [(700, 32, None), (64, 32, None),
                                   (65, 32, None)]),
    "pages-of-8": (8, 2, 2, 32, [(90, 32, None), (17, 17, None)]),
    "one-kv-head": (16, 1, 8, 32, [(100, 32, None), (20, 20, None)]),
    "four-kv-heads-eight-queries-each": (16, 4, 8, 32, [
        (100, 32, None), (20, 20, None)]),
    "one-query-a-kv-head": (16, 4, 1, 32, [(100, 32, None), (7, 7, None)]),
}


def attend_case(case, dtype=jnp.float32, head_dim=128):
    """(q, k_pool, v_pool, positions, count, tables), kv heads: every page
    random (a read of a wrong token shows), rows' pages scattered, 0
    behind a row's `count` positions."""
    page_size, kv_heads, group, k, rows_of = ATTEND_CASES[case]
    rows = len(rows_of)
    rng = np.random.default_rng(len(case))
    width = max(1, max(-(-n // page_size) for n, _, _ in rows_of))
    pages = 1 + rows * width
    tables = (1 + rng.permutation(pages - 1)).reshape(rows, width)
    positions = np.zeros((rows, k), np.int32)
    for r, (context, count, chosen) in enumerate(rows_of):
        if chosen is None:
            chosen = np.sort(rng.choice(context, count, replace=False))
        positions[r, :count] = chosen
    pools = [jnp.asarray(rng.normal(size=(1, pages, page_size,
                                          kv_heads * head_dim)), dtype)
             for _ in range(2)]
    q = rng.normal(size=(rows, kv_heads * group, head_dim)) * head_dim ** -0.5
    return (jnp.asarray(q, jnp.float32), *pools, jnp.asarray(positions),
            jnp.asarray([count for _, count, _ in rows_of], jnp.int32),
            jnp.asarray(tables, jnp.int32)), kv_heads


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(ATTEND_CASES))
def test_the_rows_read_as_gathered_give_the_split_rows_softmax(case, dtype):
    """(c) a kv head as a slice of lanes of the gathered rows = the rows
    split into kv heads (`reference=True`): the same sums, over exactly a
    row's `count` selected tokens, finite where a row selects none."""
    args, kv_heads = attend_case(case, dtype)
    got = np.asarray(sa.sparse_attend(*args, kv_heads=kv_heads))
    want = np.asarray(sa.sparse_attend(*args, kv_heads=kv_heads,
                                       reference=True))
    assert got.dtype == np.float32 and got.shape == args[0].shape
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 1e-5
    # and what stands behind a row's count took no part in it
    positions, count = np.asarray(args[3]), np.asarray(args[4])
    behind = np.arange(positions.shape[1])[None, :] >= count[:, None]
    moved = jnp.asarray(np.where(behind, 3, positions))
    again = sa.sparse_attend(*args[:3], moved, *args[4:], kv_heads=kv_heads)
    live = count > 0
    assert np.array_equal(np.asarray(again)[live], got[live])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_rows_attention_does_not_depend_on_the_rows_beside_it(dtype):
    """The same row alone, first and last in a batch: bit for bit."""
    (q, k_pool, v_pool, positions, count, tables), kv_heads = attend_case(
        "count-under-k-zeros-behind", dtype)

    def batch(*order):
        at = np.asarray(order)
        return np.asarray(sa.sparse_attend(
            q[at], k_pool, v_pool, positions[at], count[at], tables[at],
            kv_heads=kv_heads))

    alone = batch(1)[0]
    assert np.abs(alone).max() > 0.1
    assert np.array_equal(batch(1, 0, 2)[0], alone)
    assert np.array_equal(batch(0, 2, 1)[2], alone)
    assert np.array_equal(batch(2, 1, 0, 2, 2, 0, 0, 2, 1)[8], alone)


def test_the_attention_takes_its_products_in_the_pools_type():
    """bf16 operands and bf16 probabilities, float32 sums: against the
    same arithmetic spelled out in float32 on the rounded values, and told
    apart from float32 operands."""
    case = "four-kv-heads-eight-queries-each"
    page_size, _, group, k, _ = ATTEND_CASES[case]
    (q, k_pool, v_pool, positions, count, tables), kv_heads = attend_case(
        case, jnp.bfloat16)
    got = np.asarray(sa.sparse_attend(q, k_pool, v_pool, positions, count,
                                      tables, kv_heads=kv_heads))
    rounded = lambda a: np.asarray(  # noqa: E731
        a.astype(jnp.bfloat16).astype(jnp.float32))
    at = np.asarray(positions)
    token = np.take_along_axis(np.asarray(tables), at // page_size,
                               axis=1) * page_size + at % page_size
    keys, values = (rounded(pool).reshape(-1, kv_heads, 128)[token]
                    for pool in (k_pool, v_pool))     # [rows, k, g, d]
    logits = np.einsum("rgjd,rkgd->rgjk",
                       rounded(q).reshape(-1, kv_heads, group, 128), keys)
    live = np.arange(k)[None, :] < np.asarray(count)[:, None]
    probs = jax.nn.softmax(jnp.where(live[:, None, None], logits,
                                     sa.NEG_INF), axis=-1)
    want = np.einsum("rgjk,rkgd->rgjd", rounded(probs), values).reshape(
        got.shape)
    exact = np.asarray(sa.sparse_attend(
        *attend_case(case)[0], kv_heads=kv_heads, reference=True))
    assert np.abs(got - want).max() < 2e-5
    assert np.abs(got - exact).max() > 3e-3


def test_no_gathered_row_is_split_into_kv_heads():
    """The decode path holds nothing of shape [rows, k, kv heads, head
    dim] (what the chip relays out, a whole gathered pool a layer); the
    reference form does."""
    args, kv_heads = attend_case("four-kv-heads-eight-queries-each")
    split = "tensor<2x32x4x128x"

    def lowered(**how):
        return jax.jit(lambda *a: sa.sparse_attend(
            *a, kv_heads=kv_heads, **how)).lower(*args).as_text()

    as_they_lie = lowered()
    assert split not in as_they_lie and split in lowered(reference=True)
    assert "tensor<2x32x512x" in as_they_lie


# (rows, k, table width, page size, pages of the pool): the pool's token
# rows decide how many bytes of a page's first row the product carries
# (256 rows one, 65,536 two, 2^24 three), the width how many groups of 128
# lanes stand side by side
POOL_ROW_CASES = {
    "the-cells-shapes-cut-small": (6, 256, 1036, 64, 13312),
    "a-table-under-one-group": (3, 40, 16, 8, 300),
    "exactly-one-group": (2, 24, 128, 8, 2000),
    "one-lane-into-the-second-group": (2, 24, 129, 8, 2000),
    "the-published-context-4096-wide": (2, 96, 4096, 64, 262144),
    "a-pool-of-256-rows-one-byte": (2, 16, 5, 8, 32),
    "a-pool-of-257-rows-two-bytes": (2, 16, 5, 1, 257),
    "page-255-the-pools-last": (2, 16, 9, 16, 256),
    "page-256": (2, 16, 9, 16, 257),
    "a-pool-of-65536-rows-two-bytes": (2, 16, 40, 64, 1024),
    "a-pool-of-65537-rows-three-bytes": (2, 16, 40, 1, 65537),
    "page-65535-the-pools-last": (2, 16, 40, 4, 65536),
    "page-65536": (2, 16, 40, 4, 65537),
    "a-pool-of-2-to-the-24-rows-three-bytes": (2, 16, 300, 64, 262144),
    "one-page-more-four-bytes": (2, 16, 300, 64, 262145),
    "the-largest-pool-int32-addresses": (2, 16, 300, 64, 2 ** 25 - 1),
}


@pytest.mark.parametrize("case", sorted(POOL_ROW_CASES))
def test_a_position_is_resolved_to_its_pool_row_without_a_gather(case):
    """`pool_rows` = `take_along_axis(tables, positions // page_size) *
    page_size + positions % page_size`, element for element: the pool's
    last page (the largest id a pool of that shape holds) and the ids
    around a byte's edge in the tables, positions on both sides of every
    page edge and of a 128-page group's, a table's first position and its
    last, zeros behind a row's count; and the lowered form holds no
    gather."""
    rows, k, width, page_size, pages = POOL_ROW_CASES[case]
    rng = np.random.default_rng(len(case))
    tables = rng.integers(0, pages, (rows, width)).astype(np.int32)
    edges = [i for i in (0, 1, 255, 256, 257, 65535, 65536, 65537,
                         pages - 2, pages - 1) if 0 <= i < pages]
    at = rng.choice(width, min(width, len(edges)), replace=False)
    tables[0, at] = edges[-len(at):]
    tables[-1, -1] = tables[-1, 0] = pages - 1
    last = width * page_size - 1
    positions = rng.integers(0, last + 1, (rows, k)).astype(np.int32)
    pages_at = [p for p in (1, 2, 127, 128, 129, width - 1) if p < width]
    fixed = sorted({0, last, *(p * page_size - 1 for p in pages_at),
                    *(p * page_size for p in pages_at),
                    *(int(a) * page_size for a in at)})[:k - 3]
    positions[0, :len(fixed)] = fixed
    positions[:, k - 3:] = 0                     # behind a row's count
    want = np.take_along_axis(tables, positions // page_size, 1) \
        * page_size + positions % page_size
    resolve = jax.jit(lambda t, p: sa.pool_rows(t, p, page_size,
                                                pages * page_size))
    got = np.asarray(resolve(tables, positions))
    assert got.dtype == np.int32 and got.shape == (rows, k)
    assert np.array_equal(got, want)
    assert want.max() >= (pages - 1) * page_size
    assert "gather" not in resolve.lower(tables, positions).as_text()


def test_the_reference_form_keeps_the_plain_gather_of_page_ids():
    """`reference=True` resolves through `take_along_axis` as it always
    did (one gather more than the two of rows: it is what the form above
    is compared with, and stays independent of it); the decode form holds
    the two row gathers alone."""
    args, kv_heads = attend_case("four-kv-heads-eight-queries-each")

    def gathers(**how):
        text = jax.jit(lambda *a: sa.sparse_attend(
            *a, kv_heads=kv_heads, **how)).lower(*args).as_text()
        found = [line.rsplit("->", 1)[1].strip() for line
                 in text.splitlines() if "stablehlo.gather" in line]
        return sorted(found)

    rows = "tensor<2x32x512xf32>"
    assert gathers() == [rows, rows]
    assert gathers(reference=True) == [rows, rows, "tensor<2x32xi32>"]


# -- (iv) topk >= context: the sparse path is dense paged attention ------

def test_with_topk_over_the_context_it_is_paged_attention():
    rng = np.random.default_rng(9)
    rows, heads, kvh, hd, ps, pages = 3, 4, 2, 16, 8, 40
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(1, pages, ps, kvh * hd)),
                                  jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(rows, heads, hd)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[:rows * 12]
                         .reshape(rows, 12), jnp.int32)
    lengths = jnp.asarray([3, 50, 96], jnp.int32)
    scores = _scores(1, rows, 96)
    chosen, count = sa.select_top_k(scores, lengths, 128)
    assert np.asarray(count).tolist() == [3, 50, 96]
    got = sa.sparse_attend(q * hd ** -0.5, k_pool, v_pool, chosen, count,
                           tables, kv_heads=kvh)
    by_head = lambda pool: pool[0].reshape(  # noqa: E731
        pages, ps, kvh, hd).transpose(2, 0, 1, 3)
    # (`paged_attend` scales q itself and is told the tokens BEFORE the
    # newest)
    want = paged_attend(q, by_head(k_pool), by_head(v_pool), lengths - 1,
                        tables, reference=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# -- (v) a later turn maps K, V and index pages in place -------------------

def _finishing_logits(engine):
    """Record what every finishing chunk returned (`last` >= 0)."""
    seen = []
    program = engine._chunk_prefill

    def recording(*args):
        out = program(*args)
        if int(args[-1]) >= 0:
            seen.append(np.asarray(out[0][0]))
        return out

    engine._chunk_prefill = recording
    return seen


def test_a_later_turn_maps_the_document_in_place():
    document = prompt_of(7, 100).tolist()          # 12 whole pages of 8
    first, second = prompt_of(8, 9).tolist(), prompt_of(9, 13).tolist()
    shared = tiny_engine()
    seen = _finishing_logits(shared)
    tables = []
    begin = shared._begin_prefill

    def noting(index, request):
        admitted = begin(index, request)
        tables.append(list(shared.seqs[index].pages))
        return admitted

    shared._begin_prefill = noting
    shared.generate([document + first], max_new_tokens=4)
    before = shared.stats()
    tokens = shared.generate([document + second], max_new_tokens=6)
    after = shared.stats()
    # the later turn's table begins with the document's own page ids
    assert tables[1][:12] == tables[0][:12] and after["prefix_hits"] == 1
    assert after["prefix_shared_tokens"] - before["prefix_shared_tokens"] \
        == 96
    assert after["prefill_computed_tokens"] \
        - before["prefill_computed_tokens"] == 100 + 13 - 96
    assert after["radix_evictions"] == 0 and after["leaked_pages"] == 0
    assert after["radix_evict_walks"] == 0  # nothing to drop: no walk
    # and nothing was copied anywhere: no dense staging, no pool copy
    assert all(s.dense_caches is None for s in shared.seqs)
    alone = tiny_engine(shared.params)
    seen_alone = _finishing_logits(alone)
    assert alone.generate([document + second], max_new_tokens=6) == tokens
    assert alone.stats()["prefix_shared_tokens"] == 0
    assert close(seen[-1], seen_alone[-1])
    assert close(seen[-1], keye_dsa_ref.logits(
        shared.params, np.asarray(document + second), keys_of())[-1])


# -- (vi) preemption and re-admission --------------------------------------

def _reference_greedy(params, prompts, max_new):
    return plain_greedy(
        rowwise(lambda row: keye_dsa_ref.logits(params, row, keys_of())),
        prompts, max_new)


def test_a_preempted_row_comes_back_and_ends_as_it_would_have():
    prompts = [prompt_of(61, 60).tolist(), prompt_of(62, 45).tolist(),
               prompt_of(63, 70).tolist()]
    # 23 pages for three rows that grow to 9 + 7 + 10: one is preempted
    short = tiny_engine(num_pages=24)
    got = short.generate(prompts, max_new_tokens=8)
    stats = short.stats()
    assert stats["preemptions"] >= 1 and stats["leaked_pages"] == 0
    assert got == _reference_greedy(short.params, prompts, 8)


# -- (vii) the shares add up -------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that the eight chips of a layer give (2 of 16
    experts each) are the uncut reference layer's."""
    whole = tiny_model(held_experts=(0, 16))
    tokens = prompt_of(11, 24)
    from ray_tpu.parallel.mesh import unbox
    params = unbox(whole.module().init(
        jax.random.PRNGKey(3), tokens[None])["params"])
    layer = params["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 64), jnp.float32)
    sh = keye_dsa_ref.shape_of(keys_of(held_experts=(0, 16)))
    want, _ = keye_dsa_ref.expert_layer(x, layer, sh)
    u = keye_dsa_ref._norm(x, layer["mlp_norm"]["scale"], sh.eps)

    def share(first):
        held = dict(layer["moe"])
        for name in ("w_in", "w_gate", "w_out"):
            held[name] = held[name][first:first + 2]
        return moe.RoutedExperts(
            num_experts=16, experts_per_token=2, held=(first, 2),
            mlp_dim=16, dtype=jnp.float32, param_dtype=jnp.float32,
            gated=True, scoring="softmax").apply({"params": held}, u, u)

    shares = [share(first) for first in range(0, 16, 2)]
    total = x + sum(out for out, _ in shares)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 2e-5
    # every (token, choice) pair landed on exactly one chip
    assert sum(int(pairs.sum()) for _, pairs in shares) == 24 * 2


# -- (viii) the softmax router ----------------------------------------------

def test_the_softmax_router_is_the_references():
    rng = np.random.default_rng(2)
    # (the reference norms its input: rows whose mean square is 1 already)
    u = rng.normal(size=(40, 64))
    u = jnp.asarray(u / np.sqrt(np.mean(u * u, -1, keepdims=True)),
                    jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 16)) / 8, jnp.float32)
    chosen, weights, probs = moe.softmax_top_k(u, router, 2)
    layer = {"mlp_norm": {"scale": jnp.ones((64,))}, "moe": {"router": router}}
    sh = keye_dsa_ref.shape_of(keys_of())
    _, ref_probs, ref_weights = keye_dsa_ref._route(u, layer, None, sh=sh)
    assert np.abs(np.asarray(probs) - np.asarray(ref_probs)).max() < 1e-6
    dense = np.zeros((40, 16), np.float32)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(weights), -1)
    assert np.abs(dense - np.asarray(ref_weights)).max() < 1e-6
    assert np.abs(np.asarray(weights).sum(-1) - 1).max() < 1e-6
    assert probs.dtype == weights.dtype == jnp.float32


def test_the_softmax_router_in_bf16_is_told_apart():
    """What a router computed in bf16 would choose differs from float32's
    on some token of a few hundred: the comparison has teeth."""
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(512, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 128)) / 8, jnp.float32)
    chosen, _, _ = moe.softmax_top_k(u, router, 8)
    low = jax.lax.top_k(jax.nn.softmax(
        (u.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)), -1), 8)[1]
    assert (np.sort(np.asarray(chosen), -1)
            != np.sort(np.asarray(low), -1)).any()


def test_moe_layer_routes_through_the_shared_function():
    """`MoELayer` and `RoutedExperts(scoring="softmax")` take one map."""
    logits = _scores(8, 12, 6)
    probs, weights, chosen = moe._top_k_routing(logits, 2)
    want = jax.nn.softmax(logits, -1)
    top, at = jax.lax.top_k(want, 2)
    assert np.array_equal(np.asarray(chosen), np.asarray(at))
    assert np.allclose(np.asarray(weights),
                       np.asarray(top / top.sum(-1, keepdims=True)))
    assert np.array_equal(np.asarray(probs), np.asarray(want))
    layer = moe.MoELayer(num_experts=4, embed_dim=8, mlp_dim=16)
    x = _scores(9, 2, 40).reshape(2, 5, 8)
    params = layer.init(jax.random.PRNGKey(0), x)
    out, aux = layer.apply(params, x)
    assert out.shape == x.shape and np.isfinite(float(aux))


# -- (ix) the counters ---------------------------------------------------------

def test_generation_through_the_tick_matches_the_reference_and_counts():
    engine = tiny_engine()
    lengths = (37, 5, 70)
    prompts = [prompt_of(21 + i, n).tolist() for i, n in enumerate(lengths)]
    assert engine.generate(prompts, max_new_tokens=6) \
        == _reference_greedy(engine.params, prompts, 6)
    stats = engine.stats()
    assert stats["leaked_pages"] == 0 and stats["preemptions"] == 0
    assert stats["layer_kinds"] == ["pc", "pc"]
    assert stats["sparse_kernel"] == "xla"
    assert stats["prefill_computed_tokens"] == 37 + 5 + 70
    # 37 -> 32 + 16; 5 -> 16; 70 -> 32 + 32 + 16: the chunks' last real rows
    assert stats["prefill_ctx_rows"] == (32 + 37) + 5 + (32 + 64 + 70)
    # five decode steps a row (the first token comes from the prefill),
    # step j of a row of n scores n + j keys and selects min(n + j, 16)
    contexts = [n + j for n in lengths for j in range(1, 6)]
    assert stats["index_rows_scanned"] == stats["sparse_rows_context"] \
        == sum(contexts)
    assert stats["sparse_rows_selected"] == sum(min(c, 16) for c in contexts)
    assert stats["index_pages_rowwise"] == stats["index_pages_distinct"] \
        == sum(-(-c // 8) for c in contexts)
    pairs = np.asarray(stats["expert_pairs"])
    assert pairs.shape == (2, 2) and pairs.sum() <= 2 * 3 * 5 * 2
    assert stats["index_cache_bytes"] == 2 * 96 * 8 * 128 * 4
    from ray_tpu._internal import accel
    tick = next(r for r in accel.step_summary() if r["kind"] == "tick")
    assert tick["counters"]["index_rows_scanned"] > 0
    assert tick["counters"]["sparse_rows_selected"] > 0


def test_rows_on_one_document_score_the_same_pages():
    document = prompt_of(71, 64).tolist()
    engine = tiny_engine()
    engine.generate([document + [3]], max_new_tokens=2)
    before = engine.stats()
    engine.generate([document + [5, 6], document + [7]], max_new_tokens=4)
    after = engine.stats()
    rowwise_ = after["index_pages_rowwise"] - before["index_pages_rowwise"]
    distinct = after["index_pages_distinct"] - before["index_pages_distinct"]
    assert 0 < distinct < rowwise_


def test_no_program_copies_a_pool(engine):
    assert engine.pool_copies(engine.decode_program_text()) == 0
    assert engine.pool_copies(engine.lower_chunk().compile().as_text()) == 0


def test_the_engine_keeps_three_pools_a_layer(engine):
    cfg = engine.config
    assert len(engine.k_pages) == len(engine.v_pages) \
        == len(engine.index_pages) == 2
    assert engine.k_pages[0].shape == engine.v_pages[0].shape \
        == (1, cfg.num_pages, cfg.page_size, 2 * 16)
    assert engine.index_pages[0].shape \
        == (1, cfg.num_pages, cfg.page_size, 128)
    assert cfg.model.index_cache() == 128
    assert tiny_model(index_head_dim=192).index_cache() == 256


@pytest.mark.parametrize("what", ["prefill_only", "submit_prefilled",
                                  "tensor_mesh"])
def test_what_is_not_built_for_this_model_says_so(engine, what):
    with pytest.raises(NotImplementedError, match="index keys|sparse"):
        if what == "prefill_only":
            engine.prefill_only([1, 2, 3])
        elif what == "submit_prefilled":
            engine.submit_prefilled(
                GenerationRequest(prompt_tokens=[1, 2], max_new_tokens=2,
                                  request_id="x"), [], None)
        else:
            from jax.sharding import Mesh
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
            PagedLLMEngine(PagedEngineConfig(
                model=tiny_model(), max_batch=2, max_len=64, page_size=8,
                num_pages=32, prefill_buckets=(16,)), mesh=mesh)


def test_the_tiny_config_keeps_the_published_names():
    model = tiny_model()
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (4, 2, 16)
    assert (model.index_heads, model.index_head_dim, model.index_topk) \
        == (2, 8, 16)
    assert model.num_experts == 16 and model.held_experts == (2, 2)
    assert isinstance(model.module(), keye_dsa.KeyeDSAModel)
