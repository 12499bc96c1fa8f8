"""Sarvam-105B (multi-head latent attention beside routed SwiGLU experts and
a shared one) on the CPU, seeded random weights, a tiny config in the
published ratios: the model in its two forms, the latent decode kernel and
the paged engine's latent pools against the plain float32 reference
(benchmarks/reference/sarvam_mla_ref.py). Logits, never tokens."""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness.builders_sarvam_mla import (  # noqa: E402
    sarvam_mla_model)
from benchmarks.reference import sarvam_mla_ref  # noqa: E402
from plain_greedy import plain_greedy, rowwise  # noqa: E402
from ray_tpu.llm import GenerationRequest  # noqa: E402
from ray_tpu.llm.paged import (PagedEngineConfig, PagedLLMEngine,  # noqa: E402
                               pool_copies)
from ray_tpu.models import sarvam_mla  # noqa: E402
from ray_tpu.models.sarvam_mla import SarvamMLAConfig  # noqa: E402
from ray_tpu.ops import latent_attention as la  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}
# Published key names at toy widths, in the published ratios: the latent 4 x
# the nope width, rope half of it, 2 experts a token of 16, an eighth of
# them held, a leading dense layer and two expert layers.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "q_head_dim": 12,
    "v_head_dim": 8, "head_dim": 36, "first_k_dense_replace": 1,
    "num_experts": 2, "held_experts": [2, 2], "published": {"num_experts": 16},
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
    "max_position_embeddings": 131072}

# Everything here is float32 on the CPU, the system's arithmetic and the
# reference's alike; they differ in the order of their sums (absorbed
# against expanded products, a blocked running softmax against a dense one,
# every held expert on every token against each token's chosen experts).
# The largest logit difference read over three seeds is 6.1e-6 of a logit
# spread of 1.0; with the cached latent rows rounded to 8-bit floats (e4m3)
# it is 0.34 to 3.0.
TOLERANCE = 3e-5


def tiny_model(**overrides) -> SarvamMLAConfig:
    return dataclasses.replace(
        sarvam_mla_model(TINY), dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference", **overrides)


def tiny_engine(params=None, **model_overrides) -> PagedLLMEngine:
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(**model_overrides), max_batch=3, max_len=160,
        page_size=8, num_pages=96, prefill_buckets=(16, 32)), params=params)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def keys_of(**overrides):
    return dict(TINY, **overrides)


def prompt_of(seed: int, n: int):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], n)


def spread(logits) -> float:
    return float(np.asarray(logits).std(-1).mean())


def test_tiny_config_keeps_the_published_ratios():
    cfg = tiny_model()
    assert cfg.kv_lora_rank == 4 * cfg.qk_nope_head_dim
    assert cfg.qk_rope_head_dim * 2 == cfg.qk_nope_head_dim == cfg.v_head_dim
    assert cfg.latent_dim == TINY["head_dim"]
    assert cfg.num_experts == 8 * cfg.held_experts[1]
    assert cfg.layer_caches() == ((True, False, False), (True, False, True),
                                  (True, False, True))
    # a cached row takes whole 128-lane tiles; its value is the latent
    assert cfg.latent_cache() == (128, 32)
    assert SarvamMLAConfig().latent_cache() == (640, 512)
    assert SarvamMLAConfig().latent_dim == 576
    assert abs(SarvamMLAConfig().softmax_scale
               - 192 ** -0.5 * 1.3688879454113936 ** 2) < 1e-9


@pytest.mark.parametrize("length", [1, 7, 40])
def test_forward_matches_the_reference(engine, length):
    tokens = prompt_of(length, length)
    got = engine.model.apply({"params": engine.params}, tokens[None])[0]
    want = sarvam_mla_ref.logits(engine.params, tokens, keys_of())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        < TOLERANCE * spread(want)


def _chunks(engine, prompt, pools, table, start=0, rows=None):
    """`prompt[start:]` through the engine's own chunk program into
    `pools` (one row's `table`); the logits of every real position (the
    program gives one row a call: `last`)."""
    cfg = engine.config
    out = []
    off = start
    while off < len(prompt):
        rem = len(prompt) - off
        size = engine._bucket(min(rem, cfg.prefill_buckets[-1]))
        take = min(rem, size)
        tokens = np.zeros((1, size), np.int32)
        tokens[0, :take] = prompt[off:off + take]
        positions = np.arange(off, off + size, dtype=np.int32)[None]
        for last in range(take):
            lg, pools = engine._chunk_prefill(
                engine.params, jnp.asarray(tokens), jnp.asarray(positions),
                pools, jnp.asarray(off, jnp.int32), jnp.asarray(table),
                jnp.asarray(take, jnp.int32), jnp.asarray(last, jnp.int32))
            if rows is None or off + last in rows:
                out.append(np.asarray(lg[0]))
            elif rows is not None:
                break
        off += take
    return np.stack(out) if out else None, pools


def _decode_logits(engine, pools, table, start, fed):
    """`fed` tokens through a paged decode program of the engine's shapes
    that returns logits (the engine's own returns ids), row 1 live."""
    cfg = engine.config
    B = cfg.max_batch
    kinds = cfg.model.layer_caches()

    def program(params, pools, counters, active, tables, lengths, tokens):
        # what the model's paged decode path is handed a layer
        counts_of = iter(counters)
        caches = []
        for pool, (_, _, counts) in zip(pools, kinds):
            cache = {"pool": pool, "active": active,
                     "block_tables": tables, "lengths": lengths}
            if counts:
                cache["pairs"], cache["steps"] = next(counts_of)
            caches.append(cache)
        lg, new = engine.model.apply(
            {"params": params}, tokens, positions=lengths[:, None],
            kv_caches=caches, cache_index=None)
        return (lg[:, -1], [kept[0] for kept in new],
                [tuple(kept[1:]) for kept in new if len(kept) > 1])

    program = jax.jit(program)
    tables = np.zeros((B, cfg.pages_per_seq), np.int32)
    tables[1] = table
    active = np.zeros((B,), bool)
    active[1] = True
    counters = cfg.model.init_counters()
    rows = []
    for i, token in enumerate(fed):
        lengths = np.zeros((B,), np.int32)
        lengths[1] = start + i
        tokens = np.zeros((B, 1), np.int32)
        tokens[1, 0] = token
        lg, pools, counters = program(
            engine.params, pools, counters, jnp.asarray(active),
            jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(tokens))
        rows.append(np.asarray(lg[1]))
    return np.stack(rows), pools, counters


def _row_table(engine, pages):
    table = np.zeros((engine.config.pages_per_seq,), np.int32)
    table[:len(pages)] = pages
    return table


def _fresh_pools(engine):
    return [jnp.zeros_like(p) for p in engine.k_pages]


def test_absorbed_chunks_match_the_expanded_whole_sequence(engine):
    """The chunk program (absorbed, blocked, through pages) against the
    model's own whole-sequence pass (expanded, dense)."""
    prompt = prompt_of(3, 70)        # 32 + 32 + a tail of 6 in the 16 bucket
    table = _row_table(engine, [9, 4, 17, 2, 30, 31, 8, 40, 41, 12])
    got, _ = _chunks(engine, prompt, _fresh_pools(engine), table)
    want = engine.model.apply({"params": engine.params}, prompt[None])[0]
    assert np.abs(got - np.asarray(want)).max() < TOLERANCE * spread(want)


def test_chunks_then_decode_through_latent_pages_match_the_reference(engine):
    prompt, fed = prompt_of(4, 44), prompt_of(5, 24)
    table = _row_table(engine, list(range(20, 29)))
    prefill, pools = _chunks(engine, prompt, _fresh_pools(engine), table)
    decode, _, counters = _decode_logits(engine, pools, table, 44, fed)
    sequence = np.concatenate([prompt, fed])
    want = np.asarray(sarvam_mla_ref.logits(engine.params, sequence,
                                            keys_of()))
    # decode step i fed token i at position 44 + i
    assert np.abs(prefill - want[:44]).max() < TOLERANCE * spread(want)
    assert np.abs(decode - want[44:]).max() < TOLERANCE * spread(want)
    # the live row alone reached the held experts' counters
    for pairs, steps in counters:
        assert int(pairs.sum()) == int(steps.sum()) <= 24 * 2


def test_a_latent_row_in_8_bit_floats_fails_the_tolerance(engine):
    prompt, fed = prompt_of(4, 44), prompt_of(5, 8)
    table = _row_table(engine, list(range(20, 29)))
    _, pools = _chunks(engine, prompt, _fresh_pools(engine), table, rows=())
    pools = [p.astype(jnp.float8_e4m3fn).astype(p.dtype) for p in pools]
    decode, _, _ = _decode_logits(engine, pools, table, 44, fed)
    want = np.asarray(sarvam_mla_ref.logits(
        engine.params, np.concatenate([prompt, fed]), keys_of()))[44:]
    worst = np.abs(decode - want).max() / spread(want)
    assert worst > 0.1, worst


def test_the_reference_takes_sequences_with_one_beginning_as_one_array(
        engine, monkeypatch):
    """A trunk and two continuations as one array (`branch`, `positions`)
    give each continuation what its own sequence alone gives: logits, the
    latent rows a cache must hold and the attended values. The blocks are
    shrunk so that heads, queries and positions each take several."""
    for name, size in (("HEAD_BLOCK", 2), ("QUERY_BLOCK", 16),
                       ("ROW_BLOCK", 24)):
        monkeypatch.setattr(sarvam_mla_ref, name, size)
    trunk, tails = prompt_of(11, 37), [prompt_of(12, 9), prompt_of(13, 21)]
    tokens = np.concatenate([trunk] + tails)
    positions = np.concatenate(
        [np.arange(37)] + [37 + np.arange(len(t)) for t in tails])
    branch = np.concatenate([np.zeros(37, int)] + [
        np.full(len(t), b + 1) for b, t in enumerate(tails)])
    wanted = np.arange(37, len(tokens))
    got, more = sarvam_mla_ref.logits(
        engine.params, tokens, keys_of(), positions=positions,
        branch=branch, rows=wanted, details=(0, 2))
    at = 37
    for tail in tails:
        alone = np.concatenate([trunk, tail])
        want, own = sarvam_mla_ref.logits(
            engine.params, alone, keys_of(), rows=np.arange(37, len(alone)),
            details=(0, 2))
        rows = slice(at - 37, at - 37 + len(tail))
        assert np.abs(np.asarray(got)[rows] - np.asarray(want)).max() \
            < TOLERANCE * spread(want)
        for layer in (0, 2):
            assert np.allclose(more["latent"][layer][at:at + len(tail)],
                               own["latent"][layer][37:], atol=1e-5)
            assert np.allclose(more["attended"][layer][rows],
                               own["attended"][layer], atol=1e-5)
        assert len(more["selection"]) == 2
        at += len(tail)


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


def test_a_second_ask_maps_the_shared_pages_and_computes_the_tail_alone():
    document = prompt_of(7, 50).tolist()           # 6 whole pages of 8
    first, second = prompt_of(8, 9).tolist(), prompt_of(9, 13).tolist()
    shared = tiny_engine()
    seen = _finishing_logits(shared)
    shared.generate([document + first], max_new_tokens=4)
    before = shared.stats()
    tokens = shared.generate([document + second], max_new_tokens=6)
    after = shared.stats()
    # six pages of the document came from the radix; the tail alone ran
    assert after["prefix_shared_tokens"] - before["prefix_shared_tokens"] \
        == 48
    assert after["prefill_computed_tokens"] \
        - before["prefill_computed_tokens"] == 50 + 13 - 48
    assert after["prefix_hits"] - before["prefix_hits"] == 1
    assert after["radix_evictions"] == 0 and after["leaked_pages"] == 0
    assert after["radix_evict_walks"] == 0  # nothing to drop: no walk
    # and nothing was copied anywhere: the dense staging never existed
    assert all(s.dense_caches is None for s in shared.seqs)
    alone = tiny_engine(shared.params)
    seen_alone = _finishing_logits(alone)
    tokens_alone = alone.generate([document + second], max_new_tokens=6)
    assert alone.stats()["prefix_shared_tokens"] == 0
    assert np.abs(seen[-1] - seen_alone[-1]).max() \
        < TOLERANCE * spread(seen_alone[-1])
    assert tokens == tokens_alone
    want = sarvam_mla_ref.logits(
        shared.params, np.asarray(document + second), keys_of())[-1]
    assert np.abs(seen[-1] - np.asarray(want)).max() \
        < TOLERANCE * spread(want)


def test_the_eight_shares_add_up_to_the_uncut_layer(engine):
    """The routed parts that the eight chips of a layer give (2 of 16
    experts each), with the shared expert counted once, are the uncut
    reference layer."""
    whole = tiny_model(held_experts=(0, 16))
    module = whole.module()
    tokens = prompt_of(11, 24)
    from ray_tpu.parallel.mesh import unbox
    params = unbox(module.init(jax.random.PRNGKey(3), tokens[None])["params"])
    layer = params["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 64), jnp.float32)
    sh = sarvam_mla_ref.shape_of(keys_of(held_experts=(0, 16)))
    want, _ = sarvam_mla_ref.expert_layer(x, layer, sh)

    def share(first):
        part = tiny_model(held_experts=(first, 2))
        routed = dict(layer["moe"]["routed"])
        for name in ("w_in", "w_gate", "w_out"):
            routed[name] = routed[name][first:first + 2]
        u = sarvam_mla_ref._norm(x, layer["mlp_norm"]["scale"], sh.eps)
        out, pairs = sarvam_mla.SharedAndRouted(part).apply(
            {"params": dict(layer["moe"], routed=routed)}, u[None])
        return out[0], pairs

    u = sarvam_mla_ref._norm(x, layer["mlp_norm"]["scale"], sh.eps)
    shared = sarvam_mla_ref._swiglu(u, layer["moe"]["shared"], 64)
    shares = [share(first) for first in range(0, 16, 2)]
    total = x + shared + sum(out - shared for out, _ in shares)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 2e-5
    # every (token, choice) pair landed on exactly one chip
    assert sum(int(pairs.sum()) for _, pairs in shares) == 24 * 2


def test_the_yarn_table_follows_the_written_out_rule_beyond_4096():
    """At the published sizes: pairs 0-9 turn at theta_j (more than 32
    turns over the original 4096), pairs 23-31 at theta_j / 40 (fewer than
    one), a linear ramp between; cos and sin unscaled."""
    cfg = SarvamMLAConfig()
    d = 64
    lo = math.floor(d * math.log(4096 / (2 * math.pi * 32))
                    / (2 * math.log(10000)))
    hi = math.ceil(d * math.log(4096 / (2 * math.pi * 1))
                   / (2 * math.log(10000)))
    assert (lo, hi) == (10, 23)
    want = []
    for j in range(d // 2):
        theta = 10000 ** (-2 * j / d)
        r = 1 - min(max((j - lo) / (hi - lo), 0.0), 1.0)
        want.append(theta / 40 * (1 - r) + theta * r)
    got = sarvam_mla.yarn_inverse_frequencies(cfg)
    assert np.allclose(got, want, rtol=1e-6)
    assert got[9] == np.float32(10000 ** (-18 / 64))
    assert np.isclose(got[23], 10000 ** (-46 / 64) / 40, rtol=1e-6)
    positions = np.asarray([[0, 4095, 4097, 20000, 33535]])
    cos, sin = sarvam_mla._rotary(cfg, jnp.asarray(positions))
    angles = positions[0][:, None].astype(np.float64) * np.asarray(want)
    assert np.abs(np.asarray(cos)[0, :, 0] - np.cos(angles)).max() < 5e-3
    assert np.abs(np.asarray(sin)[0, :, 0] - np.sin(angles)).max() < 5e-3
    # the reference writes the same table out on its own
    sh = sarvam_mla_ref.shape_of(dict(TINY, qk_rope_head_dim=64))
    ref_cos, _ = sarvam_mla_ref.rotary_table(sh, positions[0])
    assert np.abs(np.asarray(ref_cos) - np.asarray(cos)[0, :, 0]).max() < 1e-6


PAGE, WIDTH, VALUE = 16, 256, 128
DEAD = -1
# Pages and tokens of one compute step of the kernel (`la._CHUNK_TOKENS`): a
# shared span is whole steps.
STEP = la._chunk_pages(PAGE)
TOKENS = STEP * PAGE
# id: (heads, tokens cached before this one per row (DEAD: a dead row on
# the null page), table width in pages, block override, pool type[, the
# documents: (rows, leading pages those rows' tables have in common), a
# later one over an earlier one's]). Under a block override shorter than a
# step the kernel's compute step is the block (4 pages: 64 tokens); the
# span's unit stays STEP.
KERNEL_CASES = {
    "dead-short-long": (8, [DEAD, 40, 150], 10, 4, jnp.float32),
    "page-less-one-token": (8, [PAGE - 2, 70], 8, 4, jnp.float32),
    "exactly-one-page": (8, [PAGE - 1, 70], 8, 4, jnp.float32),
    "exactly-one-block": (8, [4 * PAGE - 1, 70], 8, 4, jnp.float32),
    "block-and-one-token": (8, [4 * PAGE, 70], 8, 4, jnp.float32),
    "full-table": (8, [8 * PAGE - 1, 8 * PAGE - 1], 8, 4, jnp.float32),
    # a block of two steps: a row attends cached + 1 tokens
    "chunk-edges-inside-a-block": (
        16, [TOKENS - 2, TOKENS - 1, TOKENS, 2 * TOKENS + 350],
        2 * STEP + 32, 2 * STEP, jnp.float32),
    "one-step-less-one-token": (8, [TOKENS - 2, 70], STEP + 8, None,
                                jnp.float32),
    "exactly-one-step": (8, [TOKENS - 1, 70], STEP + 8, None, jnp.float32),
    "one-step-and-one-token": (8, [TOKENS, 70], STEP + 8, None,
                               jnp.float32),
    "table-not-whole-blocks": (8, [10 * PAGE - 1, 9 * PAGE + 3], 10, 4,
                               jnp.float32),
    "every-row-dead": (8, [DEAD, DEAD], 6, 4, jnp.float32),
    "derived-block": (64, [TOKENS + 76, TOKENS // 2 - 1, DEAD], STEP + 32,
                      None, jnp.float32),
    "bf16-pool": (8, [DEAD, 100, 300], 24, None, jnp.bfloat16),
    # rows on one document: its pages copied once for a group
    "two-rows-tails-of-two-lengths": (
        8, [TOKENS + 400, TOKENS + 130], STEP + 32, 4, jnp.float32,
        [((0, 1), STEP + 6)]),
    "three-rows": (8, [TOKENS + 500, TOKENS + 100, TOKENS + 220],
                   STEP + 32, 4, jnp.float32, [((0, 1, 2), STEP + 2)]),
    "more-rows-than-a-group": (
        8, [TOKENS + n for n in (70, 80, 60, 120, 50)], STEP + 16, 4,
        jnp.float32, [((0, 1, 2, 3, 4), STEP + 1)]),
    "nested-prefixes": (
        8, [2 * TOKENS + 250, 2 * TOKENS + 150, TOKENS + 280],
        2 * STEP + 32, 4, jnp.float32,
        [((0, 1, 2), STEP + 6), ((0, 1), 2 * STEP + 2)]),
    "span-ends-inside-a-copy-block": (
        8, [2 * TOKENS + 250, 2 * TOKENS + 50], 2 * STEP + 32, 2 * STEP,
        jnp.float32, [((0, 1), STEP + 6)]),
    "span-ends-on-a-chunk-edge": (
        8, [2 * TOKENS + 150, 2 * TOKENS + 50], 2 * STEP + 16, 2 * STEP,
        jnp.float32, [((0, 1), STEP)]),
    "a-member-ends-with-the-span": (
        8, [TOKENS, TOKENS + 400], STEP + 32, 4, jnp.float32,
        [((0, 1), STEP)]),
    "tail-shorter-than-a-page-after-a-shared-span": (
        8, [TOKENS + 5, TOKENS + 11], STEP + 8, None, jnp.float32,
        [((0, 1), STEP)]),
    # one page short of a step: nothing is shared, each row alone
    "shared-span-rounds-down-to-no-step": (
        8, [TOKENS + 100, TOKENS + 40], STEP + 8, None, jnp.float32,
        [((0, 1), STEP - 1)]),
    "dead-row-between-members": (
        8, [TOKENS + 400, DEAD, TOKENS + 380], STEP + 32, 4, jnp.float32,
        [((0, 2), STEP + 6)]),
    "two-dead-rows-do-not-group": (
        8, [DEAD, TOKENS + 70, DEAD, TOKENS + 80], STEP + 16, 4,
        jnp.float32, [((1, 3), STEP + 2)]),
    "members-out-of-order": (
        8, [TOKENS + n for n in (70, 190, 120, 60, 100)], STEP + 16, 4,
        jnp.float32, [((4, 0, 2), STEP + 1), ((3, 1), STEP)]),
    "no-row-shares": (8, [300, 420, 350], 32, None, jnp.float32),
    "bf16-pool-shared": (
        16, [DEAD, TOKENS + 400, TOKENS + 130, TOKENS + 600], STEP + 40,
        None, jnp.bfloat16, [((1, 2, 3), STEP + 6)]),
    "derived-block-shared": (
        64, [2 * TOKENS + 250, TOKENS + 900], 2 * STEP + 32, None,
        jnp.float32, [((0, 1), STEP + 36)]),
}


def kernel_case(case):
    """(q, pool, lengths, tables, documents) of a case: every page random
    (a read of a wrong page shows), rows' pages scattered, unused table
    entries on the null page, a document's pages its first row's."""
    heads, cached, width, _, dtype, *documents = KERNEL_CASES[case]
    rows = len(cached)
    rng = np.random.RandomState(len(case))
    pages = 1 + rows * width
    pool = jnp.asarray(rng.randn(1, pages, PAGE, WIDTH), dtype)
    q = jnp.asarray(rng.randn(rows, heads, WIDTH) * WIDTH ** -0.5, dtype)
    free = 1 + rng.permutation(rows * width)
    tables = np.zeros((rows, width), np.int32)
    lengths = np.zeros((rows,), np.int32)
    for r, n in enumerate(cached):
        if n != DEAD:
            held = n // PAGE + 1
            tables[r, :held] = free[r * width:r * width + held]
            lengths[r] = n
    documents = documents[0] if documents else []
    for members, leading in documents:
        for r in members[1:]:
            tables[r, :leading] = tables[members[0], :leading]
    return q, pool, lengths, tables, documents


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_latent_kernel_matches_the_gather_path(case):
    """`_latent_attend_pallas` under the TPU interpreter against the
    gather fallback in float32, and against the kernel of a program a row
    (PR 45's, at this module's step length) bit for bit: a group's shared
    span is whole steps, so every row's sums are taken in the order they
    are when it attends alone."""
    from latent_rowwise_kernel import rowwise_latent_attend
    heads, cached, _, block_pages, dtype = KERNEL_CASES[case][:5]
    q, pool, lengths, tables, documents = kernel_case(case)
    rows = len(cached)
    # a row goes with the rows of the longest document it is on, if that
    # is a whole step at least
    longest = {r: d for d, (members, leading) in enumerate(documents)
               if leading >= STEP for r in members}
    together = [r for r, d in longest.items()
                if list(longest.values()).count(d) > 1]
    assert bool(together) == (
        bool(documents) and case != "shared-span-rounds-down-to-no-step")
    schedule = la.share_schedule(tables, lengths, PAGE)
    assert sorted(schedule.order[schedule.size > 1]) == sorted(together)
    assert set(schedule.shared[schedule.size > 1]) <= {
        leading // STEP * STEP for _, leading in documents}
    got = la._latent_attend_pallas(
        q, pool, jnp.asarray(lengths + 1), jnp.asarray(tables),
        value_dim=VALUE, block_pages=block_pages)
    want = la.latent_attend(
        q.astype(jnp.float32), pool.astype(jnp.float32),
        jnp.asarray(lengths), jnp.asarray(tables), value_dim=VALUE,
        reference=True)
    live = np.asarray([n != DEAD for n in cached])
    tolerance = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert got.shape == (rows, heads, VALUE) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max(initial=0) \
        < tolerance
    alone = rowwise_latent_attend(
        q, pool, jnp.asarray(lengths + 1), jnp.asarray(tables),
        value_dim=VALUE, block_pages=block_pages)
    assert np.array_equal(np.asarray(got)[live], np.asarray(alone)[live])


def test_the_engine_keeps_one_latent_pool_a_layer_and_no_v_pool(engine):
    cfg = engine.config
    assert len(engine.k_pages) == 3 and engine.v_pages == []
    assert engine.k_pages[0].shape == (1, cfg.num_pages, cfg.page_size, 128)
    stats = engine.stats()
    assert stats["layer_kinds"] == ["p", "pc", "pc"]
    assert stats["latent_kernel"] == "gather"
    assert "paged_kernel" not in stats
    assert stats["hbm_cache_bytes"] \
        == 3 * np.prod(engine.k_pages[0].shape) * 4
    assert stats["state_bytes"] == 0
    assert cfg.pages_per_seq == 20


def _reference_greedy(params, prompts, max_new):
    return plain_greedy(
        rowwise(lambda row: sarvam_mla_ref.logits(params, row, keys_of())),
        prompts, max_new)


def test_generation_through_the_tick_matches_the_reference_and_counts():
    engine = tiny_engine()
    prompts = [prompt_of(21, 37).tolist(), prompt_of(22, 5).tolist(),
               prompt_of(23, 70).tolist()]
    assert engine.generate(prompts, max_new_tokens=6) \
        == _reference_greedy(engine.params, prompts, 6)
    stats = engine.stats()
    assert stats["leaked_pages"] == 0 and stats["preemptions"] == 0
    assert stats["prefill_computed_tokens"] == 37 + 5 + 70
    # 37 -> 32 + 16; 5 -> 16; 70 -> 32 + 32 + 16: the chunks' last real rows
    assert stats["prefill_ctx_rows"] == (32 + 37) + 5 + (32 + 64 + 70)
    # five decode steps a row (the first token comes from the prefill)
    steps = 5
    assert stats["latent_rows_attended"] \
        == sum(n * steps + sum(range(1, steps + 1)) for n in (37, 5, 70))
    assert stats["latent_pages_rowwise"] == stats["latent_pages_distinct"] > 0
    pairs = np.asarray(stats["expert_pairs"])
    assert pairs.shape == (2, 2) and pairs.sum() <= 2 * 3 * steps * 2
    from ray_tpu._internal import accel
    tick = next(r for r in accel.step_summary() if r["kind"] == "tick")
    assert tick["counters"]["latent_rows_attended"] > 0


@pytest.fixture(scope="module")
def lane_wide_engine():
    """Experts of whole lane tiles [128, 128] and a bucket on each side of
    `moe.SORTED_FROM_TOKENS`."""
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(hidden_size=128, moe_intermediate_size=128),
        max_batch=2, max_len=704, page_size=8, num_pages=200,
        prefill_buckets=(128, 512)))


@pytest.mark.parametrize("length,chunks,on_sorted", [(100, 1, 0),
                                                     (600, 2, 1)])
def test_chunks_on_the_sorted_expert_form_are_counted(lane_wide_engine,
                                                      length, chunks,
                                                      on_sorted):
    """The engine counts a chunk on the routed experts' sorted form by the
    rule its program was traced by (`moe.sorted_form`: the bucket and the
    experts' shapes): of a 600-token prompt the 512 bucket's, not the
    tail's 128; of a 100-token prompt none. The tokens are the reference's
    through either form (off the TPU the grouped products are XLA's)."""
    engine = lane_wide_engine
    assert engine._sorted_buckets == {512}
    before = engine.stats()
    prompts = [prompt_of(length, length).tolist()]
    assert engine.generate(prompts, max_new_tokens=3) \
        == _reference_greedy(engine.params, prompts, 3)
    after = engine.stats()
    assert after["prefill_chunks"] - before["prefill_chunks"] == chunks
    assert after["prefill_chunks_sorted"] \
        - before["prefill_chunks_sorted"] == on_sorted
    if on_sorted:
        from ray_tpu._internal import accel
        tick = next(r for r in accel.step_summary() if r["kind"] == "tick")
        assert tick["counters"]["prefill_chunks_sorted"] >= on_sorted


def seeded_tables(seed, rows_on, rows=12, page=64, whole=False):
    """(tables, lengths) of `rows` rows: a document for each entry of
    `rows_on` (the rows on it), two to six compute steps of pages (whole
    steps if `whole`), every row with a tail of its own; the rest dead;
    rows shuffled."""
    rng = np.random.default_rng(seed)
    step = la._chunk_pages(page)
    width = 6 * step + 8
    free = list(1 + rng.permutation(rows * width))
    tables = np.zeros((rows, width), np.int32)
    lengths = np.zeros((rows,), np.int32)
    at = list(rng.permutation(rows))
    for n in rows_on:
        leading = int(rng.integers(2, 7)) * step if whole \
            else int(rng.integers(2 * step, 6 * step + 1))
        document = [free.pop() for _ in range(leading)]
        for _ in range(n):
            r = at.pop()
            lengths[r] = leading * page + int(rng.integers(1, 5 * page))
            held = lengths[r] // page + 1
            tables[r, :leading] = document
            tables[r, leading:held] = [free.pop()
                                       for _ in range(held - leading)]
    return tables, lengths


def page_counts(tables, lengths, page=64, group=la._GROUP_ROWS):
    """(a row, once, as the schedule has the kernel copy them)."""
    rowwise = int(((lengths // page + 1) * (lengths > 0)).sum())
    distinct = len(set(tables.ravel().tolist()) - {0})
    schedule = la.share_schedule(tables, lengths, page, group)
    return rowwise, distinct, rowwise - int(la.pages_spared(schedule))


@pytest.mark.parametrize("seed", range(6))
def test_the_schedule_is_one_from_numpy_and_from_jax(seed):
    """What the decode program makes of its arguments on the device is
    what the engine counts from the arrays it staged; a group holds at
    most `_GROUP_ROWS`, its rows stand together behind its first, and its
    shared span is whole compute steps of pages that every member holds,
    the same ids, and attends in full."""
    rows_on = [[3, 2, 1, 1], [5, 1, 1], [2, 2, 2, 2, 2], [9], [1] * 7,
               [4, 4, 3]][seed]
    tables, lengths = seeded_tables(seed, rows_on)
    host = la.share_schedule(tables, lengths, 64)
    device = jax.jit(lambda t, n: la.share_schedule(t, n, 64))(
        jnp.asarray(tables), jnp.asarray(lengths))
    for ours, theirs in zip(host, device):
        assert ours.dtype == np.int32
        assert np.array_equal(ours, np.asarray(theirs))
    assert sorted(host.order) == list(range(len(lengths)))
    for at, (lead, size, shared) in enumerate(zip(*host[1:])):
        assert lead <= at < lead + size <= len(lengths)
        assert 1 <= size <= la._GROUP_ROWS
        assert (host.lead[lead], host.size[lead], host.shared[lead]) \
            == (lead, size, shared)
        assert (shared > 0) == (size > 1)
        assert shared % la._chunk_pages(64) == 0
        assert shared <= lengths[host.order[at]] // 64
        assert np.array_equal(tables[host.order[at], :shared],
                              tables[host.order[lead], :shared])
    rowwise, distinct, copied = page_counts(tables, lengths)
    assert distinct <= copied <= rowwise
    assert (copied < rowwise) == (max(rows_on) > 1)


@pytest.mark.parametrize("rows_on,spares", [
    ([1] * 8, "nothing"), ([2], "all"), ([3], "all"), ([4], "all"),
    ([2, 3, 4], "all"), ([6], "some")])
def test_the_pages_copied_lie_between_once_and_a_row(rows_on, spares):
    """No sharing: a page a row, as before. At most `_GROUP_ROWS` rows on
    a document of whole compute steps: every page once. More: once a
    group."""
    tables, lengths = seeded_tables(len(rows_on), rows_on, whole=True)
    rowwise, distinct, copied = page_counts(tables, lengths)
    assert distinct <= copied <= rowwise
    if spares == "nothing":
        assert copied == rowwise == distinct
    elif spares == "all":
        assert copied == distinct < rowwise
    else:
        assert distinct < copied < rowwise
        assert page_counts(tables, lengths, group=6)[2] == distinct


def test_rows_on_one_document_share_its_pages():
    """A document of one compute step of the kernel's and a page: the
    two rows that ask it together hold its pages once, and the schedule
    has the kernel copy the step's pages once."""
    step = la._chunk_pages(8)
    tokens = (step + 1) * 8
    engine = PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=3, max_len=tokens + 56, page_size=8,
        num_pages=3 * step + 64, prefill_buckets=(16, 32)))
    document = prompt_of(31, tokens).tolist()
    engine.generate([document + [3]], max_new_tokens=2)
    before = engine.stats()
    assert before["latent_pages_copied"] == before["latent_pages_rowwise"] \
        == before["latent_pages_distinct"] > 0
    engine.generate([document + [5, 6], document + [7]], max_new_tokens=8)
    stats = engine.stats()
    assert stats["prefix_hits"] == 2 and stats["leaked_pages"] == 0
    assert stats["latent_pages_rowwise"] > stats["latent_pages_distinct"]
    assert stats["latent_pages_distinct"] <= stats["latent_pages_copied"] \
        < stats["latent_pages_rowwise"]
    # seven steps of two rows, a compute step's pages spared in each
    assert stats["latent_pages_rowwise"] - stats["latent_pages_copied"] \
        == 7 * step
    from ray_tpu._internal import accel
    tick = next(r for r in accel.step_summary() if r["kind"] == "tick")
    assert 0 < tick["counters"]["latent_pages_copied"] \
        < tick["counters"]["latent_pages_rowwise"]


def test_decode_step_donates_and_aliases_the_pools_and_the_counters(engine):
    text = engine.decode_program_text()
    assert engine.pool_copies(text) == 0
    assert pool_copies(engine.lower_chunk().compile().as_text(),
                       engine.k_pages[0].shape) == 0


@pytest.mark.parametrize("what", ["prefill_only", "submit_prefilled",
                                  "tensor_mesh"])
def test_what_is_not_built_for_this_model_says_so(engine, what):
    with pytest.raises(NotImplementedError, match="latent"):
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


def test_gated_held_experts_leave_the_two_matrix_form_alone():
    """`w_gate=None` is static: the relu^2 experts' program is what it was,
    and the gated form is silu(W_g x) * W_u x through W_d."""
    from ray_tpu.models import moe
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    w_in, w_gate = (jnp.asarray(rng.normal(size=(2, 8, 6)), jnp.float32)
                    for _ in range(2))
    w_out = jnp.asarray(rng.normal(size=(2, 6, 8)), jnp.float32)
    chosen = jnp.asarray(rng.integers(0, 4, size=(5, 2)), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(5, 2)), jnp.float32)
    mask = jnp.ones((5,), bool)
    got, pairs = moe.held_expert_sum(x, chosen, weights, mask, w_in, w_out,
                                     1, w_gate)
    want = np.zeros((5, 8), np.float32)
    for t in range(5):
        for j in range(2):
            e = int(chosen[t, j]) - 1
            if 0 <= e < 2:
                h = jax.nn.silu(x[t] @ w_gate[e]) * (x[t] @ w_in[e])
                want[t] += float(weights[t, j]) * np.asarray(h @ w_out[e])
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert int(pairs.sum()) == int(((chosen >= 1) & (chosen < 3)).sum())
    plain = jax.jit(moe.held_expert_sum, static_argnums=(6,)).lower(
        x, chosen, weights, mask, w_in, w_out, 1).as_text()
    assert "logistic" not in plain
