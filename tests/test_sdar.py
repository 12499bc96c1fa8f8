"""SDAR's generation by diffusion over blocks on the CPU, seeded random
weights, a tiny config in the published ratios: the model under the block
mask, the chunks written straight into the row's pages, the block step (L
positions a row, attended both ways, zero to L tokens out, the commit), the
two unmasking rules, and the engine's account of rows that are cancelled,
preempted and resumed inside a block, against the plain float32 reference
(benchmarks/reference/sdar_ref.py)."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import parity_sdar as parity  # noqa: E402
from benchmarks.harness.builders_sdar import (reference_keys,  # noqa: E402
                                              sdar_model)
from benchmarks.reference import sdar_ref  # noqa: E402
from ray_tpu._internal import accel  # noqa: E402
from ray_tpu.llm import GenerationRequest, sampling  # noqa: E402
from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine  # noqa: E402
from ray_tpu.models.sdar import SdarConfig, block_mask  # noqa: E402
from ray_tpu.ops import paged_attention as pa  # noqa: E402

# Published key names at toy widths: 2 : 1 GQA, heads 8 wide, 2 experts a
# token of 8, all held; the mask is the vocabulary's last id.
TINY = {
    "vocab_size": 97, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "attention_bias": False,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "max_position_embeddings": 256, "block_length": 4, "mask_token_id": 96}
L, MASK = 4, 96
KEYS = reference_keys(TINY)

# Everything here is float32 on the CPU, the system's arithmetic and the
# reference's alike; they differ in the order of their sums (a softmax over
# pages against a dense one). The largest logit difference read over the
# cases below is 3e-6 of a logit spread; a bf16 reference reads 2e-2 and a
# causal mask inside the block 0.5. 1e-4 lies between.
TOLERANCE = 1e-4


def tiny_model(**overrides) -> SdarConfig:
    return dataclasses.replace(
        sdar_model(TINY), dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference", **overrides)


def tiny_engine(params=None, pages=64, buckets=(8, 16), batch=4,
                **model_overrides) -> PagedLLMEngine:
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(**model_overrides), max_batch=batch, max_len=128,
        page_size=8, num_pages=pages, prefill_buckets=buckets),
        params=params)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def prompt_of(seed: int, n: int):
    return np.random.default_rng(seed).integers(0, MASK, size=n).tolist()


def run(engine, requests):
    """The requests through submit / step to their ends: {id: tokens}."""
    done = {}
    for request in requests:
        engine.submit(request)
    while engine.has_work():
        for request, tokens in engine.step():
            done[request.request_id] = tokens
    return done


def spied(engine, requests):
    """`run` with the parity check's spy on the engine's two programs:
    ({id: tokens}, the spy)."""
    spy = parity.Spy(engine, parity.Programs(engine),
                     width=engine.config.max_batch)
    try:
        return run(engine, requests), spy
    finally:
        spy.restore()


def held_to_the_reference(engine, spy, request, **control):
    """Every forward of `request` as the spy recorded it, against the
    reference's full forward over the committed tokens and the ENGINE'S OWN
    block ids, and the rule recomputed by the reference's functions from
    the engine's logits. Returns the largest logit distance over a logit
    spread; raises where a rule's outcome differs."""
    prompt = request.prompt_tokens
    done = list(prompt[:len(prompt) - len(prompt) % L])
    steps, threshold = engine._block_settings(request)
    worst = 0.0
    for forward in spy.forwards[request.request_id]:
        ids = forward["ids"]
        assert forward["at"] == len(done)
        want = np.asarray(sdar_ref.logits(
            engine.params, done + ids.tolist(), KEYS,
            rows=list(range(len(done), len(done) + L)), **control))
        worst = max(worst, float(
            (np.abs(forward["logits"] - want).max(-1) / want.std(-1)).max()))
        if (ids == MASK).any():
            found, confidence = sdar_ref.candidates(forward["logits"], MASK)
            ruled = sdar_ref.static_rule(
                ids, found, confidence, MASK, forward["count"]) \
                if threshold > 1 else sdar_ref.dynamic_rule(
                    ids, found, confidence, MASK, forward["count"],
                    threshold)
            assert (ruled == forward["out"]).all(), (ids, ruled, forward)
            assert (forward["timed"] == forward["out"]).all()
        else:
            done += ids.tolist()
    return worst


def test_the_tiny_config_keeps_the_published_layer_and_the_engines_contract(
        engine):
    cfg = engine.config.model
    assert cfg.num_heads // cfg.num_kv_heads == 2 and cfg.block_length == L
    assert engine.kind == "blockwise"
    stats = engine.stats()
    assert stats["layer_kinds"] == ["pc", "pc"]
    assert len(engine.k_pages) == 2 and engine.k_pages[0].shape == (2, 64, 8, 8)
    for key in ("block_forwards", "commit_forwards", "block_tokens_out",
                "blocks_early"):
        assert key in stats


@pytest.mark.parametrize("length", [1, 4, 7, 40])
def test_the_whole_sequence_forward_matches_the_reference(engine, length):
    tokens = prompt_of(length, length)
    got = engine.model.apply({"params": engine.params},
                             jnp.asarray([tokens]))[0]
    want = sdar_ref.logits(engine.params, tokens, KEYS)
    assert np.abs(np.asarray(got) - want).max() / want.std() < TOLERANCE


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("n_prompt", [5, 16, 22, 39])
def test_every_forward_of_a_generation_matches_the_reference(
        engine, steps, n_prompt):
    """Prompts of every `len % 4`, one and two chunks; `max_new_tokens` not
    a multiple of 4: logits forward by forward, the rule exactly, and the
    tokens handed out those of the reference's own generator."""
    request = GenerationRequest(
        prompt_tokens=prompt_of(n_prompt, n_prompt), max_new_tokens=10,
        request_id=f"g{steps}-{n_prompt}", denoising_steps=steps)
    done, spy = spied(engine, [request])
    assert held_to_the_reference(engine, spy, request) < TOLERANCE
    want, forwards = sdar_ref.generate(
        engine.params, request.prompt_tokens, KEYS, 10, steps)
    assert done[request.request_id] == want
    assert len(spy.forwards[request.request_id]) == len(forwards)


def test_rows_of_different_steps_in_one_batch_match_the_reference(engine):
    before = engine.stats()
    requests = [GenerationRequest(
        prompt_tokens=prompt_of(70 + n, size), max_new_tokens=new,
        request_id=f"mixed-{n}", denoising_steps=steps)
        for n, (size, new, steps) in enumerate(
            [(23, 13, 1), (6, 9, 2), (17, 12, 4), (40, 5, 3), (3, 8, 4),
             (12, 16, 1)])]
    done, spy = spied(engine, requests)
    for request in requests:
        assert held_to_the_reference(engine, spy, request) < TOLERANCE
        assert done[request.request_id] == sdar_ref.generate(
            engine.params, request.prompt_tokens, KEYS,
            request.max_new_tokens, request.denoising_steps)[0]
    stats = engine.stats()
    handed = sum(r.max_new_tokens for r in requests)
    assert stats["block_tokens_out"] - before["block_tokens_out"] == handed
    assert stats["tokens_generated"] - before["tokens_generated"] == handed
    assert stats["leaked_pages"] == 0 and not engine._unread
    assert stats["discarded_tokens"] == before["discarded_tokens"]
    # the static rule's yield is known before anything is read: every step
    # but a batch's first was dispatched ahead of the read
    assert stats["drained_by"].get("preempt") is None


@pytest.mark.parametrize("threshold", [0.012, 0.02, 0.5])
def test_the_dynamic_rule_spends_no_forward_twice(engine, threshold):
    """Under a threshold that seeded logits pass now and then, a block ends
    ahead of the static count; the forward dispatched behind it is its
    commit, and the forwards are the reference's, one for one."""
    before = engine.stats()
    request = GenerationRequest(
        prompt_tokens=prompt_of(5, 14), max_new_tokens=24,
        request_id=f"dyn-{threshold}", denoising_steps=4,
        remasking="dynamic", confidence_threshold=threshold)
    done, spy = spied(engine, [request])
    assert held_to_the_reference(engine, spy, request) < TOLERANCE
    want, forwards = sdar_ref.generate(
        engine.params, request.prompt_tokens, KEYS, 24, 4, "dynamic",
        threshold)
    assert done[request.request_id] == want
    stats = engine.stats()
    early = stats["blocks_early"] - before["blocks_early"]
    assert (early > 0) == (threshold < 0.1)
    spent = stats["block_forwards"] - before["block_forwards"]
    # a last block that ends early leaves one forward behind it in flight
    assert len(forwards) <= spent <= len(forwards) + 1
    assert stats["commit_forwards"] - before["commit_forwards"] == \
        sum(f["commit"] for f in forwards)


def test_a_wrong_mask_and_a_lower_precision_fail_the_tolerance(engine):
    request = GenerationRequest(
        prompt_tokens=prompt_of(9, 18), max_new_tokens=8,
        request_id="controls", denoising_steps=2)
    _, spy = spied(engine, [request])
    assert held_to_the_reference(engine, spy, request) < TOLERANCE
    for control in ({"causal_inside": True}, {"dtype": jnp.bfloat16}):
        assert held_to_the_reference(
            engine, spy, request, **control) > 10 * TOLERANCE


def test_a_block_left_uncommitted_fails_the_tolerance(engine):
    """The commit is not a formality: a later block read against the K/V the
    last denoising forward wrote (masks where that forward fixed its
    tokens) stands spreads away."""
    prompt = prompt_of(11, 8)
    request = GenerationRequest(prompt_tokens=prompt, max_new_tokens=12,
                                request_id="uncommitted", denoising_steps=1)
    _, spy = spied(engine, [request])
    later = spy.forwards["uncommitted"][-1]
    assert later["at"] == 16
    rows = list(range(16, 20))
    sound = sdar_ref.logits(engine.params, prompt + spy.forwards[
        "uncommitted"][1]["ids"].tolist() + spy.forwards["uncommitted"][3][
        "ids"].tolist() + later["ids"].tolist(), KEYS, rows=rows)
    without = sdar_ref.logits(engine.params, prompt + [MASK] * 8
                              + later["ids"].tolist(), KEYS, rows=rows)
    spread = sound.std(-1)
    assert (np.abs(later["logits"] - sound).max(-1) / spread).max() \
        < TOLERANCE
    assert (np.abs(later["logits"] - without).max(-1) / spread).max() > 0.1


def test_chunked_and_unchunked_prefill_hand_out_the_same(engine):
    unchunked = tiny_engine(engine.params, buckets=(64,))
    requests = lambda: [GenerationRequest(  # noqa: E731
        prompt_tokens=prompt_of(30 + n, size), max_new_tokens=9,
        request_id=f"c{n}", denoising_steps=2)
        for n, size in enumerate((37, 50, 16))]
    chunked_done, chunked = spied(engine, requests())
    whole_done, whole = spied(unchunked, requests())
    assert chunked_done == whole_done
    assert len(chunked.chunks["c1"]) == 3 and len(whole.chunks["c1"]) == 1
    for rid in chunked_done:
        for a, b in zip(chunked.forwards[rid], whole.forwards[rid]):
            assert np.abs(a["logits"] - b["logits"]).max() < 1e-4


def test_a_shared_prefix_gives_the_logits_a_fresh_one_gives(engine):
    """Whole pages are whole blocks and a block's K/V depend on nothing
    behind it: the radix maps a shared prefix's pages as it does for every
    model."""
    fresh = tiny_engine(engine.params)
    system = prompt_of(91, 24)
    first = GenerationRequest(prompt_tokens=system + prompt_of(92, 7),
                              max_new_tokens=8, request_id="s0")
    second = GenerationRequest(prompt_tokens=system + prompt_of(93, 9),
                               max_new_tokens=8, request_id="s1")
    run(engine, [first])
    before = engine.stats()
    done, spy = spied(engine, [second])
    alone, lone = spied(fresh, [dataclasses.replace(second)])
    stats = engine.stats()
    assert stats["prefix_shared_tokens"] - before["prefix_shared_tokens"] == 24
    assert stats["prefix_hits"] == before["prefix_hits"] + 1
    assert done == alone
    for a, b in zip(spy.forwards["s1"], lone.forwards["s1"]):
        assert np.abs(a["logits"] - b["logits"]).max() < 1e-4
    assert held_to_the_reference(engine, spy, second) < TOLERANCE


def test_a_cancel_inside_a_block_drops_the_block(engine):
    before = engine.stats()
    streamed = []
    request = GenerationRequest(prompt_tokens=prompt_of(41, 10),
                                max_new_tokens=40, request_id="cancelled",
                                denoising_steps=4)
    ended = []
    engine.submit(request, done_callback=lambda r, out: ended.append(out),
                  token_callback=lambda r, t: streamed.append(t))
    while not streamed:
        engine.step()
    engine.step()                    # a forward into the next block
    seq = engine._by_id["cancelled"]
    assert seq.block_at >= 0 and 0 < seq.block_masks < L
    assert engine.cancel("cancelled")
    while engine.has_work():
        engine.step()
    assert ended == [None]
    # tokens left the engine a whole block at a time, the first block's
    # behind the prompt's two fixed ones
    assert len(streamed) % L == 2
    stats = engine.stats()
    assert stats["leaked_pages"] == 0 and stats["active"] == 0
    assert stats["free_pages"] == before["free_pages"] \
        - (stats["prefix_entries"] - before["prefix_entries"])


def test_a_preemption_inside_a_block_resumes_at_the_boundary(engine):
    """The block in flight is dropped, the tokens handed out become the
    prompt's extension, which ends at a block's boundary: its re-prefill
    under the block mask gives the K/V the commits gave."""
    prompt = prompt_of(43, 21)
    want = sdar_ref.generate(engine.params, prompt, KEYS, 18, 2)[0]
    request = GenerationRequest(prompt_tokens=prompt, max_new_tokens=18,
                                request_id="preempted", denoising_steps=2)
    streamed = []
    done = {}
    engine.submit(request, token_callback=lambda r, t: streamed.append(t))
    while len(streamed) < 7:
        engine.step()
    slot = engine.seqs.index(engine._by_id["preempted"])
    before = engine.stats()["preemptions"]
    engine._preempt(slot, reason="test")
    resumed = list(request._resume_tokens)
    assert (len(prompt) + len(resumed)) % L == 0 and len(resumed) >= 7
    while engine.has_work():
        for finished, tokens in engine.step():
            done[finished.request_id] = tokens
    assert engine.stats()["preemptions"] == before + 1
    assert done["preempted"] == want == streamed
    assert engine.stats()["leaked_pages"] == 0


def test_page_pressure_preempts_and_every_row_still_ends_right(engine):
    small = tiny_engine(engine.params, pages=14)
    requests = [GenerationRequest(
        prompt_tokens=prompt_of(50 + n, 20 + n), max_new_tokens=30,
        request_id=f"p{n}", denoising_steps=1 + n % 3) for n in range(4)]
    done = run(small, requests)
    stats = small.stats()
    assert stats["preemptions"] > 0 and stats["leaked_pages"] == 0
    for request in requests:
        assert done[request.request_id] == sdar_ref.generate(
            engine.params, request.prompt_tokens, KEYS, 30,
            request.denoising_steps)[0]


def test_an_eos_inside_a_block_ends_the_row_there(engine):
    prompt = prompt_of(61, 9)
    plain = sdar_ref.generate(engine.params, prompt, KEYS, 12, 2)[0]
    eos = plain[5]
    stopping = PagedLLMEngine(dataclasses.replace(
        engine.config, eos_token=eos), params=engine.params)
    done = run(stopping, [GenerationRequest(
        prompt_tokens=prompt, max_new_tokens=12, request_id="eos",
        denoising_steps=2)])
    assert done["eos"] == plain[:plain.index(eos) + 1]
    assert stopping.stats()["leaked_pages"] == 0


def _made(seed, rows):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, MASK, size=(rows, L))
    ids[rng.random((rows, L)) < 0.6] = MASK
    found = rng.integers(0, MASK, size=(rows, L))
    # distinct confidences: ties excluded
    confidence = rng.permutation(rows * L).reshape(rows, L) / (rows * L)
    return ids, found, confidence.astype(np.float32)


@pytest.mark.parametrize("count", [0, 1, 2, 3, 4])
@pytest.mark.parametrize("threshold", [2.0, 0.9, 0.5, 0.1])
def test_both_rules_are_the_references_on_made_logits(count, threshold):
    """The device's one rule against the reference's two, on made
    candidates and confidences with thresholds on both sides of them."""
    ids, found, confidence = _made(1000 * count + int(100 * threshold), 64)
    out, before, after = sampling.unmask_block(
        jnp.asarray(ids), jnp.asarray(found), jnp.asarray(confidence), MASK,
        jnp.full((64,), count, jnp.int32),
        jnp.full((64,), threshold, jnp.float32))
    for row in range(64):
        want = sdar_ref.static_rule(ids[row], found[row], confidence[row],
                                    MASK, count) if threshold > 1 \
            else sdar_ref.dynamic_rule(ids[row], found[row], confidence[row],
                                       MASK, count, threshold)
        assert (np.asarray(out[row]) == want).all()
        assert int(before[row]) == (ids[row] == MASK).sum()
        assert int(after[row]) == (want == MASK).sum()


@pytest.mark.parametrize("steps", [1, 2, 3, 4, 7])
def test_the_static_counts_are_the_references(steps):
    want = sdar_ref.unmask_counts(L, steps)
    assert sum(want) == L
    assert [sampling.unmask_count(L, steps, t)
            for t in range(len(want))] == want


def test_confidence_is_the_sampled_tokens_probability():
    logits = jnp.asarray(np.random.default_rng(3).normal(size=(6, 33)),
                         jnp.float32)
    zeros = jnp.zeros((6,), jnp.float32)
    tokens, confidence = sampling.sample_with_confidence(
        jax.random.PRNGKey(0), logits, zeros, zeros.astype(jnp.int32),
        zeros + 1.0)
    probs = jax.nn.softmax(logits, -1)
    assert (tokens == logits.argmax(-1)).all()
    assert np.allclose(confidence, probs.max(-1), rtol=1e-5)


def _dense_attention(q, k, v, seen):
    """q [s, heads, hd], k / v [t, kv_heads, hd], seen [s, t]."""
    groups = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, groups, axis=1) for a in (k, v))
    logits = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    probs = jax.nn.softmax(jnp.where(seen[None], logits, -1e30), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v)


def _pooled(rng, tokens, kv_heads=2, hd=8, page=8, pages=24):
    """K and V rows of one row, and pools that hold them on shuffled
    pages."""
    k, v = (jnp.asarray(rng.normal(size=(tokens, kv_heads, hd)), jnp.float32)
            for _ in range(2))
    held = -(-tokens // page)
    table = np.zeros((12,), np.int32)
    table[:held] = rng.permutation(np.arange(1, pages))[:held]
    pools = []
    for rows in (k, v):
        padded = jnp.pad(rows, ((0, held * page - tokens), (0, 0), (0, 0)))
        pool = jnp.zeros((kv_heads, pages, page, hd), jnp.float32)
        pools.append(pool.at[:, table[:held]].set(jnp.transpose(
            padded.reshape(held, page, kv_heads, hd), (2, 0, 1, 3))))
    return k, v, pools, jnp.asarray(table)


@pytest.mark.parametrize("start,chunk", [(0, 16), (16, 8), (24, 16)])
def test_a_chunk_over_pages_attends_under_the_block_mask(start, chunk):
    rng = np.random.default_rng(start + chunk)
    k, v, (kp, vp), table = _pooled(rng, start + chunk)
    q = jnp.asarray(rng.normal(size=(chunk, 4, 8)), jnp.float32)
    got = pa.paged_attend_chunk(q * 8 ** -0.5, kp, vp, table, start,
                                block_length=L)
    positions = jnp.arange(start + chunk)
    want = _dense_attention(q, k, v, block_mask(positions, L)[start:])
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    causal = pa.paged_attend_chunk(q * 8 ** -0.5, kp, vp, table, start)
    assert np.abs(np.asarray(causal) - want).max() > 1e-2


@pytest.mark.parametrize("committed", [0, 8, 20])
@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_a_block_of_queries_over_pages_attends_both_ways(committed, kernel):
    """`paged_attend_block`: the block's L queries as L more members of
    each kv head's group, through the gather path and through the kernel
    itself under the TPU interpreter."""
    rng = np.random.default_rng(committed)
    rows = []
    for r in range(3):
        k, v, pools, table = _pooled(rng, committed + L, hd=128, page=8)
        q = jnp.asarray(rng.normal(size=(L, 4, 128)), jnp.float32)
        rows.append((q, k, v, pools, table))
    # one pool for the three rows: their pages side by side
    kp = jnp.concatenate([r[3][0] for r in rows], axis=1)
    vp = jnp.concatenate([r[3][1] for r in rows], axis=1)
    tables = jnp.stack([jnp.where(r[4] > 0, r[4] + 24 * n, 0)
                        for n, r in enumerate(rows)])
    lengths = jnp.full((3,), committed, jnp.int32)
    q = jnp.stack([r[0] for r in rows])
    if kernel == "gather":
        got = pa.paged_attend_block(q, kp, vp, lengths, tables,
                                    reference=True)
    else:
        folded = jnp.transpose(q.reshape(3, L, 2, 2, 128),
                               (0, 2, 1, 3, 4)).reshape(3, -1, 128)
        out = pa._paged_attend_pallas(
            folded * 128 ** -0.5, kp, vp, lengths + L, tables, block_pages=4)
        got = jnp.transpose(out.reshape(3, 2, L, 2, 128),
                            (0, 2, 1, 3, 4)).reshape(3, L, 4, 128)
    for n, (qr, k, v, _, _) in enumerate(rows):
        want = _dense_attention(qr, k, v, jnp.ones((L, committed + L), bool))
        assert np.abs(np.asarray(got[n]) - want).max() < 2e-5


def test_block_rows_land_in_the_rows_page():
    pool = jnp.zeros((2, 6, 8, 4), jnp.float32)
    rows = jnp.arange(2 * 3 * L * 4, dtype=jnp.float32).reshape(2, 3, L, 4)
    tables = jnp.asarray([[1, 2], [3, 0], [4, 5]])
    lengths = jnp.asarray([4, 0, 12])
    out = pa.write_block_rows(pool, rows, tables, lengths)
    assert (out[:, 1, 4:8] == rows[:, 0]).all()
    assert (out[:, 3, 0:4] == rows[:, 1]).all()
    assert (out[:, 5, 4:8] == rows[:, 2]).all()
    assert float(jnp.abs(out).sum()) == float(jnp.abs(rows).sum())


def test_the_block_step_donates_and_aliases_pools_and_counters(engine):
    """On the CPU; the chip's compiler is asked the same of both programs at
    the published widths in tests/test_aot_tpu_compile.py."""
    text = engine.decode_program_text()
    assert engine.pool_copies(text) == 0
    assert "jit_decode_step" in text
    assert "input_output_alias" in engine.lower_chunk().compile().as_text()


def test_the_step_row_counts_positions_and_the_parameters_they_touch(engine):
    request = GenerationRequest(prompt_tokens=prompt_of(77, 8),
                                max_new_tokens=8, request_id="timed",
                                denoising_steps=4)
    def tokens():
        engine.stats()                  # flushes the partial window
        return sum(row["tokens"] for row in accel.step_summary()
                   if row["kind"] == "decode")
    before, forwards = tokens(), engine.stats()["block_forwards"]
    run(engine, [request])
    forwards = engine.stats()["block_forwards"] - forwards
    assert tokens() - before == L * forwards
    assert engine._active_params < engine._num_params
    routed = 3 * 8 * 32 * 16 * 2           # both layers' expert matrices
    gains = 2 * (2 * 32 + 2 * 8) + 32      # the norms': nothing multiplies
    assert engine._active_params == engine.config.model.active_params() \
        == engine._num_params - 97 * 32 - gains - routed + routed // 4


def test_the_chunks_of_the_largest_bucket_count_what_they_routed(engine):
    """The chunks' own expert counters (what `sdar_chunk_roofline_pct`
    reads): a chunk of the largest bucket adds its real rows' pairs, a
    smaller one none."""
    def counted():
        stats = engine.stats()
        if stats["chunk_expert_pairs"] and engine._counters_asked:
            engine.read_counters()
            stats = engine.stats()
        return (np.asarray(stats["chunk_expert_pairs"]),
                np.asarray(stats["chunk_expert_steps"]),
                stats["prefill_chunks_largest"])
    pairs, steps, chunks = counted()
    # 16 + 16 in the largest bucket, then 5 whole blocks' worth (4) in the
    # smaller one; the tail of 2 opens the first block
    run(engine, [GenerationRequest(prompt_tokens=prompt_of(555, 38),
                                   max_new_tokens=4, request_id="counted")])
    after = counted()
    assert after[2] - chunks == 2
    gained = after[0] - pairs
    assert gained.shape == (2, 8) and (gained.sum(-1) == 32 * 2).all()
    hit = after[1] - steps
    assert (hit <= 2).all() and (hit.sum(-1) >= 2 * 2).all()
    assert ((gained > 0) == (hit > 0)).all()


@pytest.mark.parametrize("what", ["tensor_mesh", "prefill_only",
                                  "submit_prefilled", "rule", "page_size"])
def test_what_is_not_built_for_this_model_says_so(engine, what):
    if what == "tensor_mesh":
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2, 1, 1, 1),
                    ("data", "fsdp", "tensor", "sequence", "pipeline",
                     "expert"))
        with pytest.raises(NotImplementedError, match="tensor mesh"):
            PagedLLMEngine(engine.config, mesh=mesh)
    elif what == "prefill_only":
        with pytest.raises(NotImplementedError, match="diffusion"):
            engine.prefill_only([1, 2, 3, 4, 5])
    elif what == "submit_prefilled":
        with pytest.raises(NotImplementedError, match="diffusion"):
            engine.submit_prefilled(GenerationRequest(
                prompt_tokens=[1, 2, 3]), [], np.zeros((97,)))
    elif what == "rule":
        with pytest.raises(ValueError, match="remasking"):
            engine.submit(GenerationRequest(prompt_tokens=[1, 2, 3],
                                            remasking="random"))
    else:
        with pytest.raises(ValueError, match="whole blocks"):
            PagedLLMEngine(dataclasses.replace(engine.config, page_size=6))


def test_the_server_takes_the_block_settings_of_a_request(engine):
    import asyncio

    from ray_tpu.llm.serving import LLMServer
    server = LLMServer(engine.config, params=engine.params)
    prompt = prompt_of(81, 11)

    async def both():
        plain = await server.generate(prompt, max_new_tokens=8,
                                      denoising_steps=1)
        sid = await server.generate_stream_start(
            prompt, max_new_tokens=8, denoising_steps=4,
            remasking="dynamic", confidence_threshold=0.015)
        streamed, batches = [], []
        while True:
            got = await server.stream_next(sid, timeout_s=5.0)
            streamed += got["tokens"]
            if got["tokens"]:
                batches.append(len(got["tokens"]))
            if got["done"]:
                return plain, streamed, batches

    plain, streamed, _ = asyncio.run(both())
    assert plain["tokens"] == sdar_ref.generate(
        engine.params, prompt, KEYS, 8, 1)[0]
    assert streamed == sdar_ref.generate(
        engine.params, prompt, KEYS, 8, 4, "dynamic", 0.015)[0]
    assert server.engine_stats()["block_tokens_out"] == 16
