"""Temperature / top-k / nucleus sampling (reference role: vLLM's
Sampler — SamplingParams temperature/top_k/top_p applied per sequence;
here one vectorized jitted program, llm/sampling.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm.sampling import NEG_INF, sample_tokens, sampler_tier


def _sample_many(logits_row, temperature, top_k, top_p, n=400):
    logits = jnp.asarray(np.tile(logits_row, (n, 1)), jnp.float32)
    B = logits.shape[0]
    out = sample_tokens(
        jax.random.PRNGKey(0), logits,
        jnp.full((B,), temperature, jnp.float32),
        jnp.full((B,), top_k, jnp.int32),
        jnp.full((B,), top_p, jnp.float32))
    return np.asarray(out)


def test_greedy_and_top_k_one():
    row = np.asarray([1.0, 3.0, 2.0, -1.0])
    # temperature 0 = greedy regardless of filters
    assert set(_sample_many(row, 0.0, 0, 1.0)) == {1}
    # top_k=1 at ANY temperature is greedy
    assert set(_sample_many(row, 5.0, 1, 1.0)) == {1}


def test_top_k_restricts_support():
    row = np.asarray([1.0, 3.0, 2.0, 0.5, -1.0])
    drawn = set(_sample_many(row, 2.0, 2, 1.0))
    assert drawn <= {1, 2} and len(drawn) == 2  # both top-2 appear


def test_top_p_nucleus():
    # probs ~ [0.64, 0.23, 0.086, ...]: p=0.5 keeps only the top token
    # (it crosses the 0.5 mass alone); p=0.8 keeps the top two.
    row = np.asarray([4.0, 3.0, 2.0, 1.0, 0.0])
    assert set(_sample_many(row, 1.0, 0, 0.5)) == {0}
    drawn = set(_sample_many(row, 1.0, 0, 0.8))
    assert drawn <= {0, 1} and len(drawn) == 2
    # p>=1 disables the filter: the tail can appear at high temperature
    drawn_all = set(_sample_many(row, 50.0, 0, 1.0))
    assert len(drawn_all) >= 4


def test_per_row_params_are_independent():
    row = np.asarray([1.0, 3.0, 2.0, -1.0])
    logits = jnp.asarray(np.tile(row, (3, 1)), jnp.float32)
    out = np.asarray(sample_tokens(
        jax.random.PRNGKey(1), logits,
        jnp.asarray([0.0, 8.0, 8.0], jnp.float32),   # greedy | hot | hot
        jnp.asarray([0, 1, 0], jnp.int32),           # - | k=1 | off
        jnp.asarray([1.0, 1.0, 1.0], jnp.float32)))
    assert out[0] == 1 and out[1] == 1  # greedy rows pinned


def _tiny_dense_engine():
    import dataclasses

    from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine
    from ray_tpu.models import LlamaConfig

    return PagedLLMEngine(PagedEngineConfig(
        model=dataclasses.replace(LlamaConfig.tiny_test(),
                                  dtype=jnp.float32),
        max_batch=2, max_len=64, page_size=8, num_pages=64))


@pytest.mark.timeout_s(300)
def test_paged_engine_top_k_one_matches_greedy():
    """End-to-end: the paged engine with temperature>0 but top_k=1 must
    reproduce the greedy generation exactly."""
    from ray_tpu.llm import GenerationRequest

    engine = _tiny_dense_engine()
    prompt = [3, 14, 15, 9, 2, 6]
    done = {}

    def on_done(request, tokens):
        done[request.request_id] = tokens

    engine.submit(GenerationRequest(prompt_tokens=prompt,
                                    max_new_tokens=12,
                                    request_id="greedy"),
                  done_callback=on_done)
    engine.submit(GenerationRequest(prompt_tokens=prompt,
                                    max_new_tokens=12,
                                    temperature=3.0, top_k=1,
                                    request_id="hot-k1"),
                  done_callback=on_done)
    for _ in range(60):
        if not engine.has_work():
            break
        engine.step()
    assert set(done) == {"greedy", "hot-k1"}
    assert list(done["greedy"]) == list(done["hot-k1"])


def test_top_p_zero_keeps_top_token():
    """top_p<=0 must behave like top-1, never crash or go uniform."""
    row = np.asarray([1.0, 3.0, 2.0, -1.0])
    assert set(_sample_many(row, 2.0, 0, 0.0)) == {1}


def reference_sample_tokens(rng, logits, temperature, top_k, top_p):
    """`sample_tokens` as it stood before it branched (PR 43): the whole
    contract, whatever the batch asks for. The tiered function must return
    these tokens bit for bit under the same key."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    V = scaled.shape[-1]
    k = jnp.clip(top_k.astype(jnp.int32), 0, V)
    k_idx = jnp.maximum(k - 1, 0)
    k_thresh = jnp.take_along_axis(sorted_desc, k_idx[:, None],
                                   axis=-1)[:, 0]
    k_thresh = jnp.where(k > 0, k_thresh, NEG_INF)
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cum_before = jnp.cumsum(probs_sorted, axis=-1) - probs_sorted
    in_nucleus = cum_before < jnp.clip(top_p, 1e-6, 1.0)[:, None]
    p_thresh = jnp.min(jnp.where(in_nucleus, sorted_desc, jnp.inf),
                       axis=-1)
    p_thresh = jnp.where(top_p >= 1.0, NEG_INF, p_thresh)
    thresh = jnp.maximum(k_thresh, p_thresh)
    masked = jnp.where(scaled >= thresh[:, None], scaled, NEG_INF)
    sampled = jax.random.categorical(rng, masked)
    return jnp.where(temperature > 0, sampled, greedy)


# (temperature, top_k, top_p) of six rows, and the tier they ask for. A
# greedy row's filters are set where a dead row's never are: they must not
# count (`sampler_tier` asks only SAMPLING rows for their filters).
TIER_CASES = {
    "all greedy": (0, [(0.0, 0, 1.0), (0.0, 5, 0.5), (0.0, 0, 1.0),
                       (0.0, 0, 0.9), (0.0, 1, 1.0), (0.0, 0, 1.0)]),
    "greedy + temperature-only rows": (
        1, [(0.0, 0, 1.0), (0.7, 0, 1.0), (0.0, 3, 0.2),
            (1.5, 0, 1.0), (0.0, 0, 1.0), (4.0, 0, 1.0)]),
    "greedy + top-k + top-p rows": (
        2, [(0.0, 0, 1.0), (0.7, 8, 1.0), (0.0, 0, 1.0),
            (1.5, 0, 0.9), (1.0, 0, 1.0), (2.0, 20, 0.6)]),
    "every row filtered": (
        2, [(0.7, 8, 0.9), (1.0, 3, 1.0), (2.0, 0, 0.5),
            (1.5, 40, 0.95), (0.3, 2, 0.8), (5.0, 0, 0.99)]),
    "top_k = 1 at a temperature": (
        2, [(3.0, 1, 1.0), (0.0, 0, 1.0), (8.0, 1, 1.0),
            (1.0, 1, 0.5), (0.0, 0, 1.0), (2.0, 1, 1.0)]),
}


@pytest.mark.parametrize("case", sorted(TIER_CASES))
def test_tiers_return_the_reference_tokens_bit_for_bit(case):
    tier, rows = TIER_CASES[case]
    temperature, top_k, top_p = (
        jnp.asarray(column, dtype) for column, dtype in
        zip(zip(*rows), (jnp.float32, jnp.int32, jnp.float32)))
    assert int(sampler_tier(temperature, top_k, top_p)) == tier
    # the host counts with the same predicate on numpy arrays
    assert int(sampler_tier(*map(np.asarray,
                                 (temperature, top_k, top_p)))) == tier
    tiered, reference = jax.jit(sample_tokens), \
        jax.jit(reference_sample_tokens)
    for seed in range(8):
        logits = 3.0 * jax.random.normal(
            jax.random.PRNGKey(100 + seed), (len(rows), 257), jnp.float32)
        key = jax.random.PRNGKey(seed)
        got = tiered(key, logits, temperature, top_k, top_p)
        want = reference(key, logits, temperature, top_k, top_p)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _primitives(jaxpr):
    """Every equation's primitive, through nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_no_sort_cumsum_or_random_bits_outside_the_switch():
    """The expensive work sits INSIDE branches of one switch, and only in
    the branches that need it: the greedy branch is an argmax, the plain
    one draws and sorts nothing."""
    B, V = 4, 64
    jaxpr = jax.make_jaxpr(sample_tokens)(
        jax.random.PRNGKey(0), jnp.zeros((B, V)), jnp.zeros((B,)),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,))).jaxpr
    switches = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(switches) == 1
    costly = {"sort", "cumsum", "random_bits"}
    outside = set(_primitives(jaxpr.replace(
        eqns=[e for e in jaxpr.eqns if e.primitive.name != "cond"])))
    assert not costly & outside, outside
    greedy, plain, filtered = (
        set(_primitives(branch.jaxpr))
        for branch in switches[0].params["branches"])
    assert greedy == {"argmax"}
    assert costly & plain == {"random_bits"}
    assert costly <= filtered


def _tiny_hybrid_engine():
    from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine
    from ray_tpu.models.falcon_h1 import FalconH1Config

    return PagedLLMEngine(PagedEngineConfig(
        model=FalconH1Config(
            vocab_size=384, hidden_size=96, intermediate_size=160,
            num_layers=2, num_heads=10, num_kv_heads=2, head_dim=16,
            max_seq_len=512, mamba_d_ssm=128, mamba_n_heads=8,
            mamba_d_state=24, mamba_chunk_size=16, dtype=jnp.float32,
            param_dtype=jnp.float32, attention_impl="reference"),
        max_batch=3, max_len=160, page_size=8, num_pages=96,
        prefill_buckets=(16, 32)))


@pytest.mark.timeout_s(300)
@pytest.mark.parametrize("make_engine", [_tiny_dense_engine,
                                         _tiny_hybrid_engine],
                         ids=["dense", "hybrid"])
def test_engine_counts_the_tier_of_every_dispatched_step(make_engine):
    """`stats()["sampler"]`: every decode step dispatched is counted under
    the branch the device takes in it: greedy under greedy requests,
    filtered once a `top_p = 0.9` request is live; and the `tick` row's
    counters say the same."""
    from ray_tpu._internal import accel
    from ray_tpu.llm import GenerationRequest

    engine = make_engine()
    prompt = [3, 14, 15, 9, 2, 6]

    def run(**sampling):
        before = engine.stats()
        engine.submit(GenerationRequest(prompt_tokens=prompt,
                                        max_new_tokens=10, **sampling))
        for _ in range(60):
            if not engine.has_work():
                break
            engine.step()
        assert not engine.has_work()
        after = engine.stats()
        steps = {tier: after["sampler"][tier] - before["sampler"][tier]
                 for tier in after["sampler"]}
        # a dispatched step decodes one row here, and each is counted once
        assert sum(steps.values()) \
            == after["decode_rows"] - before["decode_rows"] > 0
        for tier, count in after["sampler"].items():
            assert after[f"sampler_{tier}_steps"] == count
        return steps

    greedy = run()
    assert greedy["plain"] == greedy["filtered"] == 0 < greedy["greedy"]
    filtered = run(temperature=0.8, top_p=0.9)
    assert filtered["greedy"] == filtered["plain"] == 0 \
        < filtered["filtered"]
    plain = run(temperature=0.8)
    assert plain["greedy"] == plain["filtered"] == 0 < plain["plain"]
    tick = next(row for row in accel.step_summary() if row["kind"] == "tick")
    assert tick["counters"]["sampler_filtered_steps"] \
        >= filtered["filtered"]
