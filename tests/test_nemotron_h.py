"""Nemotron-H with latent experts (layers that are each a Mamba-2 mixer, an
attention or a mixture of experts) on the CPU, seeded random weights, a
tiny config in the published ratios: the model, the dropless expert layer
and the paged engine's per-kind caches against the plain float32 reference
(benchmarks/reference/nemotron_h_ref.py)."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import parity_nemotron_h as parity  # noqa: E402
from benchmarks.harness.builders_nemotron_h import (  # noqa: E402
    nemotron_h_model)
from benchmarks.harness.parity_falcon_h1 import state_errors  # noqa: E402
from benchmarks.reference import nemotron_h_ref  # noqa: E402
from plain_greedy import plain_greedy, rowwise  # noqa: E402
from ray_tpu.llm import GenerationRequest  # noqa: E402
from ray_tpu.llm.paged import (PagedEngineConfig, PagedLLMEngine,  # noqa: E402
                               pool_copies)
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.nemotron_h import NemotronHConfig  # noqa: E402

# Published key names at toy widths, in the published ratios: 16:1 grouping,
# 8 norm groups, latent (32) < hidden (64), 3 experts a token of 16, a
# quarter of them held, a pattern with all three kinds (two M, two E).
TINY = {
    "vocab_size": 256, "hidden_size": 64, "expand": 2,
    "hybrid_override_pattern": "ME*EM", "num_hidden_layers": 5,
    "num_attention_heads": 16, "num_key_value_heads": 1, "head_dim": 8,
    "layer_norm_epsilon": 1e-5, "mamba_num_heads": 16, "mamba_head_dim": 8,
    "n_groups": 8, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "n_routed_experts": 4, "held_experts": [4, 4],
    "published": {"n_routed_experts": 16}, "num_experts_per_tok": 3,
    "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "routed_scaling_factor": 5,
    "max_position_embeddings": 512, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001}

# Everything here is float32 on the CPU, the system's arithmetic and the
# reference's alike; they differ in the order of their sums (a chunked scan
# against a token-by-token one, a paged softmax against a dense one, every
# held expert on every token against each token's chosen experts). The largest logit difference read over
# three seeds is 1.9e-6 at a logit spread of 1.0; with the recurrent state
# kept in bf16 it is 1.5e-3, with a softmax router 0.5.
TOLERANCE = 2e-5


def tiny_model(**overrides) -> NemotronHConfig:
    return dataclasses.replace(
        nemotron_h_model(TINY), dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference", **overrides)


def tiny_engine(params=None, **model_overrides) -> PagedLLMEngine:
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(**model_overrides), max_batch=3, max_len=160,
        page_size=8, num_pages=96, prefill_buckets=(16, 32)), params=params)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def keys_of(model_cfg, **overrides):
    return dict(parity.reference_keys(model_cfg), **overrides)


def prompt_of(seed: int, n: int):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], n)


def with_bias(params, seed=5, size=0.3):
    """A copy of `params` whose e_score_correction_bias is not zero."""
    params = jax.tree_util.tree_map(lambda a: a, params)
    for i, kind in enumerate(TINY["hybrid_override_pattern"]):
        if kind == "E":
            experts = dict(params[f"layer_{i}"]["moe"]["routed"])
            experts["e_score_correction_bias"] = size * jax.random.normal(
                jax.random.PRNGKey(seed + i), (16,), jnp.float32)
            layer = dict(params[f"layer_{i}"])
            layer["moe"] = dict(layer["moe"], routed=experts)
            params = dict(params, **{f"layer_{i}": layer})
    return params


def test_tiny_config_keeps_the_published_ratios():
    cfg = tiny_model()
    assert cfg.num_heads // cfg.num_kv_heads == 16
    assert cfg.n_groups == 8
    assert cfg.moe_latent_size < cfg.hidden_size
    assert cfg.num_experts_per_tok > 1
    assert set(cfg.layer_kinds()) == {"mamba", "attention", "moe"}
    assert cfg.held_experts[1] * 4 == cfg.n_routed_experts
    assert cfg.moe_shared_expert_intermediate_size \
        == 2 * cfg.moe_intermediate_size


@pytest.mark.parametrize("length", [1, 15, 16, 37, 96])
def test_forward_matches_the_reference(engine, length):
    tokens = prompt_of(length, length)
    got = engine.model.apply({"params": engine.params},
                             jnp.asarray(tokens)[None])[0]
    want = nemotron_h_ref.logits(engine.params, tokens,
                                 keys_of(engine.config.model))
    assert float(jnp.abs(got - want).max()) < TOLERANCE


def test_forward_with_a_correction_bias_matches_the_reference(engine):
    """The bias chooses and does not weigh: non-zero here, zero in the
    cell (the published initialiser)."""
    params = with_bias(engine.params)
    tokens = prompt_of(3, 40)
    keys = keys_of(engine.config.model)
    got = engine.model.apply({"params": params}, jnp.asarray(tokens)[None])[0]
    want = nemotron_h_ref.logits(params, tokens, keys)
    assert float(jnp.abs(got - want).max()) < TOLERANCE
    unbiased = nemotron_h_ref.logits(engine.params, tokens, keys)
    assert float(jnp.abs(unbiased - want).max()) > 100 * TOLERANCE


def _through_the_pools(engine, seed=0, n_prompt=44, ticks=24):
    prompt = prompt_of(seed, n_prompt)
    prefill, decode, fed, held, routes, counted = parity.engine_logits(
        engine, prompt, chunk=32, ticks=ticks)
    sequence = np.concatenate([prompt, np.asarray(fed)])
    want, details = nemotron_h_ref.logits(
        engine.params, sequence, keys_of(engine.config.model),
        routes=routes, details=True)
    got = np.concatenate([prefill, decode])
    details["counted"] = counted
    return got, np.asarray(want), held, details, routes


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_decode_through_the_pools_matches_the_reference(
        engine, seed):
    """44 tokens in a 32-token chunk and a tail of 12 in the engine's
    16-token bucket (its 4 padded positions enter neither a state nor an
    expert's count), then 24 decode ticks through the page and state
    pools; the E layers' accumulators gain exactly what the ticks'
    routes put on the held experts."""
    got, want, held, details, routes = _through_the_pools(engine, seed)
    decoded = [r[44:] for r in routes]
    held_experts = engine.config.model.held_experts
    assert parity.counters_match(details["counted"], decoded, held_experts)
    assert not parity.counters_match(
        details["counted"], [r[1:] for r in decoded], held_experts)
    assert np.abs(got - want).max() < TOLERANCE
    errors = state_errors(held, details["states"])
    assert max(errors["worst_head"] + errors["window"]) < 1e-5
    assert len(held) == 2 and len(routes) == 2
    check = parity.routing_check(routes, details["selection"], 3)
    assert check["routing_agree"] == 1.0 and check["worst_tie"] == 0.0


def test_bf16_state_fails_the_float32_tolerance(engine):
    control = tiny_engine(engine.params, state_dtype=jnp.bfloat16)
    got, want, held, details, _ = _through_the_pools(control)
    assert np.abs(got - want).max() > 10 * TOLERANCE
    errors = state_errors(held, details["states"])
    assert errors["worst_head"][0] > 1e-3


def test_a_softmax_router_fails_the_tolerance(engine, monkeypatch):
    """Softmax for sigmoid keeps the order, so the first E layer chooses
    the same experts and its routing check passes; the weights differ,
    and the logits refuse it."""
    def softmax_top_k(u, router, bias, k, scale):
        scores = jax.nn.softmax(jnp.dot(u, router), axis=-1)
        _, chosen = jax.lax.top_k(scores + bias, k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        # not renormalised
        return chosen.astype(jnp.int32), scale * picked, scores
    monkeypatch.setattr(moe, "sigmoid_top_k", softmax_top_k)
    control = tiny_engine(engine.params)
    got, want, _, details, routes = _through_the_pools(control)
    assert np.abs(got - want).max() > 1000 * TOLERANCE
    assert parity.routing_check(routes[:1], details["selection"][:1],
                                3)["routing_agree"] == 1.0


def test_a_router_that_ignores_the_bias_fails_the_routing_check(engine):
    """Experts taken far from the reference's cut are no near tie."""
    params = with_bias(engine.params)
    tokens = prompt_of(9, 48)
    _, sown = engine.model.apply({"params": engine.params},
                                 jnp.asarray(tokens)[None],
                                 mutable=["routing"])
    routes = [r[0] for r in parity._routes_of(
        sown, engine.config.model.layer_kinds())]
    _, details = nemotron_h_ref.logits(
        params, tokens, keys_of(engine.config.model), routes=routes,
        details=True)
    check = parity.routing_check(routes, details["selection"], 3)
    assert check["routing_agree"] < 0.9
    assert check["worst_tie"] > 10 * parity.ROUTE_TIE


def test_the_shares_add_up_to_the_uncut_layer(engine):
    """The routed parts that the four chips of the deployment give, with
    the shared expert counted once, are the uncut reference layer."""
    cfg = engine.config.model
    whole = dataclasses.replace(cfg, held_experts=(0, 16))
    full = whole.module().init(jax.random.PRNGKey(3),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    from ray_tpu.parallel.mesh import unbox
    full = unbox(full)
    layer = full["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (29, cfg.hidden_size))
    sh = nemotron_h_ref.shape_of(keys_of(whole))
    want, _ = nemotron_h_ref.moe_layer(x, layer, sh)

    def share(first):
        """Chip `first // 4`'s E layer on x: its four experts' weights."""
        part = dataclasses.replace(cfg, held_experts=(first, 4))
        experts = dict(layer["moe"]["routed"])
        experts["w_in"] = experts["w_in"][first:first + 4]
        experts["w_out"] = experts["w_out"][first:first + 4]
        p = dict(layer, moe=dict(layer["moe"], routed=experts))
        sh_part = nemotron_h_ref.shape_of(keys_of(part))
        from ray_tpu.models.nemotron_h import Block
        got = Block(part, "moe").apply({"params": p}, x[None], None)[0][0]
        ref_part, _ = nemotron_h_ref.moe_layer(x, p, sh_part)
        assert float(jnp.abs(got - ref_part).max()) < TOLERANCE
        return got - x

    shares = [share(first) for first in (0, 4, 8, 12)]
    # x + shared is in every share; the routed parts differ
    u = nemotron_h_ref._norm(x, layer["norm"]["scale"], sh.eps)
    with jax.default_matmul_precision("highest"):
        shared = nemotron_h_ref._relu2(
            u @ layer["moe"]["shared_up"]["kernel"]) \
            @ layer["moe"]["shared_down"]["kernel"]
    total = x + shared + sum(s - shared for s in shares)
    assert float(jnp.abs(total - want).max()) < TOLERANCE


@pytest.mark.parametrize("tokens", [7, 64, 300])
def test_no_pair_is_dropped_when_every_token_chooses_the_same_experts(
        tokens):
    """Total imbalance: every token's k choices are the same k experts,
    all held. A capacity of 1.25 x the mean would drop most of them."""
    k, held, width, mlp = 3, 4, 32, 48
    rng = np.random.default_rng(tokens)
    x = jnp.asarray(rng.standard_normal((tokens, width)), jnp.float32)
    chosen = jnp.broadcast_to(jnp.asarray([5, 4, 6], jnp.int32), (tokens, k))
    weights = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((held, width, mlp)), jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((held, mlp, width)), jnp.float32)
    mask = jnp.ones((tokens,), bool)
    out, pairs = moe.held_expert_sum(x, chosen, weights, mask, w_in, w_out,
                                     first=4)
    assert pairs.tolist() == [tokens, tokens, tokens, 0]
    want = sum(weights[:, j:j + 1] * (jnp.square(jax.nn.relu(
        x @ w_in[e])) @ w_out[e]) for j, e in enumerate((1, 0, 2)))
    assert float(jnp.abs(out - want).max()) < 1e-3 * float(
        jnp.abs(want).max())


def test_a_masked_token_reaches_no_expert():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((6, 32)), jnp.float32)
    chosen = jnp.asarray(rng.integers(0, 4, (6, 1)), jnp.int32)
    weights = jnp.ones((6, 1), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((4, 32, 48)), jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((4, 48, 32)), jnp.float32)
    mask = jnp.asarray([True, False, True, True, False, True])
    out, pairs = moe.held_expert_sum(x, chosen, weights, mask, w_in, w_out, 0)
    assert int(pairs.sum()) == 4
    assert float(jnp.abs(out[1]).max()) == 0.0 == float(jnp.abs(out[4]).max())


def test_the_engine_keeps_a_pool_per_layer_of_its_kind(engine):
    """ME*EM: one page pool (the * layer's), two state pools (the M
    layers'), two counter pairs (the E layers')."""
    cfg = engine.config
    assert len(engine.k_pages) == len(engine.v_pages) == 1
    assert len(engine.state) == 2
    assert len(engine.counters) == 2
    staged = jax.eval_shape(engine._dense_zero_caches)
    assert len(staged["kv"]) == 1 and len(staged["state"]) == 2
    stats = engine.stats()
    assert stats["layer_kinds"] == ["s", "c", "p", "c", "s"]
    assert stats["hbm_cache_bytes"] == 2 * np.prod(engine.k_pages[0].shape) \
        * engine.k_pages[0].dtype.itemsize
    assert stats["state_bytes"] == sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(engine.state))
    assert engine.k_pages[0].shape == (1, cfg.num_pages, cfg.page_size, 8)


def _reference_greedy(params, model_cfg, prompts, max_new):
    keys = keys_of(model_cfg)
    return plain_greedy(
        rowwise(lambda row: nemotron_h_ref.logits(params, row, keys)),
        prompts, max_new)


def test_generation_through_the_tick_matches_the_reference_and_counts(
        engine):
    """Five requests on three rows through submit/step (lookahead,
    admission mid-decode, rows idle at the end): each request's tokens
    are the reference's greedy continuation, and the expert counters hold
    exactly the decode steps' pairs."""
    # earlier tests drove this engine's counters outside its tick
    engine.read_counters()
    before = engine.stats()
    prompts = [prompt_of(20 + i, n).tolist()
               for i, n in enumerate((9, 33, 17, 40, 5))]
    assert engine.generate(prompts, max_new_tokens=6) == _reference_greedy(
        engine.params, engine.config.model, prompts, 6)
    after = engine.stats()
    assert after["leaked_pages"] == 0
    assert after["state_installs"] - before["state_installs"] == 5
    pairs = np.asarray(after["expert_pairs"]) \
        - np.asarray(before["expert_pairs"])
    steps = np.asarray(after["expert_steps"]) \
        - np.asarray(before["expert_steps"])
    assert pairs.shape == steps.shape == (2, 4)
    # 5 requests x 5 decode tokens each (the first comes from the prefill)
    # x 3 choices, a quarter of which land on held experts on average
    decoded = 5 * 5
    assert 0 < pairs.sum(1).max() <= decoded * 3
    assert (steps <= pairs).all()
    assert steps.max() <= after["lookahead_ticks"] + sum(
        after["drained_by"].values())


def test_stats_reads_the_published_copy_not_the_donated_arrays():
    """The counters are donated to every decode step: a stats() call on
    another thread, mid-dispatch, would find them deleted. It reads the
    host copy the stepping thread published."""
    engine = tiny_engine()
    engine.generate([prompt_of(60, 9).tolist()], max_new_tokens=4)
    want = engine.stats()
    assert np.sum(want["expert_pairs"]) > 0
    for array in jax.tree_util.tree_leaves(engine.counters):
        array.delete()
    got = engine.stats()
    assert got["expert_pairs"] == want["expert_pairs"]
    assert got["expert_steps"] == want["expert_steps"]


def test_a_busy_engine_publishes_newer_counters_once_stats_asked():
    """No tick fetches the counters unasked; a stats() call that finds
    its copy older than the last step gets that copy and asks, and the
    stepping thread publishes before its next step; a drained engine has
    published already."""
    engine = tiny_engine()
    for i in range(3):
        engine.submit(GenerationRequest(
            prompt_tokens=prompt_of(70 + i, 9).tolist(), max_new_tokens=12,
            request_id=f"busy-{i}"))
    for _ in range(6):
        engine.step()
    assert engine.has_work()
    assert np.sum(engine.stats()["expert_pairs"]) == 0
    assert engine._counters_asked
    engine.step()
    assert not engine._counters_asked
    seen = np.sum(engine.stats()["expert_pairs"])
    assert seen > 0
    while engine.has_work():
        engine.step()
    assert np.sum(engine.stats()["expert_pairs"]) > seen
    assert not engine._counters_asked


def test_stats_of_an_engine_with_no_attending_layer():
    """A pattern with no * layer keeps no page pool."""
    engine = tiny_engine(hybrid_override_pattern="MEM")
    stats = engine.stats()
    assert stats["hbm_cache_bytes"] == 0
    assert stats["layer_kinds"] == ["s", "c", "s"]


def test_decode_step_donates_and_aliases_pools_and_counters(engine):
    compiled = engine.lower_decode().compile()
    text = compiled.as_text()
    assert engine.pool_copies(text) == 0
    assert engine.state_copies(text) == 0
    donated = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(
            (engine.k_pages, engine.v_pages, engine.state, engine.counters)))
    assert compiled.memory_analysis().alias_size_in_bytes >= donated
    assert len(jax.tree_util.tree_leaves(engine.counters)) == 4


@pytest.mark.parametrize("what", ["tensor_mesh", "prefill_only",
                                  "submit_prefilled"])
def test_what_is_not_built_for_this_model_says_so(engine, what):
    if what == "tensor_mesh":
        from ray_tpu.parallel import MeshConfig
        mesh = MeshConfig(data=1, fsdp=1, tensor=2).build(jax.devices()[:2])
        with pytest.raises(NotImplementedError):
            PagedLLMEngine(engine.config, mesh=mesh)
    elif what == "prefill_only":
        with pytest.raises(NotImplementedError):
            engine.prefill_only([1, 2, 3])
    else:
        with pytest.raises(NotImplementedError):
            engine.submit_prefilled(
                GenerationRequest(prompt_tokens=[1, 2], max_new_tokens=2,
                                  request_id="x"), [], None)


def test_a_shared_prefix_is_not_reused(engine):
    before = engine.stats()["prefix_skipped_recurrent"]
    prompt = prompt_of(50, 24).tolist()
    engine.generate([prompt, prompt], max_new_tokens=2)
    stats = engine.stats()
    assert stats["prefix_skipped_recurrent"] - before == 2
    assert stats["prefix_entries"] == 0


# recorded on the parent of PR 42 (676249f) by test_llm_paged's
# lowered_programs on `tiny_engine()`, and read again on PR 42's tree: the
# contract for rows that keep summaries of closed windows changed no
# program of a model that did not ask for it. `decode_step` again in PR 43
# (was 6ef3901514180479): the step's own `lax.cond` around the sampler
# went; `sample_tokens` holds the switch. `chunk_prefill` again in PR 61
# (was daa7529285a45f19): the layer that attends does so through
# `ops.attention.attend_cache`
NEMOTRON_PROGRAMS = {"decode_step": "36d4f1ec9cc8d44b",
                     "chunk_prefill": "0d3835313f5f4811"}


@pytest.mark.parametrize("program", sorted(NEMOTRON_PROGRAMS))
def test_the_engine_lowers_to_the_program_it_always_did(engine, program):
    from test_llm_paged import lowered_programs, program_hash
    assert program_hash(lowered_programs(engine)[program]) \
        == NEMOTRON_PROGRAMS[program]
