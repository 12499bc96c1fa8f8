"""Xing4.0-29B-A4B (a four-stream residual mixed by Sinkhorn-projected
hyper-connections around latent attention with a query latent and routed
SwiGLU experts, all held) on the CPU, seeded random weights, a tiny config
in the published ratios: the model in its three paths and the paged
engine's latent pools against the plain float32 reference
(benchmarks/reference/xing_mhc_ref.py), the hyper-connection alone, and
what the widened `LatentAttention` left of Sarvam's programs. Logits, never
tokens."""

import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness.builders_xing_mhc import xing_mhc_model  # noqa: E402
from benchmarks.reference import sarvam_mla_ref, xing_mhc_ref  # noqa: E402
from plain_greedy import plain_greedy, rowwise  # noqa: E402
from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine  # noqa: E402
from ray_tpu.models import moe, xing_mhc  # noqa: E402
from ray_tpu.models.xing_mhc import XingMHCConfig  # noqa: E402
from ray_tpu.parallel.mesh import unbox  # noqa: E402
from test_sarvam_mla import (_chunks, _decode_logits, _fresh_pools,  # noqa: E402
                             _row_table)
from test_sarvam_mla import tiny_engine as sarvam_tiny_engine  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
# Published key names at toy widths, in the published ratios: the kv latent
# 4 x the nope width, rope half of it, the query latent 1.5 x the kv latent,
# 2 experts a token of 8, ALL held, four streams, a leading dense layer and
# two expert layers.
TINY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 144,
    "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "first_k_dense_replace": 1, "n_routed_experts": 8,
    "held_experts": [0, 8], "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "n_shared_experts": 1,
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": YARN,
    "max_position_embeddings": 262144, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30}

# Everything here is float32 on the CPU, the system's arithmetic and the
# reference's alike; they differ in the order of their sums (absorbed
# against expanded products, every held expert in one product against two
# experts at a time, sixteen arrays a Sinkhorn step against one matrix). The largest logit difference read over three seeds is 1.3e-5 of
# a logit spread of 1.0.
TOLERANCE = 5e-5
# After 20 iterations from b_res = 4 I (0.95 on the diagonal: each
# iteration closes 13 % of what is left) the columns, normalised last, sum
# to 1 to rounding and the rows to 2e-3 at the seeded gains.
ROWS_WITHIN, COLUMNS_WITHIN = 5e-3, 1e-5


def tiny_model(**overrides) -> XingMHCConfig:
    return dataclasses.replace(xing_mhc_model(TINY), **dict(
        dict(dtype=jnp.float32, param_dtype=jnp.float32,
             attention_impl="reference"), **overrides))


def tiny_engine(params=None, **model_overrides) -> PagedLLMEngine:
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(**model_overrides), max_batch=3, max_len=160,
        page_size=8, num_pages=96, prefill_buckets=(16, 32)), params=params)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def prompt_of(seed: int, n: int):
    return np.random.default_rng(seed).integers(1, TINY["vocab_size"], n)


def spread(logits) -> float:
    return float(np.asarray(logits).std(-1).mean())


def test_tiny_config_keeps_the_published_ratios_and_the_engines_contract():
    cfg = tiny_model()
    assert cfg.kv_lora_rank == 4 * cfg.qk_nope_head_dim
    assert cfg.q_lora_rank * 2 == cfg.kv_lora_rank * 3
    assert cfg.held_experts == (0, cfg.num_experts)
    assert cfg.layer_caches() == ((True, False, False), (True, False, True),
                                  (True, False, True))
    assert cfg.latent_cache() == (128, 32)
    assert [c[0].shape for c in cfg.init_counters()] == [(8,), (8,)]
    published = XingMHCConfig()
    assert published.latent_cache() == (640, 512)
    assert (published.num_heads, published.q_lora_rank,
            published.hc_mult) == (32, 768, 4)
    assert abs(published.softmax_scale
               - 192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-9
    assert isinstance(cfg.module(), xing_mhc.XingMHCModel)


@pytest.mark.parametrize("length", [1, 7, 40])
def test_forward_matches_the_reference(engine, length):
    tokens = prompt_of(length, length)
    got = engine.model.apply({"params": engine.params}, tokens[None])[0]
    want = xing_mhc_ref.logits(engine.params, tokens, TINY)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        < TOLERANCE * spread(want)


@pytest.mark.parametrize("n_prompt,n_fed", [(44, 24), (70, 6)])
def test_chunks_then_decode_through_latent_pages_match_the_reference(
        engine, n_prompt, n_fed):
    """The engine's own chunk program (absorbed, through pages of 8:
    buckets of 32 and a tail of 12 or 6 in the 16 bucket, page edges inside
    every chunk), then a paged decode program of its shapes, against the
    reference's one forward pass over prompt + fed."""
    prompt, fed = prompt_of(4, n_prompt), prompt_of(5, n_fed)
    table = _row_table(engine, list(range(20, 20 + -(-(n_prompt + n_fed)
                                                     // 8))))
    prefill, pools = _chunks(engine, prompt, _fresh_pools(engine), table)
    decode, _, counters = _decode_logits(engine, pools, table, n_prompt, fed)
    want = np.asarray(xing_mhc_ref.logits(
        engine.params, np.concatenate([prompt, fed]), TINY))
    assert np.abs(prefill - want[:n_prompt]).max() < TOLERANCE * spread(want)
    assert np.abs(decode - want[n_prompt:]).max() < TOLERANCE * spread(want)
    # the live row alone reached the experts' counters: every pair is held
    for pairs, steps in counters:
        assert int(pairs.sum()) == n_fed * 2 >= int(steps.sum())


def test_engine_serves_rows_under_and_over_a_bucket_together(engine):
    prompts = [prompt_of(21, 5).tolist(), prompt_of(22, 37).tolist(),
               prompt_of(23, 70).tolist()]
    got = engine.generate(prompts, max_new_tokens=6)
    want = plain_greedy(rowwise(lambda row: xing_mhc_ref.logits(
        engine.params, row, TINY)), prompts, 6)
    assert got == want
    stats = engine.stats()
    assert stats["layer_kinds"] == ["p", "pc", "pc"]
    assert stats["leaked_pages"] == 0 and engine.v_pages == []


@pytest.mark.parametrize("streams", [2, 4])
def test_h_res_rows_and_columns_sum_to_one(streams):
    """`HyperConnection` alone, at `hc_mult` 2 and 4, seeded as the
    configuration seeds it: after 20 iterations H_res is doubly stochastic
    within ROWS_WITHIN / COLUMNS_WITHIN, H_pre in (0, 1), H_post in
    (0, 2), all of it float32 whatever the streams' type."""
    cfg = tiny_model(hc_mult=streams, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(streams),
                          (streams, 2, 9, cfg.hidden_size), jnp.bfloat16)
    module = xing_mhc.HyperConnection(cfg)
    params = unbox(module.init(jax.random.PRNGKey(0), x)["params"])
    (pre, post, res), seen = module.apply({"params": params}, x,
                                          mutable=["intermediates"])
    res = np.asarray(jnp.stack([jnp.stack(row) for row in res]))
    assert res.shape == (streams, streams, 2, 9) and res.dtype == np.float32
    assert np.abs(res.sum(0) - 1).max() < COLUMNS_WITHIN
    assert 1e-5 < np.abs(res.sum(1) - 1).max() < ROWS_WITHIN
    assert (res > 0).all()
    pre, post = np.asarray(jnp.stack(pre)), np.asarray(jnp.stack(post))
    assert ((0 < pre) & (pre < 1)).all() and ((0 < post) & (post < 2)).all()
    sown = seen["intermediates"]["coefficients"][0]
    assert sown.shape == (streams * streams + 2 * streams, 2, 9)
    # the reference's chain on the same streams and parameters
    mix = xing_mhc_ref.Mixing(streams, 20, 1e-6, (-30.0, 30.0), 1e-6)
    want = xing_mhc_ref.coefficients(
        jnp.moveaxis(x, 0, 2).reshape(18, streams, -1).astype(jnp.float32),
        params, mix=mix)
    assert np.abs(res.reshape(streams, streams, 18)
                  - np.moveaxis(np.asarray(want[2]), 0, 2)).max() < 1e-5


def test_forced_coefficients_reproduce_the_single_residual_stream():
    """HC with H_pre = H_post = [1, 0, ..] and H_res = I is x + G(N(x)) in
    stream 0, and leaves the other streams as they were."""
    n, d = 4, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (n, 2, 5, d), jnp.float32)
    ones = jnp.ones((2, 5))
    first = [ones] + [0 * ones] * (n - 1)
    identity = [[ones if i == j else 0 * ones for j in range(n)]
                for i in range(n)]
    w = jax.random.normal(jax.random.PRNGKey(2), (d, d), jnp.float32)
    sublayer = lambda u: (jnp.tanh(sarvam_mla_ref._norm(  # noqa: E731
        u, jnp.ones((d,)), 1e-6) @ w), "kept")
    got, kept = xing_mhc.hyper_connect(x, (first, first, identity), sublayer)
    assert kept == "kept"
    assert np.allclose(got[0], x[0] + sublayer(x[0])[0], atol=1e-6)
    assert np.array_equal(np.asarray(got[1:]), np.asarray(x[1:]))


# recorded from the commit before `LatentAttention` took `q_lora_rank`
# (PR 51's tree, 3472991), by this very function at these very sizes
SARVAM_PROGRAMS = {"decode_step": "0377369b20ec68dc",
                   "chunk_prefill": "0c879c2caf5ad254"}


@pytest.mark.parametrize("program", sorted(SARVAM_PROGRAMS))
def test_sarvam_lowers_to_the_program_it_did_without_a_query_latent(program):
    """`LatentAttention(q_lora_rank=None)` is, StableHLO text for text,
    Sarvam's decode step and prefill chunk as they were. A change that
    means to alter them re-records the hash."""
    engine = sarvam_tiny_engine()
    text = {"decode_step": engine.lower_decode,
            "chunk_prefill": engine.lower_chunk}[program]().as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == SARVAM_PROGRAMS[program]


def test_every_expert_held_is_the_references_whole_layer(engine):
    """`RoutedExperts(held=(0, E))`, every held expert on every token,
    against the reference's sum a block of experts at a time."""
    cfg = engine.config.model
    layer = engine.params["layer_1"]
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 23, cfg.hidden_size),
                          jnp.float32)
    routed = moe.RoutedExperts(
        num_experts=cfg.num_experts,
        experts_per_token=cfg.num_experts_per_tok, held=cfg.held_experts,
        mlp_dim=cfg.moe_intermediate_size,
        routed_scaling=cfg.routed_scaling_factor, dtype=jnp.float32,
        param_dtype=jnp.float32, gated=True)
    got, pairs = routed.apply({"params": layer["moe"]["routed"]}, u, u)
    assert int(pairs.sum()) == 23 * cfg.num_experts_per_tok
    sh, _ = xing_mhc_ref.shapes_of(TINY)
    # the reference routes n(u; mlp_norm): hand it a layer whose norm is 1
    # over inputs already of unit mean square
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-6)
    got, _ = routed.apply({"params": layer["moe"]["routed"]}, u, u)
    plain = dict(layer, mlp_norm={"scale": jnp.ones((cfg.hidden_size,))})
    want, selection = xing_mhc_ref.experts(u[0], plain, sh, shared=False)
    assert selection.shape == (23, cfg.num_experts)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 2e-5


def test_the_reference_in_small_blocks_is_the_reference(engine, monkeypatch):
    """Blocks of heads, queries and positions shrunk so that each takes
    several: the same logits."""
    tokens = prompt_of(9, 61)
    want = np.asarray(xing_mhc_ref.logits(engine.params, tokens, TINY))
    monkeypatch.setattr(xing_mhc_ref, "HEAD_BLOCK", 2)
    monkeypatch.setattr(xing_mhc_ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(sarvam_mla_ref, "ROW_BLOCK", 24)
    got, details = xing_mhc_ref.logits(engine.params, tokens, TINY,
                                       rows=np.arange(30, 61), details=True)
    assert np.abs(np.asarray(got) - want[30:]).max() < 1e-5 * spread(want)
    assert [s.shape for s in details["selection"]] == [(61, 8)] * 2
    # what every connection read, at the rows asked for: the first, n copies
    read = details["streams"]
    assert [x.shape for x in read] == [(31, 4, 64)] * 6
    assert (read[0] == read[0][:, :1]).all() and (read[1] != read[0]).any()
