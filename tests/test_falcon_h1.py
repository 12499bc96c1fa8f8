"""Falcon-H1's hybrid block (a Mamba-2 mixer beside attention) on the CPU,
seeded random weights, a tiny config in the published ratios: the model,
the chunked scan and the paged engine's state pool against the plain
float32 reference (benchmarks/reference/falcon_h1_ref.py)."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness.builders_falcon_h1 import falcon_h1_model  # noqa: E402
from benchmarks.harness.parity_falcon_h1 import (engine_logits,  # noqa: E402
                                                 state_errors)
from benchmarks.reference import falcon_h1_ref  # noqa: E402
from plain_greedy import plain_greedy, rowwise  # noqa: E402
from ray_tpu.llm import reqtrace  # noqa: E402
from ray_tpu.llm import GenerationRequest  # noqa: E402
from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine  # noqa: E402
from ray_tpu.models.falcon_h1 import FalconH1Config  # noqa: E402
from ray_tpu.ops.ssm import ssd_chunked_scan, ssm_step  # noqa: E402

# Published key names at toy widths, in the published ratios: 5:1 GQA, two
# groups, heads x head_dim (160) != hidden_size (96), every multiplier != 1.
TINY = {
    "vocab_size": 384, "hidden_size": 96, "intermediate_size": 160,
    "num_hidden_layers": 2, "num_attention_heads": 10,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "rope_theta": 1e11,
    "rms_norm_eps": 1e-5, "mamba_d_ssm": 128, "mamba_n_heads": 8,
    "mamba_d_head": 16, "mamba_d_state": 24, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 16,
    "embedding_multiplier": 5.6568, "lm_head_multiplier": 0.25,
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.6,
    "key_multiplier": 0.3, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.7, "ssm_multipliers": [0.35, 0.8, 0.6, 0.5, 0.9],
    "mlp_multipliers": [0.7, 0.4]}

# Everything here is float32 on the CPU, the system's arithmetic and the
# reference's alike; they differ in the order of their sums (a chunked scan
# against a token-by-token one, a paged softmax against a dense one). The
# largest logit difference read over three seeds is 9e-7 at a logit spread
# of 0.25; with the recurrent state kept in bf16 it is 2.6e-5 to 2.9e-5.
# 5e-6 lies between the two readings with a factor of five on each side.
TOLERANCE = 5e-6


def tiny_model(**overrides) -> FalconH1Config:
    """The builder's FalconH1Config of TINY, in float32 with jnp attention."""
    return dataclasses.replace(
        falcon_h1_model(TINY), dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference", **overrides)


def tiny_engine(params=None, **model_overrides) -> PagedLLMEngine:
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(**model_overrides), max_batch=3, max_len=160,
        page_size=8, num_pages=96, prefill_buckets=(16, 32)), params=params)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def reference_logits(params, tokens):
    return np.asarray(falcon_h1_ref.logits(
        params, tokens, TINY, num_layers=TINY["num_hidden_layers"]))


def prompt_of(seed: int, n: int):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).tolist()


def test_tiny_config_keeps_the_published_ratios():
    cfg = tiny_model()
    assert cfg.num_heads == 5 * cfg.num_kv_heads
    assert cfg.mamba_n_groups == 2
    assert cfg.num_heads * cfg.head_dim != cfg.hidden_size
    assert cfg.mamba_d_ssm != cfg.hidden_size
    multipliers = [cfg.embedding_multiplier, cfg.lm_head_multiplier,
                   cfg.attention_in_multiplier, cfg.attention_out_multiplier,
                   cfg.key_multiplier, cfg.ssm_in_multiplier,
                   cfg.ssm_out_multiplier, *cfg.ssm_multipliers,
                   *cfg.mlp_multipliers]
    assert all(m != 1.0 for m in multipliers)


@pytest.mark.parametrize("length", [7, 16, 45, 64])
def test_forward_matches_the_reference(engine, length):
    """No-cache forward (the chunked scan from a zero state, lengths that
    are and are not whole chunks) against the token-by-token reference."""
    tokens = prompt_of(length, length)
    got = engine.model.apply({"params": engine.params},
                             jnp.asarray([tokens]))[0]
    want = reference_logits(engine.params, tokens)
    assert np.abs(np.asarray(got) - want).max() < TOLERANCE


def _scan_inputs(length, heads=8, p=16, groups=2, n=24, batch=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (batch, length, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, length, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (batch, length, groups, n))
    c = jax.random.normal(keys[4], (batch, length, groups, n))
    state = jax.random.normal(keys[5], (batch, heads, p, n))
    return x, dt, a, b, c, state


def _recurrence(x, dt, a, b, c, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssm_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], state)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("length", [5, 16, 32, 37, 50])
def test_chunked_scan_matches_the_recurrence(length):
    """Chunk 16: lengths under a chunk, whole chunks and not, from a
    carried-in state that is not zero."""
    inputs = _scan_inputs(length)
    y, last = ssd_chunked_scan(*inputs, chunk=16)
    want_y, want_last = _recurrence(*inputs)
    assert np.abs(np.asarray(y - want_y)).max() < 1e-4
    assert np.abs(np.asarray(last - want_last)).max() < 1e-4


def test_chunked_scan_keeps_padding_out_of_the_state():
    """dt = 0 past the 21st token of 32: the state handed on is the state
    after 21 tokens, whatever the padded positions hold."""
    x, dt, a, b, c, state = _scan_inputs(32)
    real = jnp.arange(32) < 21
    _, padded = ssd_chunked_scan(x, jnp.where(real[None, :, None], dt, 0.0),
                                 a, b, c, state, chunk=16)
    _, exact = ssd_chunked_scan(x[:, :21], dt[:, :21], a, b[:, :21],
                                c[:, :21], state, chunk=16)
    assert np.abs(np.asarray(padded - exact)).max() < 1e-5


def _prefill_decode_error(engine):
    prompt = prompt_of(7, 53)     # chunks of 16, 16, 16 and a padded 5
    # the cell's own parity path (benchmarks/harness/parity_falcon_h1.py) at
    # 16-token chunks, into a slot that is not the first
    prefill, decoded, fed, held = engine_logits(
        engine, prompt, chunk=16, ticks=20, slot=1)
    want, carried = falcon_h1_ref.logits(
        engine.params, prompt + fed, TINY,
        num_layers=TINY["num_hidden_layers"], states=True)
    want = np.asarray(want)
    logits = max(np.abs(prefill - want[:len(prompt)]).max(),
                 np.abs(decoded - want[len(prompt):]).max())
    return logits, state_errors(held, carried)


def test_prefill_then_decode_through_the_pools_matches_the_reference(engine):
    """Four prefill chunks, the last padded, the state handed from chunk to
    chunk and installed in the pool, then 20 decode ticks: every logit
    against the reference's one full forward over the same 73 tokens."""
    assert _prefill_decode_error(engine)[0] < TOLERANCE


def test_bf16_state_fails_the_float32_tolerance(engine):
    """The same weights with the recurrent state kept in bf16 (the nearest
    precision below the configuration's float32): it must not pass."""
    lower = tiny_engine(params=engine.params, state_dtype=jnp.bfloat16)
    assert lower.state[0][1].dtype == jnp.bfloat16
    assert _prefill_decode_error(lower)[0] > 2 * TOLERANCE


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_the_pool_row_against_the_reference_state(engine, state_dtype):
    """What the slot holds after the prefill and 20 ticks against the
    reference's carried state, every head of every layer: in float32 to
    the order of the sums (read: 8e-7 of a head's norm, the window 5e-7);
    kept in bf16 every layer's worst head is off by roundings that add up
    (read: 7e-3). 2e-5 lies 25 times over the first and far under the
    second."""
    eng = engine if state_dtype == "float32" else tiny_engine(
        params=engine.params, state_dtype=jnp.bfloat16)
    errors = _prefill_decode_error(eng)[1]
    assert len(errors["worst_head"]) == TINY["num_hidden_layers"]
    assert max(errors["window"]) < 1e-4
    if state_dtype == "float32":
        assert max(errors["worst_head"]) < 2e-5
    else:
        assert min(errors["worst_head"]) > 2e-5


def _generate_alone(engine, prompt, max_new):
    alone = tiny_engine(params=engine.params)
    return alone.generate([prompt], max_new_tokens=max_new)[0]


def _reference_greedy(params, prompt, max_new):
    return plain_greedy(rowwise(lambda row: reference_logits(params, row)),
                        [prompt], max_new)[0]


def test_requests_admitted_mid_decode_do_not_disturb_each_other(engine):
    """Two requests admitted while a third decodes, into slots earlier
    requests used: each gives the tokens it gives alone and the tokens the
    reference's greedy decoding gives (slot reuse resets the state; idle
    and prefilling rows leave the others' state alone)."""
    eng = tiny_engine(params=engine.params)
    # leave stale state in every slot first
    eng.generate([prompt_of(90 + i, 20 + i) for i in range(3)],
                 max_new_tokens=6)
    prompts = [prompt_of(11, 41), prompt_of(12, 23), prompt_of(13, 35)]
    done = {}
    keep = lambda request, tokens: done.__setitem__(  # noqa: E731
        request.request_id, tokens)
    eng.submit(GenerationRequest(prompt_tokens=prompts[0],
                                 max_new_tokens=24, request_id="0"), keep)
    for _ in range(8):
        eng.step()
    assert eng.seqs[0].phase == "decode" and eng.seqs[0].request is not None
    for i in (1, 2):
        eng.submit(GenerationRequest(prompt_tokens=prompts[i],
                                     max_new_tokens=10 + i,
                                     request_id=str(i)), keep)
    while eng.has_work():
        eng.step()
    for i, new in ((0, 24), (1, 11), (2, 12)):
        assert done[str(i)] == _generate_alone(engine, prompts[i], new)
    assert done["1"] == _reference_greedy(engine.params, prompts[1], 11)
    assert eng.stats()["leaked_pages"] == 0


def test_a_preempted_request_resumes_to_the_same_tokens(engine):
    eng = tiny_engine(params=engine.params)
    prompt = prompt_of(21, 30)
    done = {}
    eng.submit(GenerationRequest(prompt_tokens=prompt, max_new_tokens=16,
                                 request_id="a"),
               lambda request, tokens: done.__setitem__("a", tokens))
    for _ in range(9):
        eng.step()
    assert len(eng.seqs[0].generated) >= 4
    eng._preempt(0, reason="page_pressure")
    while eng.has_work():
        eng.step()
    assert eng.stats()["preemptions"] == 1
    assert done["a"] == _generate_alone(engine, prompt, 16)


def test_a_shared_prefix_is_not_reused(engine):
    eng = tiny_engine(params=engine.params)
    shared = prompt_of(31, 32)
    first = eng.generate([shared + prompt_of(32, 5)], max_new_tokens=4)[0]
    again = eng.generate([shared + prompt_of(33, 7)], max_new_tokens=4)[0]
    stats = eng.stats()
    assert stats["prefix_skipped_recurrent"] == 2
    assert stats["prefix_hits"] == 0 and stats["prefix_entries"] == 0
    assert eng.radix.shared_pages() == 0
    assert again == _generate_alone(engine, shared + prompt_of(33, 7), 4)
    assert first == _generate_alone(engine, shared + prompt_of(32, 5), 4)


def test_state_counters_and_the_prefill_chunk_event(engine):
    reqtrace.clear()
    eng = tiny_engine(params=engine.params)
    before = eng.stats()
    assert before["state_installs"] == 0
    # (conv 3 x 224 + ssm 8 x 16 x 24) float32 x 3 rows x 2 layers
    assert before["state_bytes"] == (3 * 224 + 8 * 16 * 24) * 4 * 3 * 2
    eng.submit(GenerationRequest(prompt_tokens=prompt_of(41, 37),
                                 max_new_tokens=8, request_id="r"))
    while eng.has_work():
        eng.step()
    assert eng.stats()["state_installs"] == 1
    chunks = [args for rid, event, _ts, args in reqtrace.events()
              if rid == "r" and event == reqtrace.PREFILL_CHUNK]
    assert [c["valid"] for c in chunks] == [32, 5]
    assert [c["bucket"] for c in chunks] == [32, 16]
    from ray_tpu._internal import accel
    tick = next(row for row in accel.step_summary() if row["kind"] == "tick")
    assert tick["phases"]["state"] > 0.0


def test_decode_step_donates_and_aliases_the_state_pool(engine):
    import re
    lowered = engine.lower_decode()
    # the scan-state pools (3 rows x 8 x 16 x 24, one a layer) are donated
    donated = re.findall(
        r"tensor<3x8x16x24xf32> \{[^%]*(?:tf\.aliasing_output|"
        r"jax\.buffer_donor)", lowered.as_text())
    assert len(donated) == engine.config.model.num_layers
    compiled = lowered.compile()
    state_bytes = engine.stats()["state_bytes"]
    pool_bytes = engine.stats()["hbm_cache_bytes"]
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= state_bytes + pool_bytes
    assert engine.state_copies(compiled.as_text()) == 0


@pytest.mark.parametrize("what", ["prefill_only", "submit_prefilled",
                                  "tensor_mesh"])
def test_what_is_not_built_for_recurrent_state_says_so(engine, what):
    if what == "prefill_only":
        with pytest.raises(NotImplementedError):
            engine.prefill_only(prompt_of(1, 9))
    elif what == "submit_prefilled":
        with pytest.raises(NotImplementedError):
            engine.submit_prefilled(GenerationRequest(
                prompt_tokens=prompt_of(1, 9), max_new_tokens=2), [], None)
    else:
        from ray_tpu.parallel import MeshConfig
        mesh = MeshConfig(data=1, tensor=2).build(jax.devices()[:2])
        with pytest.raises(NotImplementedError):
            PagedLLMEngine(engine.config, mesh=mesh)


def test_a_dense_engine_owns_no_recurrent_state():
    """A LlamaConfig engine: no state pool, no `write_state` program, no
    `state` tick phase, no skipped prefix, the dense decode signature."""
    from ray_tpu._internal import accel
    from ray_tpu.models.llama import LlamaConfig
    dense = PagedLLMEngine(PagedEngineConfig(
        model=dataclasses.replace(LlamaConfig.tiny_test(),
                                  dtype=jnp.float32),
        max_batch=2, max_len=64, page_size=8, num_pages=32,
        prefill_buckets=(16, 32)))
    before = {row["kind"]: dict(row.get("phases", {}))
              for row in accel.step_summary()}.get("tick", {})
    shared = [t % 256 for t in prompt_of(1, 20)]
    dense.generate([shared + [3]], max_new_tokens=4)
    dense.generate([shared + [5, 7]], max_new_tokens=4)
    stats = dense.stats()
    assert dense.state is None and not hasattr(dense, "_write_state")
    assert stats["state_bytes"] == 0 and stats["state_installs"] == 0
    assert stats["prefix_skipped_recurrent"] == 0 and stats["prefix_hits"] == 1
    assert dense.state_copies(dense.decode_program_text()) == 0
    after = {row["kind"]: dict(row.get("phases", {}))
             for row in accel.step_summary()}["tick"]
    assert after.get("state", 0.0) == before.get("state", 0.0)


# recorded from the commit before the engine learned of layer kinds (PR 33's
# tree, 2c580e1), by test_llm_paged.lowered_programs on `tiny_engine()`;
# `decode_step` again in PR 37 (was 73aba8d37aa6e0a4): the gather fallback
# is now the dense model's (ops.paged_attention.paged_attend), which
# repeats the kv heads before it casts to float32 where this model's own
# copy cast first. The same values; `chunk_prefill` holds no paged decode.
# `decode_step` again in PR 43 (was 0d4903ff37ada98a): the step's own
# `lax.cond` around the sampler went; `sample_tokens` holds the switch.
# `chunk_prefill` again in PR 61 (was afb853d4dea3960f): the chunk attends
# its dense cache through `ops.attention.attend_cache`
FALCON_PROGRAMS = {"decode_step": "34f2c4ce8d947c2f",
                   "chunk_prefill": "9336d76858fc601a"}


@pytest.mark.parametrize("program", sorted(FALCON_PROGRAMS))
def test_hybrid_engine_lowers_to_the_program_it_always_did(engine, program):
    """A FalconH1Config engine (every layer attends AND scans) lowers to
    the StableHLO it lowered to before a configuration could give each
    layer a kind of its own, text for text."""
    from test_llm_paged import lowered_programs, program_hash
    assert program_hash(lowered_programs(engine)[program]) \
        == FALCON_PROGRAMS[program]
