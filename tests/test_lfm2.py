"""LFM2's hybrid of gated short convolutions, 64-wide attention heads in
packed page pools and sigmoid-routed SwiGLU experts, on the CPU, seeded
random weights, a tiny config in the published ratios: the model, the
chunks written straight into the row's pages, the paged decode through
pools and windows, and the share of the experts a chip holds, against the
plain float32 reference (benchmarks/reference/lfm2_ref.py)."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import parity_lfm2 as parity  # noqa: E402
from benchmarks.harness.builders_lfm2 import (lfm2_model,  # noqa: E402
                                              reference_keys)
from benchmarks.reference import lfm2_ref  # noqa: E402
from plain_greedy import plain_greedy, rowwise  # noqa: E402
from ray_tpu.llm import GenerationRequest  # noqa: E402
from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine  # noqa: E402
from ray_tpu.models.lfm2 import (Block, Lfm2Config,  # noqa: E402
                                 published_layer_types)
from ray_tpu.ops import paged_attention as pa  # noqa: E402

# Published key names at toy widths, in the published ratios: two leading
# conv + dense layers, two whole periods behind them, 4 : 1 GQA, heads 16
# wide, 2 experts a token of 16, all held here (the shares are a test of
# their own).
TINY = {
    "vocab_size": 384, "hidden_size": 64, "intermediate_size": 368,
    "num_hidden_layers": 10, "layer_types": list(published_layer_types(10)),
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
    "num_experts": 16, "held_experts": [0, 16], "num_experts_per_tok": 2,
    "moe_intermediate_size": 48, "routed_scaling_factor": 1,
    "norm_topk_prob": True, "use_expert_bias": True, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 512}

# Everything here is float32 on the CPU, the system's arithmetic and the
# reference's alike; they differ in the order of their sums (a blockwise
# softmax over pages against a dense one, a window carried between calls
# against one pass over the sequence). The largest logit difference read
# over the cases below is 2e-6 at a logit spread of 0.4; with the windows
# rounded to bf16 between calls it is 1e-3. 2e-5 lies between the two
# readings with a factor of ten and of fifty.
TOLERANCE = 2e-5


def tiny_model(**overrides) -> Lfm2Config:
    """The builder's Lfm2Config of TINY, in float32 with jnp attention."""
    return dataclasses.replace(
        lfm2_model(TINY), dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference", **overrides)


def tiny_engine(params=None, pages=96, **model_overrides) -> PagedLLMEngine:
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(**model_overrides), max_batch=3, max_len=160,
        page_size=8, num_pages=pages, prefill_buckets=(8, 16)),
        params=params)


@pytest.fixture(scope="module")
def engine():
    return tiny_engine()


def reference_logits(params, tokens, **kw):
    return np.asarray(lfm2_ref.logits(params, tokens, reference_keys(TINY),
                                      **kw))


def prompt_of(seed: int, n: int):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], size=n).tolist()


def test_tiny_config_keeps_the_published_pattern_and_the_engines_contract(
        engine):
    cfg = tiny_model()
    assert cfg.layer_types[:7] == ("conv", "conv", "full_attention", "conv",
                                   "conv", "conv", "full_attention")
    assert Lfm2Config().layer_types.count("conv") == 30
    assert Lfm2Config().layer_types.count("full_attention") == 10
    assert Lfm2Config().head_dim == 64
    kinds = cfg.layer_caches()
    # the counters stand beside EITHER mixer, from the third layer on
    assert kinds[1] == (False, True, False)
    assert kinds[2] == (True, False, True) and kinds[3] == (False, True, True)
    assert list(cfg.state_shapes()) == ["conv"]
    stats = engine.stats()
    assert stats["layer_kinds"][:4] == ["s", "s", "pc", "sc"]
    assert len(engine.k_pages) == 2 and len(engine.state) == 8
    assert all(len(pools) == 1 for pools in engine.state)
    assert engine.state[0][0].shape == (3, 2, 64)
    # two kv heads of 16 side by side in a row of the pool
    assert engine.k_pages[0].shape == (1, 96, 8, 32)
    assert Lfm2Config().page_pool(5632, 64) == (4, 5632, 64, 128)


@pytest.mark.parametrize("length", [1, 2, 7, 40])
def test_forward_matches_the_reference(engine, length):
    """The whole sequence from a zero window, lengths under and over the
    filter's taps."""
    tokens = prompt_of(length, length)
    got = engine.model.apply({"params": engine.params},
                             jnp.asarray([tokens]))[0]
    want = reference_logits(engine.params, tokens)
    assert np.abs(np.asarray(got) - want).max() < TOLERANCE


def _through_the_pools(engine, prompts, ticks, slots=(1,)):
    """The cell's own parity path: each prompt in the tick's buckets
    straight into its row's pages, every chunk through the check's program
    and the engine's timed one, the windows installed, then decode steps of
    the rows together through both programs, fed what the timed step
    sampled. Returns (per row the prefill's dict, the decode's dict)."""
    programs = parity.Programs(engine)
    cfg = engine.config
    tables = [[engine.pool.alloc()
               for _ in range(-(-(len(p) + ticks) // cfg.page_size))]
              for p in prompts]
    try:
        filled = [parity.prefill(engine, programs, p, pages)
                  for p, pages in zip(prompts, tables)]
        for row, slot in zip(filled, slots):
            parity.install(engine, programs, row, slot)
        decoded = parity.decode(
            engine, programs, slots, tables, [len(p) for p in prompts],
            [row["first_token"] for row in filled], ticks)
    finally:
        for pages in tables:
            for page in pages:
                engine.pool.decref(page)
    return filled, decoded


@pytest.mark.parametrize("n_prompt", list(range(33, 49)))
def test_chunks_into_pages_then_decode_match_the_reference(engine, n_prompt):
    """Prompts of 33 to 48 tokens: two chunks of 16 and a tail of every
    `valid` from 1 to 16 (in the 8 and the 16 bucket; a tail of one token
    takes half its window from the chunk before), the K/V written into the
    row's pages through its table, then 6 decode steps through the packed
    pools and the windows: every logit against the reference's one full
    forward, and the timed chunk's last row and the timed step's tokens
    against the check's program."""
    prompt = prompt_of(n_prompt, n_prompt)
    (filled,), decoded = _through_the_pools(engine, [prompt], 6)
    fed = decoded["fed"][0].tolist()
    want = reference_logits(engine.params, prompt + fed)
    assert np.abs(filled["logits"] - want[:n_prompt]).max() < TOLERANCE
    assert np.abs(decoded["logits"][0] - want[n_prompt:]).max() < TOLERANCE
    for timed, check in filled["timed"]:
        assert np.abs(timed - check).max() < TOLERANCE
    assert (decoded["sampled"] == decoded["logits"].argmax(-1)).all()


def test_ragged_rows_through_the_timed_step_match_the_reference(engine):
    """Two rows of three live, a dead one between, prompts of 37 and 17
    tokens (the second ends in a chunk of one token), ten steps together
    that take both across page edges: every row's logits are the
    reference's over its own tokens, the tokens are the timed step's, and
    the engine's expert counters gained what the live rows' routes say."""
    prompts, slots, ticks = [prompt_of(40, 37), prompt_of(41, 17)], (0, 2), 10
    was = jax.device_get(engine.counters)
    filled, decoded = _through_the_pools(engine, prompts, ticks, slots)
    for r, prompt in enumerate(prompts):
        want = reference_logits(engine.params,
                                prompt + decoded["fed"][r].tolist())
        assert np.abs(filled[r]["logits"] - want[:len(prompt)]).max() \
            < TOLERANCE
        assert np.abs(decoded["logits"][r] - want[len(prompt):]).max() \
            < TOLERANCE
    assert (decoded["sampled"] == decoded["logits"].argmax(-1)).all()
    assert (decoded["fed"][:, 1:] == decoded["sampled"][:, :-1]).all()
    gained = [tuple(np.asarray(b) - np.asarray(a) for a, b in zip(x, y))
              for x, y in zip(was, jax.device_get(engine.counters))]
    off = parity.counters_gained(gained, decoded["batch_routes"], slots,
                                 engine.config.model.held_experts)
    assert off["pairs_off"] == 0 and off["steps_off"] == 0
    assert off["idle_rows_counted"] > 0.2


def test_bf16_windows_fail_the_float32_tolerance(engine):
    """The nearest precision below: the same weights with the windows kept
    in bf16 between calls must not pass."""
    lower = tiny_engine(params=engine.params)
    lower.state = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), lower.state)
    prompt = prompt_of(3, 40)
    _, decoded = _through_the_pools(lower, [prompt], 6)
    want = reference_logits(engine.params,
                            prompt + decoded["fed"][0].tolist())
    assert np.abs(decoded["logits"][0] - want[40:]).max() > 5 * TOLERANCE


def test_a_two_tap_filter_fails_the_tolerance(engine):
    """The reference without the filter's oldest tap is another model."""
    tokens = prompt_of(9, 24)
    got = engine.model.apply({"params": engine.params},
                             jnp.asarray([tokens]))[0]
    want = reference_logits(engine.params, tokens, taps_dropped=1)
    assert np.abs(np.asarray(got) - want).max() > 100 * TOLERANCE


def _reference_greedy(params, prompts, max_new):
    return plain_greedy(rowwise(lambda row: reference_logits(params, row)),
                        prompts, max_new, length=96)


def test_rows_joining_and_leaving_match_the_reference(engine):
    """Five requests on three rows, prompts under and over a bucket and
    one of a single token: rows join mid-decode and leave at different
    steps; each request's greedy tokens are the reference's."""
    prompts = [prompt_of(20, 37), prompt_of(21, 1), prompt_of(22, 9),
               prompt_of(23, 50), prompt_of(24, 16)]
    before = engine.stats()
    got = engine.generate(prompts, max_new_tokens=8)
    assert got == _reference_greedy(engine.params, prompts, 8)
    stats = engine.stats()
    assert stats["state_installs"] - before["state_installs"] == 5
    chunks = sum(-(-len(p) // 16) for p in prompts)
    assert stats["prefill_chunks_in_place"] \
        - before["prefill_chunks_in_place"] == chunks
    assert stats["prefix_skipped_recurrent"] > 0
    assert stats["leaked_pages"] == 0 and stats["preemptions"] == 0
    assert all(seq.dense_caches is None for seq in engine.seqs)


def test_a_preempted_request_resumes_to_the_same_tokens(engine):
    """A pool too small for three rows' answers: the youngest is preempted,
    re-prefilled with what it generated, and ends on the same tokens."""
    small = tiny_engine(params=engine.params, pages=14)
    prompts = [prompt_of(30, 30), prompt_of(31, 28), prompt_of(32, 26)]
    got = small.generate(prompts, max_new_tokens=14)
    assert small.stats()["preemptions"] > 0
    assert got == _reference_greedy(engine.params, prompts, 14)
    assert small.stats()["leaked_pages"] == 0


def test_the_eight_shares_add_up_to_the_uncut_layer(engine):
    """`held = (2 k, 2)`, k = 0..7: the routed parts that the eight chips
    of the deployment give add up to the uncut reference layer (there is
    no shared expert to count once)."""
    from ray_tpu.parallel.mesh import unbox
    cfg = engine.config.model
    layer = engine.params["layer_3"]
    x = jax.random.normal(jax.random.PRNGKey(4), (29, cfg.hidden_size))
    sh = lfm2_ref.shape_of(reference_keys(TINY))
    mixed, _ = lfm2_ref.conv_layer(x, layer, sh=sh)
    want, _ = lfm2_ref.expert_layer(mixed, layer, None, sh=sh)

    def share(first):
        part = dataclasses.replace(cfg, held_experts=(first, 2))
        experts = dict(layer["moe"])
        for name in ("w_in", "w_out", "w_gate"):
            experts[name] = experts[name][first:first + 2]
        p = dict(layer, moe=experts)
        got = Block(part, 3).apply({"params": p}, x[None], None)[0][0]
        ref, _ = lfm2_ref.expert_layer(
            mixed, p, None, sh=sh._replace(held=(first, 2)))
        assert float(jnp.abs(got - ref).max()) < TOLERANCE
        return got - mixed

    total = mixed + sum(share(first) for first in range(0, 16, 2))
    assert float(jnp.abs(total - want).max()) < TOLERANCE
    assert unbox is not None


RAGGED = [1, 5, 64, 65, 200, 256, 257, 511]


def _packed_case(seed=0, rows=8, heads=32, kv_heads=8, hd=64, page=16,
                 per_row=32):
    rng = np.random.default_rng(seed)
    shape = pa.packed_pool_shape(kv_heads, hd, rows * per_row + 1, page)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((rows, heads, hd)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(rows * per_row).reshape(
        rows, per_row), jnp.int32)
    return q, k, v, jnp.asarray(RAGGED, jnp.int32), tables


def test_packed_pool_stands_two_heads_of_64_in_a_row():
    assert pa.packed_pool_shape(8, 64, 100, 64) == (4, 100, 64, 128)
    assert pa.packed_pool_shape(8, 128, 100, 64) == (8, 100, 64, 128)
    assert pa.packed_pool_shape(2, 16, 100, 8) == (1, 100, 8, 32)
    assert pa.paged_kernel(128) == pa.paged_kernel(64) == "gather"


def test_the_gather_path_over_a_packed_pool_is_plain_attention():
    """Two heads a row unpacked: each row's attention over its pages is
    the dense softmax over its own kv head's tokens."""
    q, k, v, lengths, tables = _packed_case()
    got = pa.paged_attend(q, k, v, lengths - 1, tables)
    row = 4
    n = int(lengths[row])
    unpack = lambda pool: np.asarray(pool)[:, np.asarray(tables[row])] \
        .reshape(4, -1, 2, 64).transpose(0, 2, 1, 3).reshape(8, -1, 64)[:, :n]
    keys, values = unpack(k), unpack(v)
    for head in (0, 5, 31):
        logits = keys[head // 4] @ np.asarray(q[row, head]) / 8.0
        probs = np.exp(logits - logits.max())
        want = (probs / probs.sum()) @ values[head // 4]
        assert np.abs(np.asarray(got[row, head]) - want).max() < 1e-5


@pytest.mark.parametrize("block_pages", [None, 16])
def test_the_kernel_at_64_wide_heads_matches_the_gather_path(block_pages):
    """The Pallas kernel under the TPU interpreter over a packed pool,
    ragged lengths (one token, a page's edge, a chunk's edge, most of a
    table), against the gather path."""
    q, k, v, lengths, tables = _packed_case()
    want = pa.paged_attend(q, k, v, lengths - 1, tables)
    got = pa._paged_attend_packed(q * 64 ** -0.5, k, v, lengths, tables,
                                  block_pages=block_pages)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_a_chunk_over_packed_pages_is_causal_attention():
    """`write_chunk_pages` then `paged_attend_chunk` for a chunk that
    starts 48 tokens into a row, its last 5 tokens padding: the real
    queries attend everything before them and themselves, the padded rows
    of whole pages land on the null page."""
    rng = np.random.default_rng(1)
    kv_heads, heads, hd, page, chunk, start, valid = 2, 4, 16, 8, 16, 48, 11
    shape = pa.packed_pool_shape(kv_heads, hd, 40, page)
    k_pool = jnp.zeros(shape, jnp.float32)
    v_pool = jnp.zeros(shape, jnp.float32)
    table = jnp.asarray(list(range(3, 11)) + [0] * 4, jnp.int32)
    keys = rng.standard_normal((start + chunk, kv_heads, hd)).astype(
        np.float32)
    values = rng.standard_normal((start + chunk, kv_heads, hd)).astype(
        np.float32)
    for off in range(0, start, chunk):
        k_pool = pa.write_chunk_pages(k_pool, jnp.asarray(
            keys[off:off + chunk]), table, off, chunk)
        v_pool = pa.write_chunk_pages(v_pool, jnp.asarray(
            values[off:off + chunk]), table, off, chunk)
    k_pool = pa.write_chunk_pages(k_pool, jnp.asarray(keys[start:]), table,
                                  start, valid)
    v_pool = pa.write_chunk_pages(v_pool, jnp.asarray(values[start:]),
                                  table, start, valid)
    q = rng.standard_normal((chunk, heads, hd)).astype(np.float32)
    got = np.asarray(pa.paged_attend_chunk(
        jnp.asarray(q) * hd ** -0.5, k_pool, v_pool, table, start))
    for i in (0, 4, valid - 1):
        for head in range(heads):
            n = start + i + 1
            logits = keys[:n, head // 2] @ q[i, head] * hd ** -0.5
            probs = np.exp(logits - logits.max())
            want = (probs / probs.sum()) @ values[:n, head // 2]
            assert np.abs(got[i, head] - want).max() < 1e-5
    # page 9 holds tokens 48-55, page 10 tokens 56-63 of which 56-58 are
    # real; the null page took nothing of the row's
    assert float(jnp.abs(k_pool[:, 10, 3:]).max()) > 0  # the padded tail
    assert np.abs(np.asarray(k_pool[:, 9]).reshape(8, 2, 16)
                  - keys[48:56]).max() == 0


def test_decode_step_donates_and_aliases_pools_windows_and_counters(engine):
    lowered = engine.lower_decode()
    text = lowered.compile().as_text()
    # (the CPU's compiler copies the 3 x 2 x 64 window pools; the chip's
    # does not: tests/test_aot_tpu_compile.py)
    assert engine.pool_copies(text) == 0
    for scope in ("conv/in", "conv/filter", "conv/out", "attn/qk_norm",
                  "attn/attend", "moe/route", "moe/experts"):
        assert scope in lowered.as_text(debug_info=True), scope


def test_the_chunk_stages_no_dense_cache(engine):
    """The chunk program's arguments hold the pools, the windows and
    nothing that grows with `max_len`: no [1, kv_heads, length, hd]."""
    from ray_tpu.llm.paged import array_shapes
    cfg = engine.config
    text = engine.lower_chunk().as_text()
    length = cfg.pages_per_seq * cfg.page_size + cfg.prefill_buckets[-1]
    assert array_shapes(text.replace("x", ","), (1, 2, length, 16)) == 0
    staged = engine._dense_zero_caches()
    assert staged["kv"] == [] and len(staged["state"]) == 8


@pytest.mark.parametrize("what", ["tensor_mesh", "prefill_only",
                                  "submit_prefilled", "buckets"])
def test_what_is_not_built_for_this_model_says_so(engine, what):
    if what == "tensor_mesh":
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tensor",))
        with pytest.raises(NotImplementedError, match="recurrent state"):
            PagedLLMEngine(engine.config, mesh=mesh)
    elif what == "buckets":
        with pytest.raises(ValueError, match="whole pages"):
            PagedLLMEngine(dataclasses.replace(
                engine.config, prefill_buckets=(8, 12)))
    elif what == "prefill_only":
        with pytest.raises(NotImplementedError, match="recurrent state"):
            engine.prefill_only([1, 2, 3])
    else:
        with pytest.raises(NotImplementedError, match="recurrent state"):
            engine.submit_prefilled(
                GenerationRequest(prompt_tokens=[1, 2, 3]), [], None)


def test_the_routers_constant_is_a_departure_of_5e_7_of_a_weight():
    """The program is served with the tree's one sigmoid router (1e-20 in
    the weights' sum) where the family publishes 1e-6 and the reference
    keeps it: the weights differ by 5e-7 of themselves, the choices not at
    all."""
    from ray_tpu.models import moe
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((9, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    bias = jnp.zeros((16,), jnp.float32)
    chosen, weights, scores = moe.sigmoid_top_k(u, router, bias, 4, 1.0)
    keys = dict(reference_keys(TINY), num_experts_per_tok=4)
    _, published = lfm2_ref._route(
        u @ jnp.eye(32), {"router": router, "e_score_correction_bias": bias},
        None, lfm2_ref.shape_of(keys))
    got = np.zeros((9, 16), np.float32)
    np.put_along_axis(got, np.asarray(chosen), np.asarray(weights), -1)
    assert np.array_equal(got > 0, np.asarray(published) > 0)
    assert np.abs(got - np.asarray(published)).max() < 2e-6
