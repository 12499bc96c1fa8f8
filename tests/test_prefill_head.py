"""A prefill chunk applies the head to the one row a prompt's finish needs
(PR 40): `chunk_prefill(..., last)` against the all-positions form it
replaced in the tick, for the three model families the engine runs, and
the engine's greedy outputs against the plain model's. Float32 on the
CPU: the two forms share every line in front of the head, and a row of a
product is summed in the order the whole product sums it."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from plain_greedy import model_forward, plain_greedy
from ray_tpu.llm import PagedEngineConfig, PagedLLMEngine
from ray_tpu.llm.paged import array_shapes
from ray_tpu.models.llama import LlamaConfig

BUCKET, TAKE = 32, 21          # a padded bucket: 21 real tokens of 32


def tiny_llama(tie_embeddings=False) -> LlamaConfig:
    return dataclasses.replace(
        LlamaConfig.tiny_test(), dtype=jnp.float32, max_seq_len=256,
        remat=False, use_flash=False, attention_impl="reference",
        tie_embeddings=tie_embeddings)


def llama_engine(tie_embeddings=False) -> PagedLLMEngine:
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_llama(tie_embeddings), max_batch=3, max_len=160,
        page_size=8, num_pages=96, prefill_buckets=(16, 32)))


def falcon_engine() -> PagedLLMEngine:
    from test_falcon_h1 import tiny_engine
    return tiny_engine()


def nemotron_engine() -> PagedLLMEngine:
    from test_nemotron_h import tiny_engine
    return tiny_engine()


FAMILIES = {"llama": llama_engine,
            "llama_tied": lambda: llama_engine(tie_embeddings=True),
            "falcon_h1": falcon_engine, "nemotron_h": nemotron_engine}
_ENGINES = {}


@pytest.fixture(params=sorted(FAMILIES))
def engine(request):
    if request.param not in _ENGINES:
        _ENGINES[request.param] = FAMILIES[request.param]()
    return _ENGINES[request.param]


def chunk_args(engine, seed=0):
    """A second chunk of a prompt (offset 32, so it attends over, and
    scans on from, a first one), 21 real tokens in a bucket of 32."""
    vocab = engine.config.model.vocab_size
    rng = np.random.default_rng(seed)
    first = rng.integers(1, vocab, size=(1, BUCKET)).astype(np.int32)
    tokens = np.zeros((1, BUCKET), np.int32)
    tokens[0, :TAKE] = rng.integers(1, vocab, size=TAKE)
    positions = np.arange(BUCKET, dtype=np.int32)[None]
    full = () if engine.state is None else (jnp.int32(BUCKET),)
    valid = () if engine.state is None else (jnp.int32(TAKE),)
    with engine._mesh_scope():
        _, staged = engine._chunk_prefill(
            engine.params, jnp.asarray(first), jnp.asarray(positions),
            engine._dense_zero_caches(), jnp.int32(0), *full, jnp.int32(-1))
    return (engine.params, jnp.asarray(tokens),
            jnp.asarray(positions + BUCKET)), (jnp.int32(BUCKET), *valid), \
        staged


def assert_same_staging(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def run_chunk(engine, *last):
    """The chunk of `chunk_args` (its staging is donated: made anew)."""
    head, tail, staged = chunk_args(engine)
    with engine._mesh_scope():
        return engine._chunk_prefill(*head, staged, *tail, *last)


@pytest.mark.parametrize("row", [0, 9, TAKE - 1])
def test_one_row_of_the_head_is_that_row_of_all_positions(engine, row):
    """`last=r`: row r of what the old call (no `last`) returns for every
    position, at the first, a middle and the last real row of a padded
    bucket; and the staging handed on is the same either way."""
    everywhere, staged_all = run_chunk(engine)
    one, staged_one = run_chunk(engine, jnp.int32(row))
    vocab = engine.config.model.vocab_size
    assert everywhere.shape == (1, BUCKET, vocab)
    assert one.shape == (1, vocab)
    assert one.dtype == everywhere.dtype == jnp.float32
    assert np.ptp(np.asarray(one)) > 0
    np.testing.assert_allclose(np.asarray(one)[0],
                               np.asarray(everywhere)[0, row],
                               rtol=0, atol=1e-5)
    assert_same_staging(staged_one, staged_all)


def test_a_chunk_that_finishes_nothing_meets_no_head(engine):
    """`last=-1`: zeros, the same staging, and ONE compiled program for
    both answers whose text names no array of [chunk, vocab] nor
    [1, chunk, vocab] (the all-positions form, compiled beside it, names
    both: the helper can tell)."""
    vocab = engine.config.model.vocab_size
    none, staged_none = run_chunk(engine, jnp.int32(-1))
    _, staged_all = run_chunk(engine)
    assert none.shape == (1, vocab) and not np.asarray(none).any()
    assert_same_staging(staged_none, staged_all)
    head, tail, staged = chunk_args(engine)
    with engine._mesh_scope():
        lowered = engine._chunk_prefill.lower(*head, staged, *tail,
                                              jnp.int32(-1))
        everywhere = engine._chunk_prefill.lower(*head, staged, *tail)
    text = lowered.compile().as_text()
    for shape in ((BUCKET, vocab), (1, BUCKET, vocab)):
        assert array_shapes(text, shape) == 0
    assert array_shapes(everywhere.compile().as_text(),
                        (1, BUCKET, vocab)) > 0
    # the head is still in the program, under the branch that takes a row
    assert array_shapes(text, (1, vocab)) > 0


# -- through the tick --------------------------------------------------------

PROMPTS = {"one_chunk": 11, "whole_buckets": 64, "padded_tail": 77}


@pytest.fixture(scope="module")
def paged():
    return llama_engine()


@pytest.mark.parametrize("kind", sorted(PROMPTS))
def test_greedy_outputs_equal_the_plain_model(paged, kind):
    """A prompt of one chunk, one that is an exact multiple of the largest
    bucket (its last row is its last chunk's last), and one of several
    chunks with a padded tail: the tokens the no-cache forward gives."""
    length = PROMPTS[kind]
    prompts = [np.random.default_rng(seed).integers(
        1, 256, size=length).tolist() for seed in (length, length + 1)]
    before = paged.stats()
    assert paged.generate(prompts, max_new_tokens=6) == plain_greedy(
        model_forward(paged.model, paged.params), prompts, 6)
    after = paged.stats()
    chunks = -(-length // 32) * len(prompts)
    assert after["prefill_chunks"] - before["prefill_chunks"] == chunks
    assert after["prefill_heads"] - before["prefill_heads"] == len(prompts)


@pytest.mark.parametrize("family", ["falcon_h1", "nemotron_h"])
def test_hybrid_first_tokens_are_the_all_positions_argmax(family):
    """The two hybrid families through the tick: the first token of a
    prompt of several chunks with a padded tail is the argmax of the
    no-cache forward's last row."""
    if family not in _ENGINES:
        _ENGINES[family] = FAMILIES[family]()
    engine = _ENGINES[family]
    vocab = engine.config.model.vocab_size
    prompts = [np.random.default_rng(seed).integers(
        1, vocab, size=n).tolist() for seed, n in ((3, 77), (4, 32))]
    got = engine.generate(prompts, max_new_tokens=1)
    for prompt, tokens in zip(prompts, got):
        logits = engine.model.apply({"params": engine.params},
                                    jnp.asarray([prompt]))
        assert tokens == [int(np.asarray(logits)[0, -1].argmax())]


def test_heads_are_counted_with_the_chunks():
    """After a multi-chunk run: a head a finished prompt, fewer heads than
    chunks, in `stats()` (the `tick` row's: test_tick_phases)."""
    engine = _ENGINES.get("llama") or llama_engine()
    before = engine.stats()
    prompts = [np.random.default_rng(n).integers(1, 256, size=n).tolist()
               for n in (70, 40, 9)]       # 3, 2 and 1 chunks of <= 32
    engine.generate(prompts, max_new_tokens=3)
    stats = engine.stats()
    heads = stats["prefill_heads"] - before["prefill_heads"]
    assert heads == stats["prompts_finished"] - before["prompts_finished"] \
        == 3
    assert stats["prefill_chunks"] - before["prefill_chunks"] == 3 + 2 + 1
    assert stats["prefill_heads"] < stats["prefill_chunks"]
