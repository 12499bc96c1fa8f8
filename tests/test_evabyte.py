"""EVA attention on the serve path: the model (`ray_tpu/models/evabyte.py`)
against the plain float32 reference (benchmarks/reference/evabyte_ref.py)
over whole sequences, over chunked prefill through the page pool and paged
decode across a window's close; the page ledger of rows whose pages leave
them while they live; what the engine refuses for such a model; and the
lowered programs. Toy widths in the published ratios: one query a kv head,
eight prediction heads, unit-offset norms, W / C = 8 with a window of two
of the largest bucket and two pages of summaries."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import parity_evabyte
from benchmarks.reference import evabyte_ref
from plain_greedy import plain_greedy
from ray_tpu.llm import GenerationRequest
from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine
from ray_tpu.models.evabyte import EvaByteConfig

WINDOW, CHUNK, PAGE = 32, 4, 4
TINY = EvaByteConfig(
    vocab_size=320, hidden_size=64, intermediate_size=172, num_layers=2,
    num_heads=4, head_dim=16, num_pred_heads=8, window_size=WINDOW,
    chunk_size=CHUNK, dtype=jnp.float32, param_dtype=jnp.float32,
    attention_impl="reference", max_seq_len=512)
KEYS = parity_evabyte.reference_keys(TINY)


def tiny_engine(params=None, model=TINY, **over):
    kw = dict(model=model, max_batch=3, max_len=200, page_size=PAGE,
              num_pages=128, prefill_buckets=(8, 16))
    kw.update(over)
    return PagedLLMEngine(PagedEngineConfig(**kw), params=params)


@pytest.fixture(scope="module")
def engine():
    """One engine whose weights the others share; its norm offsets are
    moved off zero so that the unit offset is seen."""
    eng = tiny_engine()
    eng.params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 if path[-1].key == "scale" else a,
        eng.params)
    return eng


def prompt_of(seed, n):
    return np.random.default_rng(seed).integers(1, 320, size=n).tolist()


PLAIN_LENGTH = 128


@jax.jit
def _plain_logits(params, tokens):
    return TINY.module().apply({"params": params}, tokens)


def _plain_greedy(engine, prompt, new):
    """The whole-sequence model, nothing cached, token by token, at one
    length for every call of the file (one compile)."""
    return plain_greedy(functools.partial(_plain_logits, engine.params),
                        [prompt], new, length=PLAIN_LENGTH)[0]


# -- the model against the reference ------------------------------------------

def test_whole_sequence_matches_the_reference_over_three_windows(engine):
    tokens = np.asarray(prompt_of(1, 3 * WINDOW + 11))
    got = engine.model.apply({"params": engine.params},
                             jnp.asarray(tokens[None]), head="all")
    want = evabyte_ref.logits(engine.params, tokens, KEYS, num_layers=2)
    assert got.shape == (1, tokens.size, 8, 320) and got.dtype == jnp.float32
    assert float(jnp.abs(got[0] - want).max()) < 2e-5 * float(want.std())
    # head 0 is what the engine's steps multiply and sample
    only = engine.model.apply({"params": engine.params},
                              jnp.asarray(tokens[None]))
    np.testing.assert_allclose(only, got[:, :, 0], atol=1e-6)


def test_the_reference_masks_are_the_displayed_sum(engine):
    """A query sees its own window exactly and one summary a chunk of the
    windows before it: moving a byte of an earlier window moves a later
    window's logits (through a summary), moving a later byte moves
    nothing before it, and dropping the sum over c changes every position
    past the first window and none inside it."""
    tokens = np.asarray(prompt_of(2, 2 * WINDOW + 9))
    ref = lambda t, **kw: np.asarray(evabyte_ref.logits(  # noqa: E731
        engine.params, t, KEYS, num_layers=2, **kw))
    base = ref(tokens)
    early = tokens.copy()
    early[3] = (early[3] + 7) % 320
    assert np.abs(ref(early)[2 * WINDOW:] - base[2 * WINDOW:]).max() > 1e-4
    late = tokens.copy()
    late[-1] = (late[-1] + 7) % 320
    assert np.abs(ref(late)[:-1] - base[:-1]).max() == 0.0
    bare = ref(tokens, with_summaries=False)
    assert np.abs(bare[:WINDOW] - base[:WINDOW]).max() == 0.0
    assert np.abs(bare[WINDOW:] - base[WINDOW:]).min(axis=(1, 2)).max() > 0


def test_pooling_weights_are_neither_uniform_nor_one_hot(engine):
    """phi at its assumed scale (N(0, 1) clipped to [-1, 1]): the entropy
    of a chunk's pooling weights lies strictly between a mean-pool's
    ln C and a single position's 0."""
    tokens = np.asarray(prompt_of(3, WINDOW))
    _, details = evabyte_ref.logits(engine.params, tokens, KEYS,
                                    num_layers=2, details=True)
    attn = engine.params["layer_0"]["attn"]
    assert float(jnp.abs(attn["phi"]).max()) <= 1.0
    assert float(jnp.abs(attn["mu"]).max()) <= 1.0
    assert float(jnp.std(attn["phi"])) > 0.3
    pooled_k = details[0]["summaries"][0]
    assert pooled_k.shape == (WINDOW // CHUNK, 4, 16)
    for layer in details:
        assert 0.3 * np.log(CHUNK) < float(layer["pool_entropy"]) \
            < 0.999 * np.log(CHUNK)


# -- prefill over pages and decode across a close, against the reference ------

@pytest.mark.parametrize("n_prompt,ticks", [
    (50, 16),            # a padded last chunk; a close mid-answer
    (WINDOW, 6),         # a prompt that ends exactly on a window boundary
    (2 * WINDOW + 5, 4),  # one that closes two windows
])
def test_prefill_over_pages_and_decode_match_the_reference(
        engine, n_prompt, ticks):
    eng = tiny_engine(params=engine.params)
    free = eng.pool.num_free()
    prompt = np.asarray(prompt_of(10 + n_prompt, n_prompt))
    run = parity_evabyte.engine_run(eng, prompt, 16, ticks)
    sequence = np.concatenate([prompt, np.asarray(run["fed"])])
    want, details = evabyte_ref.logits(engine.params, sequence, KEYS,
                                       num_layers=2, details=True)
    want = np.asarray(want[:, 0])
    spread = float(want.std())
    assert np.abs(run["prefill_logits"] - want[:n_prompt]).max() \
        < 3e-5 * spread
    assert np.abs(run["decode_logits"] - want[n_prompt:]).max() \
        < 3e-5 * spread
    closes = [made["position"] for made in run["closes"]]
    assert closes == [at for at in range(WINDOW, n_prompt + ticks + 1,
                                         WINDOW)]
    errors = parity_evabyte.summary_errors(run["closes"], details, WINDOW,
                                           CHUNK)
    assert errors and max(max(layers) for layers in errors) < 1e-5
    attended = parity_evabyte.attended_errors(run["attended"],
                                              details[0]["attended"])
    assert max(attended.values()) < 1e-5
    assert eng.pool.num_free() == free and eng.page_leak_check() == 0


def test_parity_holds_and_refuses_both_controls(engine):
    """The cell's own check on the toy engine, with the limits the chip is
    held to: the program passes; the reference without the sum over c
    fails the attention limit at both closes; pooling in bf16 where the
    file states float32 fails the summary limit."""
    eng = tiny_engine(params=engine.params)
    out = parity_evabyte.serve(eng, {}, seed=2 ** 31 + 5)
    assert out["ok"], out
    assert out["closes"] == [WINDOW, 2 * WINDOW]
    assert max(out["attended"]["by_position"].values()) < 1e-5
    control = out["attended"]["control_no_summaries"]
    assert len(control) == 2
    assert min(control.values()) > 10 * parity_evabyte.ATTENDED_TOLERANCE
    low = tiny_engine(params=engine.params, model=dataclasses.replace(
        TINY, pool_dtype=jnp.bfloat16))
    out = parity_evabyte.serve(low, {}, seed=2 ** 31 + 5)
    assert not out["ok"]
    assert max(out["summaries"]["pooling_by_close"]) \
        > parity_evabyte.POOLING_TOLERANCE


# -- the engine: rows whose pages leave them while they live ------------------

def test_greedy_outputs_equal_the_plain_model_when_windows_close_apart(
        engine):
    """A batch whose rows close windows on different ticks, in prefill
    and in decode, with more requests than rows."""
    eng = tiny_engine(params=engine.params)
    prompts = [prompt_of(20 + i, n) for i, n in
               enumerate((70, 32, 13, 64, 45))]
    outs = eng.generate(prompts, max_new_tokens=40)
    for prompt, out in zip(prompts, outs):
        assert out == _plain_greedy(engine, prompt, 40)
    stats = eng.stats()
    assert stats["window_closes_prefill"] == sum(
        len(p) // WINDOW for p in prompts)
    assert stats["window_closes_decode"] == sum(
        (len(p) + 39) // WINDOW - len(p) // WINDOW for p in prompts)
    closes = stats["window_closes_prefill"] + stats["window_closes_decode"]
    assert stats["pages_released"] == closes * (WINDOW - WINDOW // CHUNK) \
        // PAGE
    # from lengths alone: the decode steps' rows of each kind (each row's
    # first token comes from its prefill)
    lengths = [len(p) + i for p in prompts for i in range(39)]
    assert stats["summary_rows"] == sum(
        n // WINDOW * (WINDOW // CHUNK) for n in lengths)
    assert stats["window_rows"] == sum(n % WINDOW + 1 for n in lengths)
    assert stats["leaked_pages"] == 0 and stats["preemptions"] == 0
    assert eng.pool.num_free() == 127
    # the step ahead stayed ahead: a close drains nothing
    assert stats["drained_by"] == {"idle": 1}


def test_a_row_holds_what_the_model_says_after_every_step(engine):
    eng = tiny_engine(params=engine.params)
    model = eng.config.model
    done = {}
    for i, (n, new) in enumerate(((50, 30), (31, 40), (64, 5))):
        eng.submit(GenerationRequest(
            prompt_tokens=prompt_of(30 + i, n), max_new_tokens=new,
            request_id=str(i)),
            lambda request, tokens: done.__setitem__(
                request.request_id, tokens))
    while eng.has_work():
        eng.step()
        held = 0
        for seq in eng.seqs:
            if seq.request is None:
                continue
            at = seq.length if seq.phase == "decode" else seq.prefill_off
            assert len(seq.pages) in (model.pages_held(at, PAGE),
                                      model.pages_held(at + 1, PAGE)), at
            assert len(seq.pages) <= eng.config.pages_per_seq
            held += len(seq.pages)
        assert eng.pool.num_free() == 127 - held
        assert eng.page_leak_check() == 0
    assert sorted(done) == ["0", "1", "2"]
    assert eng.pool.num_free() == 127


def test_the_block_table_is_as_wide_as_the_most_a_row_holds():
    cfg = EvaByteConfig()
    # published: 128 window pages + 8 summary pages a closed window
    assert cfg.pages_held(2047, 16) == 128
    assert cfg.pages_held(2048, 16) == 8
    assert cfg.pages_held(5000, 16) == 16 + 57
    assert cfg.prefill_pages(5000, 16) == 8 + 128
    assert cfg.prefill_pages(10496 + 256, 16) == 160  # not 656 + 16
    assert cfg.cache_rows(np.asarray([0, 2047, 2048, 4100])).tolist() \
        == [0, 2047, 128, 260]
    assert [cfg.window_closes(n) for n in (0, 2047, 2048, 4096)] \
        == [False, False, True, True]
    engine_cfg = PagedEngineConfig(model=cfg, max_len=10496, page_size=16,
                                   num_pages=4608)
    assert engine_cfg.pages_per_seq == 160
    with pytest.raises(ValueError, match="do not tile"):
        cfg.check_pages(16, (32, 64, 128, 192))
    with pytest.raises(ValueError, match="do not tile"):
        cfg.check_pages(48, (48, 96))


def test_cancel_and_preemption_keep_the_ledger_and_the_tokens(engine):
    eng = tiny_engine(params=engine.params)
    prompt = prompt_of(41, 40)
    done = {}
    keep = lambda request, tokens: done.__setitem__(  # noqa: E731
        request.request_id, tokens)
    eng.submit(GenerationRequest(prompt_tokens=prompt, max_new_tokens=36,
                                 request_id="a"), keep)
    eng.submit(GenerationRequest(prompt_tokens=prompt_of(42, 70),
                                 max_new_tokens=50, request_id="b"), keep)
    for _ in range(14):
        eng.step()
    assert len(eng.seqs[0].generated) >= 4
    eng._preempt(0, reason="page_pressure")
    assert eng.page_leak_check() == 0
    eng.cancel("b")
    while eng.has_work():
        eng.step()
        assert eng.page_leak_check() == 0
    assert eng.stats()["preemptions"] == 1
    assert done["b"] is None
    # re-prefilled prompt + generated closes the window in PREFILL that
    # the row had closed in decode, and resumes to the same tokens
    assert done["a"] == _plain_greedy(engine, prompt, 36)
    assert eng.pool.num_free() == 127


def test_a_prefill_that_finds_the_pool_short_goes_back_to_the_queue(engine):
    """Pages are taken as the chunks come to them: a row admitted while
    its budget was free, whose neighbours have grown since, parks again
    with nothing held, and ends with the same tokens."""
    eng = tiny_engine(params=engine.params, num_pages=24, max_batch=2)
    done = {}
    keep = lambda request, tokens: done.__setitem__(  # noqa: E731
        request.request_id, tokens)
    first, second = prompt_of(51, 20), prompt_of(52, 30)
    eng.submit(GenerationRequest(prompt_tokens=first, max_new_tokens=20,
                                 request_id="a"), keep)
    eng.submit(GenerationRequest(prompt_tokens=second, max_new_tokens=8,
                                 request_id="b"), keep)
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert eng.page_leak_check() == 0 and steps < 400
    assert done["a"] == _plain_greedy(engine, first, 20)
    assert done["b"] == _plain_greedy(engine, second, 8)
    assert eng.pool.num_free() == 23


def test_prefix_reuse_is_refused_and_counted(engine):
    eng = tiny_engine(params=engine.params)
    shared = prompt_of(61, 40)
    first = eng.generate([shared + prompt_of(62, 5)], max_new_tokens=4)[0]
    again = eng.generate([shared + prompt_of(63, 7)], max_new_tokens=4)[0]
    stats = eng.stats()
    assert stats["prefix_skipped_compressed"] == 2
    assert stats["prefix_skipped_recurrent"] == 0
    assert stats["prefix_hits"] == 0 and stats["prefix_entries"] == 0
    assert eng.radix.shared_pages() == 0
    assert first == _plain_greedy(engine, shared + prompt_of(62, 5), 4)
    assert again == _plain_greedy(engine, shared + prompt_of(63, 7), 4)


def test_what_is_not_built_is_refused_loudly(engine):
    eng = tiny_engine(params=engine.params)
    with pytest.raises(NotImplementedError, match="closed windows"):
        eng.prefill_only(prompt_of(71, 20))
    with pytest.raises(NotImplementedError, match="closed windows"):
        eng.submit_prefilled(GenerationRequest(
            prompt_tokens=prompt_of(72, 20), max_new_tokens=2,
            request_id="x"), [], np.zeros(320))
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "tensor"))
    with pytest.raises(NotImplementedError, match="tensor mesh"):
        PagedLLMEngine(eng.config, mesh=mesh)
    with pytest.raises(ValueError, match="do not tile"):
        tiny_engine(prefill_buckets=(8, 24))


def test_the_tick_row_has_the_compress_phase_and_the_counters(engine):
    from ray_tpu._internal import accel
    eng = tiny_engine(params=engine.params)
    eng.generate([prompt_of(81, 40)], max_new_tokens=30)
    eng.stats()
    tick = next(row for row in accel.step_summary() if row["kind"] == "tick")
    assert tick["phases"]["compress"] > 0.0
    for name in ("window_closes_prefill", "window_closes_decode",
                 "pages_released", "summary_rows", "window_rows",
                 "prefix_skipped_compressed"):
        assert tick["counters"][name] > 0, name


# -- the lowered and compiled programs -------------------------------------

def programs(engine):
    return {"decode_step": engine.lower_decode(),
            "chunk_prefill": engine.lower_chunk(),
            "compress_window": engine.lower_compress()}


def test_no_program_copies_a_pool_and_every_pool_is_donated(engine):
    pool = "x".join(map(str, engine.k_pages[0].shape)) + "xf32"
    for name, lowered in programs(engine).items():
        donated = re.findall(
            rf"tensor<{pool}> \{{[^%]*(?:tf\.aliasing_output|"
            r"jax\.buffer_donor)", lowered.as_text())
        assert len(donated) == 2 * TINY.num_layers, name
        compiled = lowered.compile()
        assert engine.pool_copies(compiled.as_text()) == 0, name
        assert compiled.memory_analysis().alias_size_in_bytes \
            >= engine.stats()["hbm_cache_bytes"], name
    # the chunk is handed no dense cache of a row: pools, a table, scalars
    args = jax.tree_util.tree_leaves(programs(engine)["chunk_prefill"]
                                     .args_info)
    assert not [a for a in args if len(a.shape) == 4
                and a.shape[:2] == (1, TINY.num_heads)]


def test_named_scopes_reach_the_compiled_steps(engine):
    decode = engine.lower_decode().compile().as_text()
    chunk = engine.lower_chunk().compile().as_text()
    compress = engine.lower_compress().compile().as_text()
    for scope in ("eva/qkv", "eva/attend", "mlp"):
        assert scope in decode and scope in chunk, scope
    assert "eva/pool" in compress


# recorded by this very function at these very sizes (PR 42); a change
# that means to alter these programs re-records them. `decode_step` again in
# PR 43 (was ed29019092c0e93a): `sample_tokens` is now a switch over three
# branches where it was the sorting one alone
EVA_PROGRAMS = {"decode_step": "440d3530e50c21bf",
                "chunk_prefill": "ae19d8c4f25ce3fc",
                "compress_window": "bd8cbac8a25691c6"}


@pytest.mark.parametrize("program", sorted(EVA_PROGRAMS))
def test_the_programs_lower_to_what_was_recorded(program):
    from test_llm_paged import program_hash
    eng = tiny_engine()
    assert program_hash(programs(eng)[program].as_text()) \
        == EVA_PROGRAMS[program]
