"""One cache kind, one place (`ray_tpu/llm/kinds.py`): the scheduler in
`ray_tpu/llm/paged.py` names no kind of model, and giving each kind its own
class changed no program and no report. Over the tiny preset of every model
configuration the engine serves (the ones their own test files build): the
StableHLO text of each program hashes as it did at PR 59's tree (6307f01),
recorded there by `program_texts` at these very sizes; `_ahead_counts()` and
`stats()` report the keys they reported there; and every name the benchmark's
harness reaches for on an engine is an attribute of the instance. CPU,
float32, toy widths; nothing is compiled.

PR 61 recorded three hashes anew and no other: the `chunk_prefill` of the
configurations whose chunk attends a dense cache (llama, falcon_h1,
nemotron_h), which now goes through `ops.attention.attend_cache`; and gave
those two kinds the counters `prefill_ctx_rows` and `prefill_cache_rows`."""

import functools
import os
import re
import sys
import types

import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.llm.paged import PagedLLMEngine  # noqa: E402
from test_llm_paged import program_hash  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_of(module, builder="tiny_engine"):
    def build():
        return getattr(__import__(module), builder)()
    return build


# the dense preset `test_paged_staging.py` runs its script on
_llama = _tiny_of("test_paged_staging", "_dense")


# configuration -> (its tiny engine, the kind `kind_of` finds it to be)
CONFIGS = {
    "llama": (_llama, "dense"),
    "falcon_h1": (_tiny_of("test_falcon_h1"), "recurrent"),
    "nemotron_h": (_tiny_of("test_nemotron_h"), "recurrent"),
    "lfm2": (_tiny_of("test_lfm2"), "pooled"),
    "evabyte": (_tiny_of("test_evabyte"), "windowed"),
    "sarvam_mla": (_tiny_of("test_sarvam_mla"), "latent"),
    "xing_mhc": (_tiny_of("test_xing_mhc"), "latent"),
    "keye_dsa": (_tiny_of("test_keye_dsa"), "indexed"),
    "sdar": (_tiny_of("test_sdar"), "blockwise"),
}


@functools.lru_cache(maxsize=None)
def engine_of(name):
    return CONFIGS[name][0]()


def _like(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def program_texts(engine):
    """StableHLO text of an engine's decode step, of its largest prefill
    chunk in the form the tick runs (`last` given; the kinds whose chunk
    takes what `_dense_zero_caches` makes through a lowering of their own,
    as `test_llm_paged.lowered_programs`), and of `compress_window` /
    `write_state` where the engine has them. From shapes alone."""
    cfg = engine.config
    bucket = cfg.prefill_buckets[-1]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    texts = {"decode_step": engine.lower_decode().as_text()}
    if engine.kind in ("dense", "recurrent"):
        staged = jax.eval_shape(engine._dense_zero_caches)
        valid = () if engine.state is None else (i32(),)
        with engine._mesh_scope():
            texts["chunk_prefill"] = engine._chunk_prefill.lower(
                engine.params, i32(1, bucket), i32(1, bucket), staged,
                i32(), *valid, i32()).as_text()
    else:
        texts["chunk_prefill"] = engine.lower_chunk().as_text()
    if hasattr(engine, "_compress_window"):
        texts["compress_window"] = engine.lower_compress().as_text()
    if hasattr(engine, "_write_state"):
        staged = jax.eval_shape(engine._dense_zero_caches)["state"]
        texts["write_state"] = engine._write_state.lower(
            _like(engine.state), staged, i32()).as_text()
    return texts


# the names `benchmarks/harness/*.py` reach for on an engine that every
# engine has (`grep -rhoE "engine\.[A-Za-z_]+" benchmarks/harness/*.py`, less
# `engine.update`, a dict's), and `lower_compress`'s sibling programs
FACADE = (
    "_ahead_counts", "_bucket", "_chunk_prefill", "_decode",
    "_dense_zero_caches", "_gather_pages", "_match_prefix", "_mesh_scope",
    "_paged_kernel", "_prefill_chunk", "_register_prefix", "_rng",
    "_row_pools", "_write_owned_pages", "_write_pages", "config", "counters",
    "decode_program_text", "generate", "has_work", "index_pages", "k_pages",
    "lower_chunk", "lower_decode", "model", "params", "pool", "radix",
    "read_counters", "seqs", "state", "stats", "step", "submit", "v_pages")
# and those only some kinds have: a shim of the harness probes for the first
# two with `hasattr`, and a parity file of a kind calls its kind's helpers
SOME = ("_write_state", "_compress_window", "_by_kind", "_decode_caches",
        "_chunk_caches", "_block_caches")


def record(engine):
    """What this file holds an engine to, as recorded at the parent."""
    ahead = set(engine._ahead_counts())
    (kernel,) = set(engine.stats()) - ahead - set(EVERY_STATS)
    return {"programs": {name: program_hash(text) for name, text
                         in program_texts(engine).items()},
            "ahead": sorted(ahead - set(EVERY_AHEAD)), "kernel": kernel,
            "some": [name for name in SOME if hasattr(engine, name)]}


# recorded at PR 59's tree (6307f01) by `record` over `CONFIGS` (PR 61: the
# dense and the recurrent kinds' chunk and counters, see above): the keys of
# `_ahead_counts()` every engine reports, those `stats()` adds to them on
# every engine, and per configuration its programs' hashes, the keys its
# kind adds to `_ahead_counts()`, the key `stats()` names its kernel by and
# the names of `SOME` it has
EVERY_AHEAD = [
 'decode_rows', 'discarded_tokens', 'drained_ticks', 'lookahead_ticks',
 'prefill_chunks', 'prefill_heads', 'prompts_finished',
 'sampler_filtered_steps', 'sampler_greedy_steps', 'sampler_plain_steps',
 'stage_steps', 'stage_uploads']
EVERY_STATS = [
 'active', 'chunk_expert_pairs', 'chunk_expert_steps', 'drained_by', 'dry',
 'expert_pairs', 'expert_steps', 'free_pages', 'hbm_cache_bytes',
 'hbm_cache_bytes_per_device', 'hbm_param_bytes',
 'hbm_param_bytes_per_device', 'index_cache_bytes', 'layer_kinds',
 'leaked_pages', 'pending', 'preemptions', 'prefix_entries', 'prefix_hits',
 'prefix_misses', 'prefix_skipped_recurrent', 'sampler', 'state_bytes',
 'state_installs', 'steps', 'tokens_generated', 'tp']
RECORDED = {'evabyte': {'ahead': ['pages_released', 'prefix_skipped_compressed',
                       'summary_rows', 'window_closes_decode',
                       'window_closes_prefill', 'window_rows'],
             'kernel': 'paged_kernel',
             'programs': {'chunk_prefill': 'ae19d8c4f25ce3fc',
                          'compress_window': 'bd8cbac8a25691c6',
                          'decode_step': '440d3530e50c21bf'},
             'some': ['_compress_window']},
 'falcon_h1': {'ahead': ['prefill_cache_rows', 'prefill_ctx_rows'],
               'kernel': 'paged_kernel',
               'programs': {'chunk_prefill': '1c33e8e8fc9a123a',
                            'decode_step': '34f2c4ce8d947c2f',
                            'write_state': '7c1c65117e4c0e0a'},
               'some': ['_write_state', '_by_kind', '_decode_caches',
                        '_chunk_caches']},
 'keye_dsa': {'ahead': ['index_pages_distinct', 'index_pages_rowwise',
                        'index_rows_scanned', 'prefill_chunks_sorted',
                        'prefill_computed_tokens', 'prefill_ctx_rows',
                        'prefix_shared_tokens', 'radix_evict_walks',
                        'radix_evictions', 'sparse_rows_context',
                        'sparse_rows_selected'],
              'kernel': 'sparse_kernel',
              'programs': {'chunk_prefill': 'db6d415737bb42dd',
                           'decode_step': 'f65d59d39edbb86c'},
              'some': []},
 'lfm2': {'ahead': ['prefill_chunks_in_place', 'prefill_chunks_sorted',
                    'prefill_computed_tokens', 'prefill_ctx_rows'],
          'kernel': 'paged_kernel',
          'programs': {'chunk_prefill': 'bd17fafeb7e2aa38',
                       'decode_step': 'd8ffe07e4d7ab816',
                       'write_state': '9cac13f65cdc3ba2'},
          'some': ['_write_state', '_by_kind', '_decode_caches',
                   '_chunk_caches']},
 'llama': {'ahead': ['prefill_cache_rows', 'prefill_ctx_rows'],
           'kernel': 'paged_kernel',
           'programs': {'chunk_prefill': 'd105d9660e33eed0',
                        'decode_step': 'd1875a319a18fe89'},
           'some': []},
 'nemotron_h': {'ahead': ['prefill_cache_rows', 'prefill_chunks_sorted',
                          'prefill_ctx_rows'],
                'kernel': 'paged_kernel',
                'programs': {'chunk_prefill': 'c5aa2227a41400e3',
                             'decode_step': '36d4f1ec9cc8d44b',
                             'write_state': 'e85cbd918151c7f2'},
                'some': ['_write_state', '_by_kind', '_decode_caches',
                         '_chunk_caches']},
 'sarvam_mla': {'ahead': ['latent_pages_copied', 'latent_pages_distinct',
                          'latent_pages_rowwise', 'latent_rows_attended',
                          'prefill_chunks_sorted', 'prefill_computed_tokens',
                          'prefill_ctx_rows', 'prefix_shared_tokens',
                          'radix_evict_walks', 'radix_evictions'],
                'kernel': 'latent_kernel',
                'programs': {'chunk_prefill': '0c879c2caf5ad254',
                             'decode_step': '0377369b20ec68dc'},
                'some': []},
 'sdar': {'ahead': ['block_forwards', 'block_tokens_out', 'blocks_early',
                    'commit_forwards', 'prefill_chunks_largest',
                    'prefill_chunks_sorted', 'prefill_computed_tokens',
                    'prefill_ctx_rows', 'prefix_shared_tokens'],
          'kernel': 'paged_kernel',
          'programs': {'chunk_prefill': '75b8220e2c354b31',
                       'decode_step': '051b07c1fcfc3264'},
          'some': ['_by_kind', '_block_caches']},
 'xing_mhc': {'ahead': ['latent_pages_copied', 'latent_pages_distinct',
                        'latent_pages_rowwise', 'latent_rows_attended',
                        'prefill_chunks_sorted', 'prefill_computed_tokens',
                        'prefill_ctx_rows', 'prefix_shared_tokens',
                        'radix_evict_walks', 'radix_evictions'],
              'kernel': 'latent_kernel',
              'programs': {'chunk_prefill': 'ee01fd5e9917525a',
                           'decode_step': 'cdc7c4b75393afa6'},
              'some': []}}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_program_lowers_to_the_text_it_had_before_the_kinds(name):
    """Moving a kind's builder into its class moved no line of any program:
    same function names, same argument trees, same named scopes."""
    got = {program: program_hash(text) for program, text
           in program_texts(engine_of(name)).items()}
    assert got == RECORDED[name]["programs"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_lower_chunk_is_the_chunk_the_tick_runs_for_every_kind(name):
    """`lower_chunk` builds its arguments where the tick builds them
    (`_chunk_args`), so it lowers the dense and the recurrent chunk too,
    which before it could not: to the text their own lowering gives."""
    engine = engine_of(name)
    assert program_hash(engine.lower_chunk().as_text()) \
        == RECORDED[name]["programs"]["chunk_prefill"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_tick_row_counts_what_it_counted(name):
    engine = engine_of(name)
    assert sorted(engine._ahead_counts()) \
        == sorted(EVERY_AHEAD + RECORDED[name]["ahead"])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_stats_reports_the_keys_it_reported(name):
    engine = engine_of(name)
    assert sorted(engine.stats()) == sorted(
        EVERY_AHEAD + RECORDED[name]["ahead"] + EVERY_STATS
        + [RECORDED[name]["kernel"]])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_harness_finds_every_name_it_reaches_for(name):
    """On the INSTANCE, and a kind's own programs and helpers exactly where
    they were: two shims of the harness probe for `_write_state` and
    `_compress_window` with `hasattr`."""
    engine = engine_of(name)
    missing = [attr for attr in FACADE if not hasattr(engine, attr)]
    assert not missing
    assert [attr for attr in SOME if hasattr(engine, attr)] \
        == RECORDED[name]["some"]
    # `_row_pools` is set as well as read, and `_prefill_chunk` replaced on
    # the instance: the tick must find both there at call time
    engine._row_pools = engine._row_pools
    assert "_prefill_chunk" not in vars(engine)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_an_engine_is_of_the_one_kind_its_configuration_answers_to(name):
    from ray_tpu.llm import kinds
    engine = engine_of(name)
    assert kinds.kind_of(engine.config.model) == CONFIGS[name][1]
    assert engine.kind == CONFIGS[name][1]
    assert type(engine) is kinds.ENGINES[engine.kind]
    assert isinstance(engine, PagedLLMEngine)
    assert (type(engine).__doc__ or "").strip()


@pytest.mark.parametrize("name", ["llama", "falcon_h1", "nemotron_h"])
def test_a_dense_cache_chunk_counts_what_it_attended_and_what_it_held(name):
    """`prefill_ctx_rows`: a chunk's cached rows up to its last real token
    (`off + take`, as the kinds that attend pages count it);
    `prefill_cache_rows`: the row's whole dense cache a chunk, which is what
    every chunk attended before `attend_cache`. Their ratio is the share of
    that work that was real; both are on the `tick` row and in `stats()`."""
    engine = CONFIGS[name][0]()
    capacity = engine.config.max_len + engine.config.prefill_buckets[-1]
    assert engine._staged_rows == capacity
    assert jax.eval_shape(engine._dense_zero_caches) is not None
    prompt = [5 + i % 90 for i in range(75)]     # chunks of 32, 32 and 11
    assert engine.generate([prompt], max_new_tokens=2)[0]
    stats = engine.stats()
    assert stats["prefill_chunks"] == 3
    assert stats["prefill_ctx_rows"] == 32 + 64 + 75
    assert stats["prefill_cache_rows"] == 3 * capacity
    assert engine._ahead_counts()["prefill_ctx_rows"] == 32 + 64 + 75


def test_the_prefill_tick_calls_the_chunk_through_the_instance():
    """`parity_sdar` swaps `_prefill_chunk` on the instance after
    construction: the tick must call what it finds there then."""
    engine = _llama()
    calls = []
    chunk = engine._prefill_chunk

    def spy(seq):
        calls.append(seq.prefill_off)
        return chunk(seq)

    engine._prefill_chunk = spy
    assert engine.generate([[5, 6, 7, 8, 9]], max_new_tokens=2)[0]
    assert calls == [0]


@pytest.mark.parametrize("name", ["llama", "falcon_h1", "evabyte",
                                  "sarvam_mla", "sdar"])
def test_an_empty_prompt_is_served_beside_a_live_request(name):
    """`submit` admits a prompt of no token (the HTTP entry refuses only a
    missing one). A kind that samples its first token from a chunk's logits
    runs a chunk of nothing for it, as before the kinds; the blockwise kind
    opens its first block on masks alone. Either way `step` does not raise,
    which would fail every live request of the replica."""
    from ray_tpu.llm.paged import GenerationRequest
    engine = CONFIGS[name][0]()
    done = {}
    for rid, prompt in (("live", [5, 6, 7, 8, 9]), ("empty", [])):
        engine.submit(
            GenerationRequest(prompt_tokens=prompt, max_new_tokens=3,
                              request_id=rid),
            done_callback=lambda request, result: done.__setitem__(
                request.request_id, result))
    for _ in range(200):
        if len(done) == 2:
            break
        engine.step()
    assert sorted(done) == ["empty", "live"]
    assert all(isinstance(tokens, list) and 1 <= len(tokens) <= 3
               for tokens in done.values()), done
    assert engine.page_leak_check() == 0


def test_a_configuration_of_two_kinds_is_refused():
    """Before, the programs of whichever builder ran last were what it got,
    silently."""
    from ray_tpu.llm import kinds
    latent = engine_of("sarvam_mla").config.model
    both = types.SimpleNamespace(
        latent_cache=latent.latent_cache, block_length=4)
    with pytest.raises(NotImplementedError, match="blockwise.*latent"):
        kinds.kind_of(both)
    # a model that lays its own pools is a refinement of one kind alone
    alone = types.SimpleNamespace(page_pool=lambda pages, page_size: ())
    with pytest.raises(NotImplementedError, match="recurrent state"):
        kinds.kind_of(alone)
    assert kinds.kind_of(types.SimpleNamespace()) == "dense"


KIND_FLAG = re.compile(
    r"self\._(windowed|latent|indexed|blockwise|pooled|in_place|recurrent)\b"
    r"|self\.state is|hasattr\(cfg|isinstance\(cfg")


def test_the_scheduler_names_no_kind():
    """`paged.py` asks nowhere which kind of model it serves, so the next
    model configuration cannot put a test back unnoticed; and one function
    of `ray_tpu/llm/` probes a configuration for its kind."""
    llm = os.path.join(REPO, "ray_tpu", "llm")
    with open(os.path.join(llm, "paged.py")) as f:
        source = f.read()
    found = [(source[:m.start()].count("\n") + 1, m.group(0))
             for m in KIND_FLAG.finditer(source)]
    assert not found
    assert len(source.splitlines()) < 2000
    probes = re.compile(
        r"hasattr\(\w+, \"(state_shapes|window_closes|latent_cache|"
        r"index_cache|page_pool|block_length)\"\)")
    probing = []
    for name in sorted(os.listdir(llm)):
        if name.endswith(".py"):
            with open(os.path.join(llm, name)) as f:
                text = f.read()
            probing += [name] * bool(
                probes.search(text) or '_PROBES' in text)
    assert probing == ["kinds.py"]
