"""Paged-KV engine: equivalence vs the plain forward, page-bounded HBM,
prefix sharing, and continuous-batching behavior under pressure
(VERDICT r2 item 5; reference: vLLM PagedAttention as delegated by
llm/_internal/serve/deployments/llm/vllm/, prefix reuse a la
serve/request_router/).
"""

from __future__ import annotations

import numpy as np
import pytest

from plain_greedy import model_forward, plain_greedy
from ray_tpu.llm import (GenerationRequest, PagedEngineConfig,
                         PagedLLMEngine)
from ray_tpu.models.llama import LlamaConfig


def tiny_model():
    return LlamaConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=4, max_seq_len=256, remat=False,
                       use_flash=False, attention_impl="reference")


@pytest.fixture(scope="module")
def paged():
    return PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=4, max_len=128, page_size=8,
        num_pages=128, prefill_buckets=(16, 32, 64)))


@pytest.mark.parametrize("seed,n_prompts,max_new", [
    (0, 16, 12),  # 4x max_batch of 4
    (8, 12, 10),  # the mix the legacy scheduler was held to (PR 17)
])
def test_greedy_equivalence_under_load(paged, seed, n_prompts, max_new):
    """Identical outputs vs the no-cache forward with queue depth 3-4x
    max_batch (the VERDICT's acceptance bar)."""
    rng = np.random.RandomState(seed)
    prompts = [list(rng.randint(1, 128, size=rng.randint(4, 30)))
               for _ in range(n_prompts)]
    want = plain_greedy(model_forward(paged.model, paged.params), prompts,
                        max_new)
    assert paged.generate(prompts, max_new_tokens=max_new) == want


def test_hbm_scales_with_pages_not_max_len():
    """Pool bytes are num_pages x page_size, independent of
    max_len x max_batch (a dense cache's footprint: a row a request, each
    max_len long)."""
    model = tiny_model()
    paged = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=8, max_len=128, page_size=8, num_pages=32,
        prefill_buckets=(16,)))
    paged_bytes = paged.stats()["hbm_cache_bytes"]
    dense_bytes = 2 * model.num_layers * 8 * model.num_kv_heads * 128 \
        * model.head_dim_ * np.dtype(model.dtype).itemsize
    # 32 pages x 8 tokens = 256 cached tokens vs 8 rows x 128 = 1024
    assert paged_bytes * 3 < dense_bytes
    # and the engine still completes work under that budget
    out = paged.generate([[1, 2, 3, 4]] * 12, max_new_tokens=4)
    assert len(out) == 12


def test_prefix_pages_shared():
    model = tiny_model()
    paged = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=128, page_size=8,
        num_pages=128, prefill_buckets=(32, 64)))
    shared_prefix = list(range(1, 25))  # 24 tokens = 3 full pages
    free0 = paged.pool.num_free()
    out1 = paged.generate([shared_prefix + [30]], max_new_tokens=4)
    used_after_one = free0 - paged.pool.num_free()
    out2 = paged.generate([shared_prefix + [31]], max_new_tokens=4)
    used_after_two = free0 - paged.pool.num_free()
    assert len(out1[0]) == 4 and len(out2[0]) == 4
    # the second request reuses the 3 shared prefix pages: its net new
    # page usage must be smaller than the first request's
    assert used_after_two - used_after_one < used_after_one
    assert paged.stats()["prefix_entries"] >= 3


def test_prefix_hit_refreshes_recency_and_counts():
    """A reused prefix must not age out of the radix while hot, and
    stats() exposes the hit/miss counters: the engine's own admission
    path, where test_radix_lru_evicts_only_unreferenced_leaves drives
    the tree alone."""
    model = tiny_model()
    paged = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=128, page_size=8,
        num_pages=128, prefill_buckets=(32, 64)))
    hot = list(range(1, 17))  # 16 tokens = 2 full pages
    paged.generate([hot + [30]], max_new_tokens=2)
    s0 = paged.stats()
    assert s0["prefix_misses"] >= 1 and s0["prefix_hits"] == 0
    assert "continuous" not in s0  # one scheduler: nothing to report
    hot_pages = paged.prefix_pinned_pages()  # nothing else cached yet
    assert len(hot_pages) == 2
    # a few distinct filler prefixes inserted AFTER the hot one
    rng = np.random.RandomState(7)
    filler = [list(rng.randint(40, 128, size=16)) + [i + 1]
              for i in range(4)]
    paged.generate(filler, max_new_tokens=2)
    # hit the hot prefix through admission: its nodes become the newest
    paged.generate([hot + [31]], max_new_tokens=2)
    s1 = paged.stats()
    assert s1["prefix_hits"] == 1
    assert s1["prefix_misses"] > s0["prefix_misses"]  # fillers missed
    assert s1["prefix_entries"] == 10
    # evict down to 2 entries: insertion order would keep only the
    # newest fillers; LRU keeps the hot nodes (just refreshed)
    paged._evict_prefixes(max_entries=2)
    assert paged.stats()["prefix_entries"] == 2
    assert paged.prefix_pinned_pages() == hot_pages, \
        "hot prefix evicted despite being reused (recency not refreshed)"
    assert paged.page_leak_check() == 0


def _series_value(metric, tags):
    snap = metric.snapshot()
    key = [tags.get(k, "") for k in snap["tag_keys"]]
    for tag_values, value in snap["series"]:
        if tag_values == key:
            return value
    return 0.0


def test_prefix_cache_metrics_exposition():
    """prefix_hits/prefix_misses/LRU occupancy (previously stats()-only)
    export as rtpu_prefix_cache_* series through the Prometheus
    exposition pipeline. Counters are process-global, so the assertions
    are deltas against this engine instance's own stats()."""
    import os

    from ray_tpu.llm._metrics import llm_metrics
    from ray_tpu.util.metrics import prometheus_text

    m = llm_metrics()
    tags = {"engine": "paged"}
    hits0 = _series_value(m.prefix_hits, tags)
    miss0 = _series_value(m.prefix_misses, tags)

    paged = PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=4, max_len=128, page_size=8,
        num_pages=128, prefill_buckets=(32, 64)))
    hot = list(range(1, 17))  # 16 tokens = 2 full pages
    paged.generate([hot + [30]], max_new_tokens=2)   # miss
    paged.generate([hot + [31]], max_new_tokens=2)   # hit
    s = paged.stats()
    assert s["prefix_hits"] == 1 and s["prefix_misses"] >= 1
    assert _series_value(m.prefix_hits, tags) - hits0 \
        == s["prefix_hits"]
    assert _series_value(m.prefix_misses, tags) - miss0 \
        == s["prefix_misses"]
    gauge_tags = {"engine": "paged", "pid": str(os.getpid())}
    assert _series_value(m.prefix_entries, gauge_tags) \
        == paged.stats()["prefix_entries"] > 0

    text = prometheus_text([m.prefix_hits.snapshot(),
                            m.prefix_misses.snapshot(),
                            m.prefix_entries.snapshot()])
    assert "# TYPE rtpu_prefix_cache_hits_total counter" in text
    assert "# TYPE rtpu_prefix_cache_misses_total counter" in text
    assert "# TYPE rtpu_prefix_cache_entries gauge" in text
    assert 'rtpu_prefix_cache_hits_total{engine="paged"}' in text
    assert ('rtpu_prefix_cache_entries{engine="paged",'
            f'pid="{os.getpid()}"}}') in text


def test_streaming_and_cancellation(paged):
    streamed = []
    done = []

    def on_token(request, token):
        streamed.append((request.request_id, token))

    def on_done(request, tokens):
        done.append((request.request_id, tokens))

    long_req = GenerationRequest(prompt_tokens=[1, 2, 3],
                                 max_new_tokens=64, request_id="victim")
    short_req = GenerationRequest(prompt_tokens=[4, 5, 6],
                                  max_new_tokens=6, request_id="short")
    paged.submit(long_req, done_callback=on_done, token_callback=on_token)
    paged.submit(short_req, done_callback=on_done, token_callback=on_token)
    free_before = paged.pool.num_free()
    for _ in range(4):
        paged.step()
    assert paged.cancel("victim") is True
    for _ in range(30):
        if not paged.has_work():
            break
        paged.step()
    ids_done = dict(done)
    assert ids_done["victim"] is None          # cancelled marker
    assert len(ids_done["short"]) == 6         # unaffected neighbor
    # victim streamed a few tokens before dying, then stopped
    victim_tokens = [t for rid, t in streamed if rid == "victim"]
    assert 1 <= len(victim_tokens) < 64
    assert paged.pool.num_free() >= free_before  # pages reclaimed


def test_queue_pressure_admission_bounded_by_pages():
    """Queue depth beyond the page budget: requests wait, none is lost,
    all finish."""
    model = tiny_model()
    paged = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=4, max_len=64, page_size=8, num_pages=16,
        prefill_buckets=(16,)))
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, 128, size=8)) for _ in range(10)]
    out = paged.generate(prompts, max_new_tokens=8, timeout_s=300)
    assert len(out) == 10
    assert all(len(o) == 8 for o in out)
    assert paged.pool.num_free() >= 16 - 1 - 10  # prefix entries may pin


# ---------------------------------------------------------------------------
# the decode token's write into the page pool
# ---------------------------------------------------------------------------

_PS, _PAGES, _HD = 8, 12, 16   # page size, pages (page 0 the null page)

# lengths per batch row, block tables per batch row. A row that is not
# decoding has length 0 and a table of null pages.
_WRITE_CASES = {
    "offset_0": ([0, 16], [[3, 4, 5], [6, 7, 8]]),
    "offset_last": ([_PS - 1, 2 * _PS - 1], [[3, 4, 5], [6, 7, 8]]),
    "fresh_page": ([_PS, 2 * _PS], [[3, 9, 0], [6, 7, 11]]),
    "null_page_collision": ([5, 0, 0], [[2, 0, 0], [0, 0, 0], [0, 0, 0]]),
}


def _numpy_write(pool, rows, tables, lengths):
    """rows[h, b] to pool[h, page, offset], one element of the index at
    a time; returns the pool and, per target, the batch rows aimed at it."""
    out = pool.copy()
    writers = {}
    for b, n in enumerate(lengths):
        target = (tables[b][n // _PS], n % _PS)
        writers.setdefault(target, []).append(b)
        for h in range(pool.shape[0]):
            out[(h,) + target] = rows[h, b]
    return out, writers


def _assert_written(got, pool, rows, tables, lengths):
    want, writers = _numpy_write(pool, rows, tables, lengths)
    for (page, off), batch in writers.items():
        if len(batch) > 1:
            # colliding rows: any one of them may land, whole
            for h in range(pool.shape[0]):
                assert any(np.array_equal(got[h, page, off], rows[h, b])
                           for b in batch)
            want[:, page, off] = got[:, page, off]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kv_heads", [8, 4])
@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_decode_write_equals_numpy_write(case, kv_heads):
    """`write_token_rows` against an index-by-index numpy write of the
    same rows into a random bf16 pool: the rows land at (kv head,
    table[len // ps], len % ps) and no other element changes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import write_token_rows
    lengths, tables = _WRITE_CASES[case]
    rng = np.random.default_rng(len(case) + kv_heads)
    pool = np.asarray(jnp.asarray(
        rng.standard_normal((kv_heads, _PAGES, _PS, _HD)), jnp.bfloat16))
    rows = np.asarray(jnp.asarray(
        rng.standard_normal((kv_heads, len(lengths), _HD)), jnp.bfloat16))
    got = jax.jit(write_token_rows)(
        pool, rows, jnp.asarray(tables, jnp.int32),
        jnp.asarray(lengths, jnp.int32))
    assert got.dtype == jnp.bfloat16
    _assert_written(np.asarray(got), pool, rows, tables, lengths)


@pytest.mark.parametrize("kv_heads", [8, 4])
def test_paged_branch_writes_its_k_and_v_rows(kv_heads):
    """Through the model's own paged-decode branch (32 query heads on 8
    and on 4 kv heads): the K pool takes the rotated key rows and the V
    pool the value rows, at the same places, and nothing else moves."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (Attention, apply_rope,
                                      rope_frequencies)
    from ray_tpu.parallel.mesh import unbox
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, num_heads=32,
                      num_kv_heads=kv_heads, head_dim=_HD, max_seq_len=64,
                      dtype=jnp.float32, remat=False, use_flash=False,
                      attention_impl="reference")
    lengths, tables = [_PS - 1, _PS, 0, 0], \
        [[3, 4, 5], [6, 7, 8], [0, 0, 0], [0, 0, 0]]
    rng = np.random.default_rng(kv_heads)
    x = jnp.asarray(rng.standard_normal((4, 1, 64)), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32)[:, None]
    layer = Attention(cfg)
    params = unbox(layer.init(jax.random.PRNGKey(0), x, positions)["params"])
    pools = {n: rng.standard_normal(
        (kv_heads, _PAGES, _PS, _HD)).astype(np.float32) for n in "kv"}
    cache = {"k": jnp.asarray(pools["k"]), "v": jnp.asarray(pools["v"]),
             "block_tables": jnp.asarray(tables, jnp.int32),
             "lengths": jnp.asarray(lengths, jnp.int32)}
    _, new = layer.apply({"params": params}, x, positions, cache, None)

    def rows(name, rotate):
        r = jnp.einsum("bsd,dhk->bhsk", x, params[name]["kernel"])
        if rotate:
            r = apply_rope(r, *rope_frequencies(_HD, cfg.max_seq_len,
                                                cfg.rope_theta), positions)
        return np.asarray(jnp.transpose(r[:, :, 0, :], (1, 0, 2)))

    for name, proj in (("k", "k_proj"), ("v", "v_proj")):
        got = np.asarray(new[name])
        want, _ = _numpy_write(pools[name], rows(proj, name == "k"),
                               tables, lengths)
        untouched = want == pools[name]
        np.testing.assert_array_equal(got[untouched],
                                      pools[name][untouched])
        want[:, 0, 0] = got[:, 0, 0]     # two idle rows collide there
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert (got[:, 3, _PS - 1] != pools[name][:, 3, _PS - 1]).all()
        assert (got[:, 7, 0] != pools[name][:, 7, 0]).all()


def test_decode_program_scatters_by_head_page_offset(paged):
    """The engine's decode step, lowered from shapes: every scatter into
    a page pool indexes kv head, page and offset, so its window is
    head_dim alone. Indexed by (page, offset) with the kv heads in the
    window, XLA:TPU relays every layer's whole pool out and back around
    the scatter each tick (PERF.md, PR 29)."""
    import re
    text = paged.lower_decode().as_text()
    pool = "x".join(map(str, paged.k_pages[0].shape)) + "xbf16"
    scatters = re.findall(
        r'"stablehlo\.scatter".*?scatter_dimension_numbers = '
        r'#stablehlo\.scatter<([^>]*)>.*?-> tensor<([^>]*)>', text, re.S)
    into_pool = [dims for dims, result in scatters if result == pool]
    assert len(into_pool) == 2 * paged.config.model.num_layers
    for dims in into_pool:
        assert "inserted_window_dims = [0, 1, 2]" in dims
        assert "update_window_dims = [2]" in dims


@pytest.mark.parametrize("what", ["token_vector", "pool_copies",
                                  "first_token"])
def test_decode_program_runs_one_step_ahead(paged, what):
    """The decode step of PR 33: its sampled tokens are a [rows] int32
    vector that is the next call's token input as it stands (no trip
    through the host), the pools are still donated and copied nowhere,
    and a prompt's first token is written into that vector on the
    device."""
    import re
    rows = paged.config.max_batch
    lowered = paged.lower_decode()
    if what == "token_vector":
        text = lowered.as_text()
        main = text[text.index("func.func public @main"):]
        head = main[:main.index("{\n")]
        args, results = head.split("->", 1)
        vector = f"tensor<{rows}xi32>"
        # block tables are [rows, pages]: lengths, tokens, top_k are the
        # three [rows] int32 arguments; the first result is the vector
        assert len(re.findall(re.escape(vector), args)) == 3
        assert results.lstrip(" (").startswith(vector)
        assert f"tensor<{rows}x1xi32>" not in args
    elif what == "pool_copies":
        compiled = lowered.compile()
        assert paged.pool_copies(compiled.as_text()) == 0
        pool = paged.k_pages[0]
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            2 * paged.config.model.num_layers * pool.size \
            * pool.dtype.itemsize
    else:
        import jax.numpy as jnp
        from ray_tpu.llm.paged import first_token
        # the one row of logits a chunk that finishes a prompt returns
        # (`last` names the row)
        tokens = np.arange(1, 17, dtype=np.int32)[None]
        with paged._mesh_scope():
            row, _caches = paged._chunk_prefill(
                paged.params, jnp.asarray(tokens), jnp.asarray(tokens - 1),
                paged._dense_zero_caches(), jnp.int32(0), jnp.int32(4))
        assert row.shape == (1, paged.config.model.vocab_size)
        assert row.dtype == jnp.float32
        want = np.arange(rows)
        want[2] = int(np.argmax(np.asarray(row)[0]))
        assert np.ptp(np.asarray(row)) > 0
        # greedy, and a sampler whose top_k leaves one token to draw
        for temperature, top_k in ((0.0, 0), (2.0, 1)):
            out = first_token(
                np.arange(rows, dtype=np.int32),
                row, np.int32(2), paged._rng,
                np.full((1,), temperature, np.float32),
                np.full((1,), top_k, np.int32), np.ones((1,), np.float32),
                sampled=temperature > 0)
            assert np.asarray(out).tolist() == want.tolist()


# -- the engine's per-layer-kind contract changes no program that did not
# -- ask for it ---------------------------------------------------------------

def lowered_programs(engine):
    """StableHLO text of an engine's decode step and of its largest
    prefill chunk, lowered from shapes."""
    import jax
    import jax.numpy as jnp
    cfg = engine.config
    staged = jax.eval_shape(engine._dense_zero_caches)
    bucket = cfg.prefill_buckets[-1]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    valid = () if engine.state is None else (i32(),)
    return {"decode_step": engine.lower_decode().as_text(),
            "chunk_prefill": engine._chunk_prefill.lower(
                engine.params, i32(1, bucket), i32(1, bucket), staged,
                i32(), *valid).as_text()}


def program_hash(text: str) -> str:
    import hashlib
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# recorded from the commit before the engine learned of layer kinds (PR 33's
# tree, 2c580e1), by this very function at these very sizes
# `decode_step` again in PR 43 (was 618df5990d42e21b): `sample_tokens`, the
# step's last call, is now a switch over three branches (argmax / draw /
# sort and draw) where it was the third alone; `chunk_prefill` calls no
# sampler and reads as before. `chunk_prefill` again in PR 61 (was
# b087c299668649d9): the chunk attends its dense cache through
# `ops.attention.attend_cache`, here the loop over the filled blocks
DENSE_PROGRAMS = {"decode_step": "315a3b48a436eb30",
                  "chunk_prefill": "e469d5381fef3f10"}


@pytest.mark.parametrize("program", sorted(DENSE_PROGRAMS))
def test_dense_engine_lowers_to_the_program_it_always_did(program):
    """A LlamaConfig engine's programs are, text for text, what they were
    before `PagedEngineConfig.model` could say what each layer keeps. A
    change that means to alter the dense programs re-records the hash."""
    engine = PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=4, max_len=96, page_size=8,
        num_pages=64, prefill_buckets=(16, 32)))
    assert program_hash(lowered_programs(engine)[program]) \
        == DENSE_PROGRAMS[program]
