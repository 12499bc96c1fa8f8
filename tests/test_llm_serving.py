"""LLM serving vertical: paged engine behind serve, chunked prefill,
streaming, cancellation, prefix routing, OpenAI shapes, PD-disagg
(reference: llm/_internal/serve/builders/application_builders.py,
deployments/prefill_decode_disagg/, request_router/)."""

import asyncio
import json

import numpy as np
import pytest

import ray_tpu
from plain_greedy import model_forward, plain_greedy
from ray_tpu.llm.paged import (GenerationRequest, PagedEngineConfig,
                               PagedLLMEngine)
from ray_tpu.models.llama import LlamaConfig


def tiny_model():
    return LlamaConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=4, max_seq_len=256, remat=False,
                       use_flash=False, attention_impl="reference")


# ---------------------------------------------------------------------------
# engine-level (no cluster)
# ---------------------------------------------------------------------------

@pytest.mark.timeout_s(600)
def test_chunked_prefill_matches_plain_forward():
    """Prompts LONGER than the largest prefill bucket decode identically
    to the no-cache forward (the old 'prompt exceeds the largest prefill
    bucket' rejection is gone — chunked prefill runs to max_len)."""
    paged = PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=2, max_len=160, page_size=8,
        num_pages=128, prefill_buckets=(16, 32)))
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 128, size=n)))
               for n in (5, 40, 100)]
    assert paged.generate(prompts, max_new_tokens=6) == plain_greedy(
        model_forward(paged.model, paged.params), prompts, 6)


@pytest.mark.timeout_s(600)
def test_chunked_prefill_bucket_overrun_regression():
    """The final bucket-rounded chunk may extend past max_len; the dense
    cache must carry slack for it or dynamic_update_slice CLAMPS the
    write and silently corrupts earlier positions (code-review find):
    max_len=96 with bucket 64 and a 90-token prompt writes chunk 2 at
    [64, 128) into what used to be a 96-long cache."""
    paged = PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=1, max_len=96, page_size=8,
        num_pages=64, prefill_buckets=(64,)))
    rng = np.random.default_rng(3)
    prompt = list(map(int, rng.integers(1, 128, size=90)))
    assert paged.generate([prompt], max_new_tokens=4) == plain_greedy(
        model_forward(paged.model, paged.params), [prompt], 4)


@pytest.mark.timeout_s(600)
def test_pd_disagg_matches_local_prefill():
    """prefill_only on one engine + submit_prefilled on another produces
    the same tokens as a single engine doing both."""
    model = tiny_model()
    cfg = PagedEngineConfig(model=model, max_batch=2, max_len=96,
                            page_size=8, num_pages=64,
                            prefill_buckets=(16, 32))
    local = PagedLLMEngine(cfg)
    prefiller = PagedLLMEngine(cfg, params=local.params)
    decoder = PagedLLMEngine(cfg, params=local.params)
    rng = np.random.default_rng(1)
    prompts = [list(map(int, rng.integers(1, 128, size=n)))
               for n in (7, 20, 40)]
    want = local.generate(prompts, max_new_tokens=5)
    results = {}
    for i, p in enumerate(prompts):
        logits, caches = prefiller.prefill_only(p)
        decoder.submit_prefilled(
            GenerationRequest(prompt_tokens=p, max_new_tokens=5,
                              request_id=str(i)),
            caches, logits,
            done_callback=lambda r, t: results.__setitem__(
                int(r.request_id), t))
    import time
    deadline = time.monotonic() + 300
    while len(results) < len(prompts) and time.monotonic() < deadline:
        decoder.step()
    assert [results[i] for i in range(len(prompts))] == want
    assert decoder.page_leak_check() == 0


@pytest.mark.timeout_s(600)
def test_paged_under_4x_load_with_cancellation():
    """4x queue depth vs max_batch, with a cancellation mid-flight:
    survivors byte-equal the no-cache forward (VERDICT r3 load-test
    bar)."""
    paged = PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=4, max_len=96, page_size=8,
        num_pages=256, prefill_buckets=(16,)))
    rng = np.random.default_rng(2)
    prompts = [list(map(int, rng.integers(1, 128, size=9 + i % 5)))
               for i in range(16)]  # 4x the decode slots
    results = {}
    for i, p in enumerate(prompts):
        paged.submit(
            GenerationRequest(prompt_tokens=p, max_new_tokens=6,
                              request_id=str(i)),
            done_callback=lambda r, t: results.__setitem__(
                int(r.request_id), t))
    cancelled = {3, 11}
    for i in cancelled:
        paged.cancel(str(i))
    import time
    deadline = time.monotonic() + 300
    while len(results) < len(prompts) and time.monotonic() < deadline:
        paged.step()
    want = plain_greedy(
        model_forward(paged.model, paged.params),
        [p for i, p in enumerate(prompts) if i not in cancelled], 6)
    got = [results[i] for i in range(len(prompts)) if i not in cancelled]
    assert got == want
    for i in cancelled:
        assert results[i] is None  # cancelled marker


def test_prefix_router_affinity():
    """Same-prefix requests stick to one replica; load imbalance past the
    slack reroutes (reference: llm request_router prefix-aware policy)."""
    from ray_tpu.serve._private.common import ReplicaInfo
    from ray_tpu.serve._private.router import PrefixAwareRouter

    router = PrefixAwareRouter("k", controller_handle=None)
    replicas = [ReplicaInfo(replica_tag=f"t{i}", actor_name=f"r{i}",
                            actor_id=b"\x00" * 16) for i in range(3)]
    router.update_replicas(1, [r.__dict__ for r in replicas])
    router._handle_for = lambda info: info  # skip real actor handles
    hint = hash((1, 2, 3))
    first = router._pick(hint)
    for _ in range(5):
        assert router._pick(hint).actor_name == first.actor_name
    # a different prefix may go elsewhere; same one must not move
    router._inflight[first.actor_name] = 100  # overload the pinned one
    moved = router._pick(hint)
    assert moved.actor_name != first.actor_name  # slack exceeded -> move


@pytest.mark.timeout_s(600)
def test_openai_shapes_direct():
    """OpenAI-compat request/response shapes, no cluster needed."""
    from ray_tpu.llm.openai import OpenAIServer
    from ray_tpu.serve._private.proxy import Request

    model = LlamaConfig(vocab_size=300, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=256, remat=False,
                        use_flash=False, attention_impl="reference")
    cfg = PagedEngineConfig(model=model, max_batch=2, max_len=96,
                            page_size=8, num_pages=64,
                            prefill_buckets=(16, 32))
    server = OpenAIServer(cfg, model_id="tiny")

    def req(path, body):
        return Request("POST", path, {}, {}, json.dumps(body).encode())

    async def scenario():
        out = await server(req("/v1/completions",
                               {"prompt": "hello", "max_tokens": 4}))
        assert out["object"] == "text_completion"
        assert out["usage"]["completion_tokens"] == 4
        assert isinstance(out["choices"][0]["text"], str)
        out = await server(req("/v1/chat/completions",
                               {"messages": [{"role": "user",
                                              "content": "hi"}],
                                "max_tokens": 3}))
        assert out["object"] == "chat.completion"
        assert out["choices"][0]["message"]["role"] == "assistant"
        models = await server(Request("GET", "/v1/models", {}, {}, b""))
        assert models["data"][0]["id"] == "tiny"
        # streaming: marker + SSE events via stream_next
        out = await server(req("/v1/completions",
                               {"prompt": "go", "max_tokens": 3,
                                "stream": True}))
        sid = out["__rtpu_stream__"]
        events, done = [], False
        while not done:
            batch = await server.stream_next(sid, timeout_s=60)
            if batch.get("data"):
                events.append(batch["data"])
            done = batch["done"]
        joined = "".join(events)
        assert "data: " in joined and "data: [DONE]" in joined
        n_chunks = joined.count('"text"')
        assert n_chunks >= 1
        return True

    assert asyncio.run(scenario())


def test_streamed_tokens_reach_the_loop_once_a_step():
    """The engine's thread hands a decode step's tokens to the replica's
    loop in one wake-up, not one a token; every stream still gets its
    tokens in order, and its last token before its end. The first request
    freezes the heap (a full collection then leaves it alone)."""
    from ray_tpu.llm.serving import LLMServer

    cfg = PagedEngineConfig(model=tiny_model(), max_batch=4, max_len=96,
                            page_size=8, num_pages=64,
                            prefill_buckets=(16, 32))
    server = LLMServer(cfg)
    wakeups = []
    push = server._push_tokens

    def counted():
        wakeups.append(len(server._emitted))
        push()
    server._push_tokens = counted
    prompts = [[3 + i, 5, 7, 11 + i] for i in range(4)]

    async def stream(prompt):
        sid = await server.generate_stream_start(prompt, max_new_tokens=12)
        tokens, done = [], False
        while not done:
            batch = await server.stream_next(sid, timeout_s=60)
            tokens += batch["tokens"]
            done = batch["done"]
        return tokens

    async def scenario():
        streamed = await asyncio.gather(*(stream(p) for p in prompts))
        whole = [(await server.generate(p, max_new_tokens=12))["tokens"]
                 for p in prompts]
        return streamed, whole

    import gc
    frozen = gc.get_freeze_count()
    try:
        streamed, whole = asyncio.run(scenario())
        # the heap the server was built on is frozen when serving begins
        assert gc.get_freeze_count() > frozen
    finally:
        gc.unfreeze()   # this process is pytest's, not a replica's
    assert streamed == whole
    assert all(len(tokens) == 12 for tokens in streamed)
    assert not server._emitted
    # 48 tokens; four rows decode together, so a wake-up carries several
    assert max(wakeups) > 1
    assert sum(1 for n in wakeups if n) < 48


# ---------------------------------------------------------------------------
# cluster-level: HTTP streaming through the proxy
# ---------------------------------------------------------------------------

from conftest import raw_http as _raw_http  # noqa: E402 — shared helper


@pytest.mark.timeout_s(600)
def test_http_token_streaming_and_prefix_routing(llm_cluster):
    """Paged engine behind serve: chunked-HTTP token streaming end-to-end
    plus prefix-affinity routing config on the app."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    cfg = PagedEngineConfig(model=tiny_model(), max_batch=2, max_len=96,
                            page_size=8, num_pages=128,
                            prefill_buckets=(8, 16))
    app = build_llm_deployment(cfg)
    serve.run(app, name="llm", route_prefix="/llm",
              request_router="prefix", wait_for_ready_timeout_s=240)
    addr = serve.get_http_address().replace("http://", "")
    host, port = addr.rsplit(":", 1)

    head, raw = _raw_http(host, int(port), "POST", "/llm",
                          {"prompt_tokens": [1, 2, 3],
                           "max_new_tokens": 5, "stream": True})
    assert "Transfer-Encoding: chunked" in head
    tokens = []
    buf = raw
    while buf:
        line, _, buf = buf.partition(b"\r\n")
        if not line:
            continue
        n = int(line, 16)
        if n == 0:
            break
        chunk, buf = buf[:n], buf[n + 2:]
        for ln in chunk.decode().splitlines():
            if ln.strip():
                tokens.extend(json.loads(ln)["tokens"])
    assert len(tokens) == 5
    # non-streamed result for the same prompt matches the stream
    head, body = _raw_http(host, int(port), "POST", "/llm",
                           {"prompt_tokens": [1, 2, 3],
                            "max_new_tokens": 5})
    assert json.loads(body)["tokens"] == tokens
