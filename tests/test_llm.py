"""LLM engine + serving tests: greedy decode exactness vs full-context
forward, continuous batching of concurrent requests, row reuse, and the
serve deployment end-to-end over HTTP (reference coverage: the vLLM
integration tests in llm/tests — here the engine is ours, so exactness
against the model itself is the ground truth)."""

import json
import urllib.request

import pytest

from plain_greedy import model_forward, plain_greedy
from ray_tpu.llm import (GenerationRequest, PagedEngineConfig,
                         PagedLLMEngine)
from ray_tpu.models.llama import LlamaConfig


def _tiny_engine(max_batch=3, max_len=96, temperature=0.0):
    config = LlamaConfig.tiny_test()
    return PagedLLMEngine(PagedEngineConfig(
        model=config, max_batch=max_batch, max_len=max_len,
        prefill_buckets=(8, 16, 32), temperature=temperature))


def _reference_greedy(engine, prompts, n):
    """Full-context re-forward each step: the exactness oracle."""
    return plain_greedy(model_forward(engine.model, engine.params),
                        prompts, n)


def test_greedy_decode_matches_full_forward():
    engine = _tiny_engine()
    prompt = [5, 17, 42, 7]
    n = 6
    got = engine.generate([prompt], max_new_tokens=n)
    want = _reference_greedy(engine, [prompt], n)
    assert got == want, (got, want)


def test_continuous_batching_concurrent_requests():
    engine = _tiny_engine(max_batch=3)
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [11], [4, 4], [13, 12]]
    results = engine.generate(prompts, max_new_tokens=5)
    assert results == _reference_greedy(engine, prompts, 5)
    stats = engine.stats()
    # 5 requests x 5 tokens on 3 rows: batching means far fewer ticks
    # than 5 sequential generations would take.
    assert stats["tokens_generated"] == 25
    assert stats["steps"] < 5 * 5


def test_slot_reuse_after_completion():
    engine = _tiny_engine(max_batch=2)
    first = engine.generate([[3, 1], [2, 2]], max_new_tokens=3)
    second = engine.generate([[5, 5, 5]], max_new_tokens=3)
    assert first + second == _reference_greedy(
        engine, [[3, 1], [2, 2], [5, 5, 5]], 3)
    assert all(seq.request is None for seq in engine.seqs)
    assert engine.page_leak_check() == 0


def test_prompt_too_long_rejected():
    engine = _tiny_engine()
    with pytest.raises(ValueError):
        engine.submit(GenerationRequest(prompt_tokens=list(range(200))))


@pytest.mark.timeout_s(300)
def test_llm_serve_deployment_http(llm_cluster):
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    config = PagedEngineConfig(model=LlamaConfig.tiny_test(), max_batch=2,
                               max_len=64, prefill_buckets=(8, 16))
    app = build_llm_deployment(config)
    serve.run(app, name="llm", route_prefix="/llm",
              wait_for_ready_timeout_s=240)
    addr = serve.api.get_http_address()
    req = urllib.request.Request(
        addr + "/llm",
        data=json.dumps({"prompt_tokens": [1, 2, 3],
                         "max_new_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=180) as resp:
        out = json.loads(resp.read())
    assert len(out["tokens"]) == 4
    assert out["num_generated"] == 4
    # Handle path + concurrent requests ride one engine.
    handle = serve.get_app_handle("llm")
    responses = [handle.generate.remote([7, 7], max_new_tokens=3)
                 for _ in range(4)]
    for r in responses:
        assert len(r.result(timeout_s=180)["tokens"]) == 3
