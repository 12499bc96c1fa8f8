"""LLM engine + serving tests: greedy decode exactness vs full-context
forward, continuous batching of concurrent requests, slot reuse, and the
serve deployment end-to-end over HTTP (reference coverage: the vLLM
integration tests in llm/tests — here the engine is ours, so exactness
against the model itself is the ground truth)."""

import json
import urllib.request

import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, GenerationRequest, LLMEngine
from ray_tpu.models.llama import LlamaConfig, LlamaModel


def _tiny_engine(max_batch=3, max_len=96, temperature=0.0):
    config = LlamaConfig.tiny_test()
    return LLMEngine(EngineConfig(
        model=config, max_batch=max_batch, max_len=max_len,
        prefill_buckets=(8, 16, 32), temperature=temperature))


def _reference_greedy(engine, prompt, n):
    """Full-context re-forward each step: the exactness oracle."""
    import jax.numpy as jnp
    tokens = list(prompt)
    out = []
    for _ in range(n):
        logits = engine.model.apply({"params": engine.params},
                                    jnp.asarray([tokens], jnp.int32))
        nxt = int(np.argmax(np.asarray(logits[0, -1], np.float32)))
        out.append(nxt)
        tokens.append(nxt)
    return out


def test_greedy_decode_matches_full_forward():
    engine = _tiny_engine()
    prompt = [5, 17, 42, 7]
    n = 6
    got = engine.generate([prompt], max_new_tokens=n)[0]
    want = _reference_greedy(engine, prompt, n)
    assert got == want, (got, want)


def test_continuous_batching_concurrent_requests():
    engine = _tiny_engine(max_batch=3)
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [11], [4, 4], [13, 12]]
    results = engine.generate(prompts, max_new_tokens=5)
    assert len(results) == 5
    for prompt, tokens in zip(prompts, results):
        assert tokens == _reference_greedy(engine, prompt, 5), prompt
    stats = engine.stats()
    # 5 requests x 5 tokens with 3 slots: batching means far fewer decode
    # steps than 5 sequential generations would take.
    assert stats["tokens_generated"] == 25
    assert stats["steps"] < 5 * 5


def test_slot_reuse_after_completion():
    engine = _tiny_engine(max_batch=2)
    first = engine.generate([[3, 1], [2, 2]], max_new_tokens=3)
    second = engine.generate([[5, 5, 5]], max_new_tokens=3)
    assert second[0] == _reference_greedy(engine, [5, 5, 5], 3)
    assert all(s.request is None for s in engine.slots)


def test_prompt_too_long_rejected():
    engine = _tiny_engine()
    with pytest.raises(ValueError):
        engine.submit(GenerationRequest(prompt_tokens=list(range(200))))


@pytest.mark.timeout_s(300)
def test_llm_serve_deployment_http(llm_cluster):
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    config = EngineConfig(model=LlamaConfig.tiny_test(), max_batch=2,
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
