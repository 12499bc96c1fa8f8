"""ray_tpu.llm — native TPU LLM serving
(reference: python/ray/llm — serve deployments wrapping vLLM
llm/_internal/serve/deployments/llm/vllm/; builders
serve/llm/__init__.py:92 build_llm_deployment / :168 build_openai_app).

The reference delegates the engine to vLLM (CUDA); no such engine exists
for TPU, so this package IS the engine (SURVEY §7 step 8): a
continuous-batching decode loop over paged KV caches (pages shared
through a radix tree of prefixes, prefill in chunks jitted once per
bucket, one jitted decode step for the whole batch), deployed behind
ray_tpu.serve.

Exports resolve lazily (PEP 562): the engine pulls in jax at import
time, but jax-free processes — the serve proxy stamping request-trace
events, the dashboard folding `reqtrace` payloads — must be able to
import this package (and its light submodules) without paying the jax
import."""

_EXPORTS = {
    "GenerationRequest": ".paged",
    "PagedEngineConfig": ".paged",
    "PagedLLMEngine": ".paged",
    "LLMServer": ".serving",
    "build_llm_deployment": ".serving",
    "OpenAIServer": ".openai",
    "build_openai_app": ".openai",
    "ByteTokenizer": ".openai",
    "PrefillServer": ".disagg",
    "PDDecodeServer": ".disagg",
    "build_pd_disagg_app": ".disagg",
    "RadixPrefixCache": ".radix",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    submodule = _EXPORTS.get(name)
    if submodule is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    from importlib import import_module
    return getattr(import_module(submodule, __name__), name)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
