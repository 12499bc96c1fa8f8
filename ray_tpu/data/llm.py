"""Batch LLM inference over Datasets
(reference: python/ray/data/llm.py + llm/_internal/batch/ — the
build_llm_processor API: a Dataset stage that runs every row's prompt
through an engine replica pool with continuous batching).

TPU-native: the processor is an actor-pool map stage whose workers each
hold ONE paged engine (weights + KV pool on device, loaded once);
within a block the prompts run through the engine's continuous-batching
scheduler, so decode steps batch across rows."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional


def build_llm_processor(engine_config, *, concurrency: int = 1,
                        max_new_tokens: int = 32,
                        prompt_column: str = "prompt_tokens",
                        output_column: str = "generated_tokens",
                        params=None,
                        detokenize: Optional[Callable] = None
                        ) -> Callable:
    """Returns `processor(dataset) -> dataset` adding `output_column`
    with each row's generation (reference: data/llm.py
    build_llm_processor -> Processor). `prompt_column` holds token-id
    lists (or strings when `detokenize`'s inverse applies upstream)."""

    class _EngineWorker:
        def __init__(self):
            from ..llm.paged import PagedEngineConfig, PagedLLMEngine
            if not isinstance(engine_config, PagedEngineConfig):
                raise TypeError(type(engine_config).__name__)
            self.engine = PagedLLMEngine(engine_config, params=params)

        def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
            import numpy as np
            prompts = [list(map(int, p)) for p in batch[prompt_column]]
            outs = self.engine.generate(prompts,
                                        max_new_tokens=max_new_tokens)
            out = dict(batch)
            result = np.empty(len(outs), dtype=object)
            for i, tokens in enumerate(outs):
                result[i] = detokenize(tokens) if detokenize else tokens
            out[output_column] = result
            return out

    def processor(dataset):
        return dataset.map_batches(
            _EngineWorker, compute="actors", concurrency=concurrency)

    return processor
