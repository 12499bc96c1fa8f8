"""Prefill/decode disaggregation
(reference: llm/_internal/serve/deployments/prefill_decode_disagg/ —
separate prefill and decode engine pools with KV transfer between them,
so compute-bound prefill and latency-bound decode scale independently).

TPU-native shape: the prefill deployment runs chunked prefill only and
returns the prompt's KV pages + final logits; the decode deployment's
paged engine installs them via `submit_prefilled` (page allocation,
prefix sharing, streaming all behave exactly as with local prefill).
KV moves over the object plane as numpy arrays; on real multi-host
topologies the same handoff rides device-objects/ICI transfer
(experimental/device_objects.py) instead of host shm."""

from __future__ import annotations

import asyncio
import logging
from typing import Any, Dict, List, Optional

from .serving import LLMServer

logger = logging.getLogger(__name__)


class PrefillServer:
    """Prefill-only deployment: owns a paged engine but never decodes."""

    def __init__(self, engine_config, params=None):
        from .paged import PagedEngineConfig, PagedLLMEngine
        if not isinstance(engine_config, PagedEngineConfig):
            raise TypeError("PD-disagg requires PagedEngineConfig")
        self._engine = PagedLLMEngine(engine_config, params=params)

    async def prefill(self, prompt_tokens: List[int]):
        """Chunked prefill; returns (last_logits, per-layer (k, v) numpy
        pairs trimmed to the prompt's pages)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, self._engine.prefill_only, list(prompt_tokens))

    def engine_stats(self) -> Dict[str, Any]:
        return self._engine.stats()


class PDDecodeServer(LLMServer):
    """Decode-side server: prefill is delegated to the PrefillServer
    deployment; everything else (streaming, cancel, HTTP shapes) is
    inherited from LLMServer."""

    def __init__(self, engine_config, params=None, prefill_handle=None):
        super().__init__(engine_config, params=params)
        if prefill_handle is None:
            raise ValueError("PDDecodeServer needs a prefill_handle")
        self._prefill_handle = prefill_handle

    async def _submit(self, request, done_callback, token_callback=None):
        last_logits, caches = await \
            self._prefill_handle.prefill.remote(request.prompt_tokens)
        self._ensure_loop()
        self._engine.submit_prefilled(
            request, caches, last_logits, done_callback=done_callback,
            token_callback=token_callback)
        self._wake.set()


def build_pd_disagg_app(engine_config, *, params=None,
                        num_prefill_replicas: int = 1,
                        num_decode_replicas: int = 1,
                        max_ongoing_requests: int = 64):
    """Disaggregated serving application: ingress = decode deployment,
    composed with a prefill deployment (reference:
    prefill_decode_disagg/ builders). Both pools must share params —
    pass them explicitly, or rely on the deterministic seed init."""
    from .. import serve
    prefill_app = serve.deployment(
        PrefillServer, name="PrefillServer",
        num_replicas=num_prefill_replicas,
        max_ongoing_requests=max_ongoing_requests,
    ).bind(engine_config, params)
    decode = serve.deployment(
        PDDecodeServer, name="PDDecodeServer",
        num_replicas=num_decode_replicas,
        max_ongoing_requests=max_ongoing_requests)
    return decode.bind(engine_config, params, prefill_app)
