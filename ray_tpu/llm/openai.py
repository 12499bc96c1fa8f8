"""OpenAI-compatible serving app
(reference: llm/_internal/serve/builders/application_builders.py:60
build_openai_app + public serve/llm/__init__.py:168 — an HTTP app exposing
/v1/completions, /v1/chat/completions, /v1/models over the LLM engine).

The deployment subclasses `LLMServer`: same engine drive / stream plumbing,
plus tokenization and the OpenAI request/response shapes. Token streams go
out as SSE `data:` events through the proxy's chunked-HTTP relay."""

from __future__ import annotations

import json
import time
import uuid
from typing import Any, Dict, List, Optional

from .serving import BLOCK_SETTINGS, LLMServer
from .tokenizer import ByteTokenizer, get_tokenizer  # noqa: F401 — re-export


def _chat_prompt(messages: List[Dict[str, str]]) -> str:
    """Minimal chat template (reference models apply their HF chat
    template; the wire contract — not the template — is what the
    OpenAI-compat layer owns)."""
    parts = [f"{m.get('role', 'user')}: {m.get('content', '')}"
             for m in messages]
    parts.append("assistant:")
    return "\n".join(parts)


class OpenAIServer(LLMServer):
    """LLMServer speaking the OpenAI REST wire shapes."""

    def __init__(self, engine_config, params=None,
                 model_id: str = "ray-tpu-llm", tokenizer=None):
        super().__init__(engine_config, params=params)
        self.model_id = model_id
        # str → load tokenizer.json (native BPE) / checkpoint dir;
        # None → byte fallback; object → duck-typed encode/decode.
        self.tokenizer = get_tokenizer(tokenizer)
        # stream_id -> SSE formatting state
        self._sse: Dict[str, Dict[str, Any]] = {}

    # -- HTTP dispatch -----------------------------------------------------

    async def __call__(self, http_request):
        path = http_request.path
        from ._metrics import llm_metrics

        def _count(route: str):
            llm_metrics().openai_requests.inc(tags={"route": route})

        if path.endswith("/v1/models"):
            _count("/v1/models")
            return {"object": "list",
                    "data": [{"id": self.model_id, "object": "model",
                              "owned_by": "ray_tpu"}]}
        if path.endswith("/v1/completions"):
            _count("/v1/completions")
            return await self._completions(http_request.json(), chat=False)
        if path.endswith("/v1/chat/completions"):
            _count("/v1/chat/completions")
            return await self._completions(http_request.json(), chat=True)
        return (404, {"error": f"no route {path}"})

    async def _completions(self, body: Dict[str, Any], chat: bool):
        if chat:
            prompt_text = _chat_prompt(body.get("messages", []))
        else:
            prompt_text = body.get("prompt", "")
        prompt_tokens = self.tokenizer.encode(prompt_text)
        max_new = int(body.get("max_tokens", 16))
        temperature = body.get("temperature")
        top_k = body.get("top_k")
        top_p = body.get("top_p")
        # extensions for a model that generates by diffusion over blocks
        block_settings = {key: body[key] for key in BLOCK_SETTINGS
                          if body.get(key) is not None}
        # the proxy-stamped id (X-RTPU-Request-Id) IS the completion id
        # when present, so `why_slow(<header id>)` resolves client-side
        request_id = self._context_request_id() \
            or f"cmpl-{uuid.uuid4().hex[:24]}"
        if body.get("stream"):
            stream_id = await self.generate_stream_start(
                prompt_tokens, max_new_tokens=max_new,
                temperature=temperature, top_k=top_k, top_p=top_p,
                request_id=request_id, **block_settings)
            self._sse[stream_id] = {
                "chat": chat, "id": request_id,
                "created": int(time.time()), "first": True}
            return {"__rtpu_stream__": stream_id}
        out = await self.generate(
            prompt_tokens, max_new_tokens=max_new,
            temperature=temperature, top_k=top_k, top_p=top_p,
            request_id=request_id, **block_settings)
        text = self.tokenizer.decode(out["tokens"])
        created = int(time.time())
        usage = {"prompt_tokens": len(prompt_tokens),
                 "completion_tokens": out["num_generated"],
                 "total_tokens": len(prompt_tokens) +
                 out["num_generated"]}
        if chat:
            return {"id": request_id, "object": "chat.completion",
                    "created": created, "model": self.model_id,
                    "choices": [{"index": 0,
                                 "message": {"role": "assistant",
                                             "content": text},
                                 "finish_reason": "stop"}],
                    "usage": usage}
        return {"id": request_id, "object": "text_completion",
                "created": created, "model": self.model_id,
                "choices": [{"index": 0, "text": text,
                             "finish_reason": "stop"}],
                "usage": usage}

    # -- SSE stream formatting --------------------------------------------

    async def stream_next(self, stream_id: str,
                          timeout_s: float = 10.0) -> Dict[str, Any]:
        meta = self._sse.get(stream_id)
        batch = await super().stream_next(stream_id, timeout_s)
        if meta is None:  # plain (non-OpenAI) stream
            return batch
        events = []
        if batch.get("tokens"):
            text = self.tokenizer.decode(batch["tokens"])
            if meta["chat"]:
                delta: Dict[str, Any] = {"content": text}
                if meta.pop("first", None):
                    delta["role"] = "assistant"
                chunk = {"id": meta["id"],
                         "object": "chat.completion.chunk",
                         "created": meta["created"],
                         "model": self.model_id,
                         "choices": [{"index": 0, "delta": delta,
                                      "finish_reason": None}]}
            else:
                chunk = {"id": meta["id"], "object": "text_completion",
                         "created": meta["created"],
                         "model": self.model_id,
                         "choices": [{"index": 0, "text": text,
                                      "finish_reason": None}]}
            events.append(f"data: {json.dumps(chunk)}\n\n")
        if batch.get("error"):
            # mid-stream engine failure: surface it as an SSE event so
            # the client sees the error, not a silent [DONE] — with the
            # request id, so the failure stays attributable (why_slow)
            events.append("data: " + json.dumps(
                {"error": {"message": batch["error"],
                           "type": "engine_error",
                           "request_id": meta["id"]}}) + "\n\n")
        if batch["done"]:
            self._sse.pop(stream_id, None)
            events.append("data: [DONE]\n\n")
        return {"data": "".join(events), "done": batch["done"]}


def build_openai_app(engine_config, *, model_id: str = "ray-tpu-llm",
                     tokenizer=None, name: str = "OpenAIServer",
                     num_replicas: int = 1, params=None,
                     max_ongoing_requests: int = 64,
                     ray_actor_options: Optional[Dict[str, Any]] = None):
    """OpenAI-compatible application over the TPU engine (reference:
    serve/llm/__init__.py:168 build_openai_app). Deploy with
    `serve.run(app, request_router="prefix")` for prompt-prefix replica
    affinity; `ray_actor_options={"num_tpus": n}` gives each replica n
    chips."""
    from .. import serve
    deployment = serve.deployment(
        OpenAIServer, name=name, num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=ray_actor_options)
    return deployment.bind(engine_config, params, model_id, tokenizer)
