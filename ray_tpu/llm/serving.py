"""LLM serving deployment
(reference: llm/_internal/serve/deployments/llm/ — the vLLM server class;
builders llm/_internal/serve/builders/application_builders.py:19,60 →
public serve/llm/__init__.py:92 build_llm_deployment, :168 build_openai_app.
Here the engine is in-process and TPU-native instead of a vLLM subprocess.)

The deployment's asyncio loop drives the engine: requests enqueue into
the engine's scheduler and await completion futures; one background task
steps the engine whenever work is pending — iteration-level (continuous)
batching across concurrent HTTP/handle requests.

Streaming: tokens are pushed from the engine's token callbacks into
per-request stream buffers; the HTTP proxy long-polls `stream_next` on
the SAME replica and relays chunked HTTP (reference streams via ASGI
from the replica; the long-poll hop keeps the data plane on the actor
RPC plane with batched token delivery)."""

from __future__ import annotations

import asyncio
import collections
import gc
import logging
import time
import uuid
from typing import Any, Dict, List, Optional

from . import reqtrace

logger = logging.getLogger(__name__)

# what a request's body may say to a model that generates by diffusion over
# blocks (`paged.GenerationRequest`); every other model ignores them
BLOCK_SETTINGS = ("denoising_steps", "remasking", "confidence_threshold")


class _Stream:
    """One streamed request's buffer between the engine's thread and the
    proxy's long-polls, with the hand-off's own account: how long
    tokens lay here before a poll took them (reqtrace STREAMED)."""

    __slots__ = ("tokens", "event", "done", "error", "request_id",
                 "oldest", "polls", "delivered", "hold_sum_s",
                 "hold_max_s")

    def __init__(self, request_id: str):
        self.tokens: List[int] = []
        self.event = asyncio.Event()
        self.done = False
        self.error: Optional[str] = None
        self.request_id = request_id
        # time.monotonic() on the ENGINE's thread at the emit of the
        # oldest token still in `tokens`
        self.oldest = 0.0
        self.polls = 0          # polls answered with tokens
        self.delivered = 0      # tokens those polls took
        self.hold_sum_s = 0.0   # over those polls: answer - oldest
        self.hold_max_s = 0.0

    def take(self) -> List[int]:
        """Everything buffered, for one poll's answer."""
        tokens, self.tokens = self.tokens, []
        if tokens:
            hold = time.monotonic() - self.oldest
            self.polls += 1
            self.delivered += len(tokens)
            self.hold_sum_s += hold
            self.hold_max_s = max(self.hold_max_s, hold)
        return tokens

    def close(self) -> None:
        """The stream is over (its last poll answered, or cancelled):
        one event per request — per token would overrun the ring."""
        reqtrace.record(self.request_id, reqtrace.STREAMED,
                        polls=self.polls, tokens=self.delivered,
                        hold_sum_s=round(self.hold_sum_s, 6),
                        hold_max_s=round(self.hold_max_s, 6))


class LLMServer:
    """The replica callable (wrapped by serve.deployment).

    `engine_config` is a `PagedEngineConfig`: the replica runs the
    paged-KV continuous-batching engine (prefix page sharing, chunked
    prefill to max_len, streaming, cancel).

    `mesh_config` (a `parallel.MeshConfig`, e.g. tensor=4) shards the
    engine's params + KV pages over the replica's chips — the
    tensor-parallel analog of the reference's TP×PP engine-worker
    bundles (vllm_models.py:169-178,251)."""

    def __init__(self, engine_config, params=None, mesh_config=None):
        from .paged import PagedEngineConfig, PagedLLMEngine
        if not isinstance(engine_config, PagedEngineConfig):
            raise TypeError(
                f"engine_config must be a PagedEngineConfig, "
                f"got {type(engine_config).__name__}")
        mesh = None if mesh_config is None \
            else self._build_mesh(mesh_config)
        self._engine = PagedLLMEngine(engine_config, params=params,
                                      mesh=mesh)
        self._loop_task: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None
        self._streams: Dict[str, _Stream] = {}
        # (stream, emit time, token) from the engine's thread, not yet in
        # their streams' buffers; `_push_due`: a `_push_tokens` is queued
        self._emitted: collections.deque = collections.deque()
        self._push_due = False

    @staticmethod
    def _build_mesh(mesh_config):
        """Build the replica's device mesh: exactly the devices the
        config's fixed axes need (a replica may own a subset of the
        host's chips). A wildcard axis (the MeshConfig default data=-1)
        is pinned to 1 — an engine replica must not silently absorb
        every visible chip into a data axis it would only replicate
        over; scale-out across chips-beyond-TP belongs to
        num_replicas."""
        import dataclasses as _dc
        import math as _math
        import jax
        sizes = {"data": mesh_config.data, "fsdp": mesh_config.fsdp,
                 "tensor": mesh_config.tensor,
                 "sequence": mesh_config.sequence,
                 "pipeline": mesh_config.pipeline,
                 "expert": mesh_config.expert}
        wild = [k for k, v in sizes.items() if v == -1]
        if wild:
            mesh_config = _dc.replace(mesh_config,
                                      **{k: 1 for k in wild})
            for k in wild:
                sizes[k] = 1
        needed = _math.prod(sizes.values())
        devices = jax.devices()
        if len(devices) < needed:
            raise ValueError(
                f"mesh needs {needed} devices, replica sees "
                f"{len(devices)}")
        return mesh_config.build(devices[:needed])

    # -- engine drive ------------------------------------------------------

    def _ensure_loop(self):
        if self._loop_task is None:
            # serving begins. What the imports, the weights and the
            # compiles left on the heap stays for the replica's life:
            # moved out of the collector's sight, a full collection scans
            # what serving allocates (5 ms) and no longer stops every
            # thread of the process for the whole heap (100-140 ms with
            # jax loaded, every few seconds at 96 rows: several engine
            # ticks each, 2 % of the chip's time and most of the run-to-run
            # spread of a serving rate)
            gc.collect()
            gc.freeze()
            # what the collector still costs is stamped: a pass over 1 ms
            # is in the slow visit it stopped (accel `tick` row, `slow`)
            from .._internal import accel
            accel.watch_gc()
        if self._loop_task is None or self._loop_task.done():
            self._wake = asyncio.Event()
            self._loop_task = asyncio.ensure_future(self._drive())

    async def _drive(self):
        loop = asyncio.get_running_loop()
        while True:
            if not self._engine.has_work():
                self._wake.clear()
                await self._wake.wait()
            # One engine tick off-loop (it blocks on device compute).
            try:
                await loop.run_in_executor(None, self._engine.step)
            except Exception as e:  # noqa: BLE001 — keep the loop alive
                # Fail the in-flight requests LOUDLY: a deterministic step
                # failure (bad kernel shape, OOM) would otherwise spin
                # here forever while callers hang on their futures.
                logger.exception("engine step failed")
                try:
                    self._engine.fail_all(e)
                except Exception:  # noqa: BLE001
                    logger.debug("fail_all after engine step failure "
                                 "raised", exc_info=True)
                await asyncio.sleep(0.1)

    @staticmethod
    def _context():
        """Proxy-stamped request context (request id + tenant/route
        labels) of the serve call being handled — empty off-replica."""
        from ..serve.context import get_request_context
        return get_request_context()

    @classmethod
    def _context_request_id(cls) -> str:
        return cls._context().request_id

    async def _submit(self, request, done_callback, token_callback=None):
        # async so subclasses can do remote work first (PD-disagg fetches
        # the prefilled KV from the prefill deployment here)
        self._ensure_loop()
        self._engine.submit(request, done_callback=done_callback,
                            token_callback=token_callback)
        self._wake.set()

    # -- one-shot generation ----------------------------------------------

    async def generate(self, prompt_tokens: List[int],
                       max_new_tokens: int = 32,
                       temperature: Optional[float] = None,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       request_id: Optional[str] = None,
                       tenant: Optional[str] = None,
                       route: Optional[str] = None,
                       **block_settings) -> Dict[str, Any]:
        """`block_settings`: `denoising_steps`, `remasking`,
        `confidence_threshold` of a request to a model that generates by
        diffusion over blocks (`GenerationRequest`)."""
        from .paged import GenerationRequest
        loop = asyncio.get_running_loop()
        future = loop.create_future()

        def on_done(request, tokens):
            def _resolve():
                if future.done():
                    return
                if isinstance(tokens, Exception):
                    future.set_exception(tokens)
                elif tokens is None:  # cancelled
                    future.set_result(None)
                else:
                    future.set_result(tokens)
            loop.call_soon_threadsafe(_resolve)

        request = GenerationRequest(
            prompt_tokens=list(prompt_tokens),
            max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            request_id=request_id or self._context_request_id()
            or uuid.uuid4().hex,
            tenant=tenant or self._context().tenant,
            route=route or self._context().route, **block_settings)
        from ._metrics import llm_metrics
        await self._submit(request, on_done)
        try:
            tokens = await future
        except Exception:
            llm_metrics().server_requests.inc(
                tags={"entry": "generate", "outcome": "error"})
            raise
        llm_metrics().server_requests.inc(
            tags={"entry": "generate",
                  "outcome": "cancelled" if tokens is None else "ok"})
        if tokens is None:
            return {"tokens": [], "num_generated": 0, "cancelled": True}
        return {"tokens": tokens, "num_generated": len(tokens)}

    # -- streaming ---------------------------------------------------------

    def _push_tokens(self) -> None:
        """On the loop: move what the engine's thread emitted into the
        streams' buffers and wake their polls. One wake-up of the loop a
        decode step, not one a token: each wake-up hands the GIL from the
        stepping thread to the loop's."""
        # cleared first: a token appended from here on queues a new call
        self._push_due = False
        emitted = self._emitted
        while emitted:
            stream, at, token = emitted.popleft()
            if not stream.tokens:
                stream.oldest = at
            stream.tokens.append(token)
            stream.event.set()

    async def generate_stream_start(
            self, prompt_tokens: List[int], max_new_tokens: int = 32,
            temperature: Optional[float] = None,
            top_k: Optional[int] = None,
            top_p: Optional[float] = None,
            request_id: Optional[str] = None,
            tenant: Optional[str] = None,
            route: Optional[str] = None,
            **block_settings) -> str:
        """Begin a streamed generation; returns a stream id the caller
        polls with `stream_next` (the proxy relays it as chunked HTTP).
        `block_settings`: as `generate`'s; such a model's tokens arrive a
        finished block at a time, in position order."""
        from .paged import GenerationRequest
        loop = asyncio.get_running_loop()
        request_id = request_id or self._context_request_id() \
            or uuid.uuid4().hex
        stream_id = uuid.uuid4().hex
        stream = _Stream(request_id)
        self._streams[stream_id] = stream

        def on_token(request, token):
            # on the engine's thread; `_push_tokens` hands a whole step's
            # tokens to the loop in one wake-up
            self._emitted.append((stream, time.monotonic(), int(token)))
            if not self._push_due:
                self._push_due = True
                loop.call_soon_threadsafe(self._push_tokens)

        def on_done(request, tokens):
            def _finish():
                self._push_tokens()  # its last token before its end
                # outcome counted at COMPLETION, not submit — a stream
                # that errors or is cancelled must not read as "ok"
                from ._metrics import llm_metrics
                if isinstance(tokens, Exception):
                    stream.error = str(tokens)
                    outcome = "error"
                elif tokens is None:
                    outcome = "cancelled"
                else:
                    outcome = "ok"
                llm_metrics().server_requests.inc(
                    tags={"entry": "stream", "outcome": outcome})
                stream.done = True
                stream.event.set()
            loop.call_soon_threadsafe(_finish)

        request = GenerationRequest(
            prompt_tokens=list(prompt_tokens),
            max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            request_id=request_id,
            tenant=tenant or self._context().tenant,
            route=route or self._context().route, **block_settings)
        await self._submit(request, on_done, token_callback=on_token)
        return stream_id

    async def stream_next(self, stream_id: str,
                          timeout_s: float = 10.0) -> Dict[str, Any]:
        """Long-poll: the next batch of tokens (whatever has accumulated
        since the last call), plus the done flag. Empty batch on timeout."""
        stream = self._streams.get(stream_id)
        if stream is None:
            return {"tokens": [], "done": True, "error": "unknown stream"}
        if not stream.tokens and not stream.done:
            stream.event.clear()
            try:
                await asyncio.wait_for(stream.event.wait(), timeout_s)
            except asyncio.TimeoutError:
                pass
        tokens = stream.take()
        done = stream.done
        # every batch echoes the request id so clients can correlate
        # chunks (and why_slow the request) mid-stream
        out = {"tokens": tokens, "done": done,
               "request_id": stream.request_id}
        if stream.error:
            out["error"] = stream.error
        if done:
            self._streams.pop(stream_id, None)
            stream.close()
        return out

    async def cancel_stream(self, stream_id: str) -> bool:
        stream = self._streams.pop(stream_id, None)
        if stream is None:
            return False
        stream.close()
        return await self.cancel(stream.request_id)

    async def cancel(self, request_id: str) -> bool:
        """Abort a running or queued request."""
        ok = self._engine.cancel(request_id)
        if self._wake is not None:
            self._wake.set()
        return ok

    # -- HTTP entry --------------------------------------------------------

    async def __call__(self, http_request) -> Dict[str, Any]:
        body = http_request.json()
        prompt = body.get("prompt_tokens")
        if prompt is None:
            raise ValueError("body must contain prompt_tokens")
        max_new = int(body.get("max_new_tokens", 32))
        temp = body.get("temperature")
        headers = getattr(http_request, "headers", None) or {}
        request_id = body.get("request_id") \
            or headers.get("x-rtpu-request-id")
        tenant = body.get("tenant") or headers.get("x-rtpu-tenant")
        route = headers.get("x-rtpu-route")
        block_settings = {key: body[key] for key in BLOCK_SETTINGS
                          if body.get(key) is not None}
        if body.get("stream"):
            stream_id = await self.generate_stream_start(
                prompt, max_new_tokens=max_new, temperature=temp,
                request_id=request_id, tenant=tenant, route=route,
                **block_settings)
            # The proxy recognises this marker and relays stream_next
            # batches as chunked HTTP on the same replica.
            return {"__rtpu_stream__": stream_id}
        return await self.generate(
            prompt, max_new_tokens=max_new, temperature=temp,
            request_id=request_id, tenant=tenant, route=route,
            **block_settings)

    def engine_stats(self) -> Dict[str, Any]:
        return self._engine.stats()

    async def device_report(self) -> Dict[str, Any]:
        """What this replica really runs on, read in its own process:
        the backend's platform, device kind and count, each device's
        `memory_stats()`, the accel plane's compile and step folds, the
        Pallas kernels found in the compiled decode step and the
        whole-pool copies in it (page pools, recurrent-state pools: both
        0). The caller — a driver that must stay
        off JAX — learns from this whether a chip lease became a chip."""
        def probe():
            import os
            import jax
            from .._internal import accel
            from ..ops.attention import pallas_kernels
            devices = jax.devices()
            report: Dict[str, Any] = {
                "pid": os.getpid(),
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
                "memory": [d.memory_stats() for d in devices],
                "compile": accel.compile_summary(),
                "steps": accel.step_summary(),
            }
            text = self._engine.decode_program_text()
            report["decode_kernels"] = pallas_kernels(text)
            report["decode_pool_copies"] = self._engine.pool_copies(text)
            report["decode_state_copies"] = self._engine.state_copies(text)
            return report
        # off-loop: the probe compiles, and a blocked loop fails the
        # replica's health check
        return await asyncio.get_running_loop().run_in_executor(
            None, probe)

    def autoscaling_metrics(self) -> Dict[str, Any]:
        """Replica autoscaling hook (replica.get_metrics() folds this
        into the controller's closed loop): the engine's waiting-queue
        depth, median TTFT, and KV page occupancy."""
        return dict(self._engine.autoscaling_metrics())


def build_llm_deployment(engine_config, *, name: str = "LLMServer",
                         num_replicas: int = 1, params=None,
                         max_ongoing_requests: int = 64,
                         mesh_config=None,
                         ray_actor_options: Optional[Dict[str, Any]] = None):
    """Serve application for the paged engine: `engine_config` is a
    `PagedEngineConfig`
    (reference: serve/llm/__init__.py:92 build_llm_deployment).
    `ray_actor_options={"num_tpus": n}` gives each replica n chips: its
    worker then opens the TPU backend or dies with the backend's error."""
    from .. import serve
    deployment = serve.deployment(
        LLMServer, name=name, num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=ray_actor_options)
    return deployment.bind(engine_config, params, mesh_config)
