"""Shared metric series for the LLM serving hot path
(reference: the vLLM-delegated stack exports these through
ray.util.metrics — TTFT / per-token latency / batch occupancy are the
series TPU utilization tuning is driven by; Podracer, arxiv 2104.06272).

Lazy singleton: metric objects register with the process-global registry
on first use, so importing the engines stays side-effect free and the
background flusher thread only starts in processes that actually serve.
All observations are local dict updates (microseconds) — safe on the
decode tick."""

from __future__ import annotations

from types import SimpleNamespace

from ..util.metrics import LazyMetrics

# Decode ticks are milliseconds; request latencies are seconds. One
# boundary set covers both with sub-ms resolution at the low end.
LATENCY_BOUNDARIES = [
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0]


def _build() -> SimpleNamespace:
    from ..util.metrics import Counter, Gauge, Histogram
    return SimpleNamespace(
        ttft=Histogram(
            "rtpu_llm_ttft_seconds",
            "Time from submit to first generated token",
            boundaries=LATENCY_BOUNDARIES,
            tag_keys=("engine",)),
        token_latency=Histogram(
            "rtpu_llm_token_latency_seconds",
            "Per-token decode latency (one batched decode "
            "tick)",
            boundaries=LATENCY_BOUNDARIES,
            tag_keys=("engine",)),
        request_latency=Histogram(
            "rtpu_llm_request_latency_seconds",
            "Submit-to-done latency of one generation "
            "request",
            boundaries=LATENCY_BOUNDARIES,
            tag_keys=("engine",)),
        prefill_tokens=Counter(
            "rtpu_llm_prefill_tokens_total",
            "Prompt tokens prefilled",
            tag_keys=("engine",)),
        decode_tokens=Counter(
            "rtpu_llm_decode_tokens_total",
            "Tokens generated by decode ticks",
            tag_keys=("engine",)),
        requests_finished=Counter(
            "rtpu_llm_requests_finished_total",
            "Finished generation requests by outcome",
            tag_keys=("engine", "outcome")),
        # Gauges carry a pid tag: two replicas of the same
        # engine kind live in different processes, and the
        # cross-process merge is last-write-wins per tag
        # tuple — without the pid one replica's saturation
        # would silently shadow the other's.
        queue_depth=Gauge(
            "rtpu_llm_queue_depth",
            "Requests waiting for admission",
            tag_keys=("engine", "pid")),
        running=Gauge(
            "rtpu_llm_running_requests",
            "Requests occupying decode slots (running-batch "
            "occupancy)",
            tag_keys=("engine", "pid")),
        kv_utilization=Gauge(
            "rtpu_llm_kv_page_utilization",
            "Fraction of KV pages allocated (paged engine)",
            tag_keys=("engine", "pid")),
        kv_occupancy=Gauge(
            "rtpu_kv_page_occupancy",
            "KV pages currently allocated out of the paged "
            "engine's pool",
            tag_keys=("engine", "pid")),
        waiting=Gauge(
            "rtpu_engine_waiting_requests",
            "Requests waiting for admission (queued plus "
            "preemption-parked)",
            tag_keys=("engine", "pid")),
        preemptions=Counter(
            "rtpu_engine_preemptions_total",
            "Sequences preempted by the scheduler, by reason",
            tag_keys=("engine", "reason")),
        park_seconds=Histogram(
            "rtpu_engine_park_seconds",
            "Time a request spent parked (admission blocked "
            "on page pressure, or preempted awaiting resume) "
            "before re-admission, by park reason",
            boundaries=LATENCY_BOUNDARIES,
            tag_keys=("engine", "reason")),
        shared_pages=Gauge(
            "rtpu_prefix_shared_pages",
            "Prefix-cache pages currently mapped by at least "
            "one live sequence (copy-on-write shares)",
            tag_keys=("engine", "pid")),
        masks_in_flight=Gauge(
            "rtpu_llm_masks_in_flight",
            "Masked positions in the open blocks of the rows of a "
            "model that generates by diffusion over blocks",
            tag_keys=("engine", "pid")),
        block_forwards=Counter(
            "rtpu_llm_block_forwards_total",
            "Row-forwards of open blocks dispatched (generation by "
            "diffusion over blocks), by kind: denoise or commit",
            tag_keys=("engine", "kind")),
        block_tokens_out=Counter(
            "rtpu_llm_block_tokens_out_total",
            "Tokens handed out by finished blocks",
            tag_keys=("engine",)),
        prefix_hits=Counter(
            "rtpu_prefix_cache_hits_total",
            "Prefill requests that reused cached full-page "
            "prefixes (paged engine prefix cache)",
            tag_keys=("engine",)),
        prefix_misses=Counter(
            "rtpu_prefix_cache_misses_total",
            "Prefill requests with full pages but no cached "
            "prefix to reuse",
            tag_keys=("engine",)),
        prefix_entries=Gauge(
            "rtpu_prefix_cache_entries",
            "Live prefix-cache LRU entries (occupancy against "
            "the eviction ceiling)",
            tag_keys=("engine", "pid")),
        server_requests=Counter(
            "rtpu_llm_server_requests_total",
            "LLM server entrypoint calls",
            tag_keys=("entry", "outcome")),
        openai_requests=Counter(
            "rtpu_openai_requests_total",
            "OpenAI-compat HTTP requests by route",
            tag_keys=("route",)),
    )


llm_metrics = LazyMetrics(_build)
