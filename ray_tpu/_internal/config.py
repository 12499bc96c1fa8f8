"""Runtime flag system.

Equivalent of the reference's `RAY_CONFIG` x-macro table
(src/ray/common/ray_config_def.h, 224 entries): a typed default table,
overridable per-process via `RTPU_<name>` environment variables and
cluster-wide via `init(_system_config={...})`.

Typed access:  `from ray_tpu._internal.config import CONFIG;
CONFIG.lease_idle_timeout_s`.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict

_DEFAULTS: Dict[str, Any] = {
    # --- RPC layer ---
    "rpc_connect_timeout_s": 10.0,
    "rpc_call_timeout_s": 60.0,
    "rpc_retry_base_delay_ms": 50,
    "rpc_retry_max_delay_ms": 2000,
    "rpc_max_retries": 5,
    # Fault injection: "method:req_prob:resp_prob,method2:..." — probability of
    # dropping the request / the response of matching RPC methods.
    # (Reference: src/ray/rpc/rpc_chaos.h RAY_testing_rpc_failure.)
    "testing_rpc_failure": "",
    # --- chaos harness (_internal/chaos.py) ---
    # Extended fault spec "method:action:prob[:param],..." with actions
    # drop_req / drop_resp / delay / dup; folds into one registry with
    # the legacy testing_rpc_failure rules.
    "chaos_spec": "",
    # Seed for the chaos RNG (0 = process-random). A fixed seed makes a
    # failing chaos run replayable bit-for-bit.
    "chaos_seed": 0,
    # Gate on the self-kill RPCs (`cli chaos kill-gcs`): a production
    # cluster must not expose a remote SIGKILL by default.
    "chaos_allow_kill": False,
    # Time-scheduled chaos script: "at_s:method:action:prob[:param],..."
    # — each entry ARMS its rule `at_s` seconds after the schedule is
    # armed (a later entry for the same method:action replaces the
    # earlier one, so `10:hb:delay:0` switches a fault off at t=10).
    # Deterministic under chaos_seed; `cli chaos show` prints the armed
    # schedule with per-entry activation state.
    "chaos_schedule": "",
    # --- fleet operations (drain / rolling upgrades) ---
    # Graceful-drain budget: how long a draining raylet waits for
    # in-flight leases to finish before stragglers get postmortem-tagged
    # kills (kill_reason=drain_timeout -> DRAIN_TIMEOUT_KILLED).
    "drain_timeout_s": 30.0,
    # --- elastic autoscaler (autoscaler/elastic.py) ---
    # Scale-up fires only after the pending-lease queue has been
    # non-empty AND older than queue_age_up_s for up_delay_s straight;
    # scale-in only after a node has been fully idle for down_delay_s.
    # Both delays are the hysteresis that keeps an oscillating queue
    # from flapping the fleet.
    "autoscale_queue_age_up_s": 1.0,
    "autoscale_up_delay_s": 2.0,
    "autoscale_down_delay_s": 15.0,
    # --- object store ---
    "object_store_memory_bytes": 2 * 1024**3,
    # Objects <= this many bytes are returned inline in RPC replies and live
    # in the in-process memory store instead of shared memory.
    "max_direct_call_object_size": 100 * 1024,
    "object_spilling_threshold": 0.8,
    # fsspec URL prefix for cloud spilling ("" = node-local directory);
    # e.g. "memory://rtpu-spill", "s3://bucket/prefix"
    # (reference: _private/external_storage.py:398 smart_open driver)
    "object_spilling_uri": "",
    "object_store_chunk_bytes": 4 * 1024**2,
    "spill_directory": "",  # default: <session dir>/spill
    # --- scheduling ---
    "scheduler_hybrid_threshold": 0.5,
    "lease_idle_timeout_s": 2.0,
    "worker_lease_parallelism": 10,
    "max_pending_lease_requests_per_shape": 10,
    # Pipelined task pushes per leased worker (reference:
    # normal_task_submitter.h max_tasks_in_flight_per_worker). The worker
    # executes serially; >1 hides push/reply latency behind execution.
    "max_tasks_in_flight_per_lease": 8,
    # Cooperative lease fairness: a driver flooding tasks returns each
    # lease to the raylet after holding it this long (the worker stays
    # warm in the raylet's idle pool), so other drivers' queued lease
    # requests get a turn instead of starving behind indefinitely-held
    # leases (multi-client flood fairness; reference: the raylet asks
    # for unused leased workers back, release_unused_workers).
    "lease_fair_rotation_s": 1.0,
    # Self-heal for lost pushes/replies WITHOUT bounding task duration
    # (tasks may legitimately run for hours): while a push_task call is
    # outstanding, the submitter probes the worker every period; if the
    # worker doesn't know the task for `threshold` consecutive probes,
    # the push (or its reply) was lost — drop the lease and retry.
    "push_probe_period_s": 15.0,
    "push_probe_unknown_threshold": 2,
    "push_probe_unreachable_threshold": 8,
    # --- device objects ---
    # HBM bytes the process may hold pinned for device-resident objects
    # (device_put_ref pins + DeviceChannel staging). 0 = unlimited.
    # Past the budget, producers BLOCK briefly for frees and then spill
    # to the host object store (reference: gpu_object_manager.py:61
    # tracks the same producer/consumer imbalance).
    "device_object_hbm_budget": 0,
    # How long device_put_ref blocks for frees before spilling to host.
    "device_object_backpressure_timeout_s": 10.0,
    # --- workers ---
    "worker_start_timeout_s": 60.0,
    "num_prestart_workers": 0,
    "worker_idle_timeout_s": 60.0,
    "maximum_startup_concurrency": 4,
    # --- health / failure detection ---
    "health_check_period_s": 1.0,
    "health_check_timeout_s": 5.0,
    "health_check_failure_threshold": 5,
    # driver (job) liveness: a crashed/os._exit'd driver's leases,
    # actors and PGs are reclaimed once its ping fails this many sweeps
    "driver_health_check_period_s": 3.0,
    "driver_health_check_failure_threshold": 3,
    "worker_liveness_check_period_s": 1.0,
    # --- gcs ---
    "gcs_storage": "memory",  # or a file path for persistence
    # Persistence path selector once a storage path exists:
    #   wal    — write-ahead log + compacted snapshot (durable per
    #            mutation, O(record) appends, torn-write detection)
    #   legacy — whole-state snapshot rewrite on every mutation (the
    #            pre-WAL behavior, kept as the A/B arm)
    #   off    — storage path ignored, nothing persisted
    "gcs_persist": "wal",
    # Compact (fold WAL into the snapshot) once the log passes this size.
    "gcs_wal_compact_bytes": 4 * 1024**2,
    # fsync appended records (group-committed per event-loop tick).
    # Off trades the last tick's mutations for bench-grade append speed.
    "gcs_wal_fsync": True,
    # Consecutive persist failures (disk full, permissions) before the
    # GCS emits a rate-limited GCS_PERSIST_FAILING event — durability
    # loss must be visible, not a logger.exception loop.
    "gcs_persist_failure_event_threshold": 3,
    # --- gcs failover / reconnect ---
    # Consecutive heartbeat failures before a raylet declares the GCS
    # down and enters its reconnect loop.
    "gcs_heartbeat_failure_threshold": 3,
    # Jittered-exponential reconnect schedule (raylets, drivers, the
    # serve controller and autoscaler all ride backoff.Backoff with
    # these bounds) and the total give-up deadline for client-side
    # reconnecting calls (0 = fail fast, no reconnect window).
    "gcs_reconnect_base_delay_ms": 50,
    "gcs_reconnect_max_delay_ms": 2000,
    "gcs_reconnect_timeout_s": 60.0,
    "pubsub_push_timeout_s": 5.0,
    # --- actors ---
    # Bound on actor __init__: a wedged-but-alive worker must fail the
    # creation (and reschedule) rather than park it forever.
    "actor_creation_timeout_s": 600.0,
    # Per-RPC bound on one actor lease request to a raylet. Generous by
    # default: the raylet's bounded spawn pipeline legitimately queues a
    # grant behind hundreds of spawns in an actor storm; retries after
    # this timeout coalesce onto the SAME in-flight grant raylet-side.
    "actor_lease_rpc_timeout_s": 600.0,
    # --- owner sharding (the multi-loop driver core) ---
    # Owner shards per CoreWorker: driver-side ownership state (lease /
    # pending tables, done-stream fold, probe sweeps, reply routing)
    # partitions across this many io loops, each with its own fastrpc
    # ring, keyed by hash(task_id/actor_id) % N. 0 = auto (min(4,
    # cores // 2) for drivers — sharding needs spare cores, small
    # boxes stay single-loop; always 1 for workers); 1 = the
    # exact-legacy single-loop A/B path.
    "owner_shards": 0,
    # --- tasks ---
    "task_max_retries_default": 3,
    "actor_max_restarts_default": 0,
    "max_lineage_bytes": 64 * 1024**2,
    "inline_arg_max_bytes": 100 * 1024,
    # --- memory monitor ---
    "memory_monitor_refresh_ms": 250,
    "memory_usage_threshold": 0.95,
    # Watermark BELOW the kill threshold at which the raylet starts
    # emitting MEMORY_PRESSURE events (reference: memory_monitor.h
    # usage_threshold vs min_memory_free_bytes two-level policy).
    "memory_monitor_watermark": 0.90,
    # Policy hook: stop granting NEW worker leases while node memory
    # sits above the watermark — requests queue (or spill back to a
    # healthy node) and grant once pressure clears; grant_or_reject
    # callers (actor scheduling) get a transient rejection instead.
    # Existing leases run on.
    "memory_pressure_refuse_leases": False,
    # --- cluster event log ---
    "event_log_max_entries": 10_000,
    # --- metrics ---
    "metrics_report_interval_s": 5.0,
    # --- continuous profiler (the CPU observability plane) ---
    # Default sampling rate for on-demand captures (cli profile /
    # profile_cluster) when the caller doesn't pass one.
    "profiler_hz": 100.0,
    # Bounded per-process sample ring (a sample is ~a few hundred bytes
    # of interned strings; overflow drops the oldest and counts it).
    "profiler_ring_size": 65536,
    # >0: every process (worker/raylet/GCS/driver) starts a continuous
    # sampler at boot at this rate. Off by default — captures start
    # samplers on demand.
    "profiler_autostart_hz": 0.0,
    # --- accelerator observability plane ---
    # HBM used/limit ratio above which device snapshots publish
    # DEVICE_MEMORY_PRESSURE events into the GCS event log (only on
    # backends that report a limit; rate-limited per device below).
    "accel_hbm_watermark": 0.90,
    "accel_pressure_min_interval_s": 30.0,
    # --- task events (reference: RAY_task_events_* flags) ---
    "enable_task_events": True,
    # --- logging / the log & forensics plane ---
    "log_to_driver": True,
    # Per-worker bounded log ring at the raylet (lines; overflow drops
    # the oldest and counts it). Rings retain output even with
    # log_to_driver off — the ring IS the retention layer.
    "log_ring_lines": 2000,
    # Dead workers' rings kept (FIFO) so `cli logs --task` and
    # postmortems still answer after the process is gone.
    "log_ring_dead_workers": 16,
    # Max concurrently in-flight WORKER_LOGS publishes per raylet: with
    # the GCS down/slow, batches beyond the window drop-with-counter
    # instead of queueing unboundedly on the EventLoopThread.
    "log_pump_inflight_max": 16,
    # Per-worker forwarding rate limit (lines/s; 0 = unlimited). Gates
    # pubsub streaming only — the bounded ring always captures.
    "log_rate_limit_lines_per_s": 0.0,
    # Lines of a dead worker's ring quoted in its postmortem report.
    "postmortem_tail_lines": 20,
    # How long a caller waits for the raylet's death report to reach
    # the GCS before raising WorkerCrashedError without a postmortem
    # (the liveness sweep runs every worker_liveness_check_period_s,
    # so the report usually lags the connection drop by ~1s).
    "postmortem_fetch_timeout_s": 2.0,
    # --- collectives backend (util/collective) ---
    # Algorithm forcing for the host-plane allreduce: auto picks per
    # (bytes, topology) — flat topologies keep the exact legacy
    # star/ring cutover, multi-slice topologies take the binomial tree
    # below the ring threshold and the hierarchical schedule (intra-
    # slice reduce-scatter, DCN allreduce of the shards, intra-slice
    # allgather) above it. ring/tree/hier/star force one arm for A/B.
    "collective_algo": "auto",
    # EQuARX-style block-int8 quantization of the hierarchical
    # schedule's inter-slice (DCN) hop: off (default, bit-exact) or
    # int8 (quantize per block, accumulate fp32, dequantize — SUM over
    # float payloads only; everything else stays exact).
    "collective_quant": "off",
    # Elements per quantization block (one fp32 scale per block).
    "collective_quant_block": 64,
    # --- owner-shard lease reclaim ---
    # With the owner core sharded, one shard's queued lease request can
    # starve behind ANOTHER shard's idle leases until the holder's 2s
    # idle-lease cleaner tick (observed as ~2s sync-get outliers at
    # RTPU_OWNER_SHARDS>=2). If a grant hasn't landed within this
    # delay, the requesting shard asks every other shard to return its
    # idle leases (zero in-flight, no local waiters) immediately.
    "lease_reclaim_delay_s": 0.1,
    # --- train-plane flight deck (steptrace / straggler / alerts) ---
    # Bounded per-process step-span ring (a span is 5 small fields;
    # overflow drops the oldest — steady-state loops keep the tail).
    "steptrace_max_spans": 4096,
    # Straggler detector: a peer whose collective entry-wait exceeds
    # BOTH the absolute floor and median_multiple x the median wait of
    # the other peers for `consecutive` collective ops in a row is
    # flagged (rate-limited per peer below).
    "straggler_median_multiple": 4.0,
    "straggler_consecutive_ops": 3,
    "straggler_min_wait_s": 0.02,
    "straggler_min_interval_s": 30.0,
    # SLO alert engine: evaluation tick of the daemon thread, and the
    # per-rule re-fire rate limit (a sustained breach is one alert per
    # interval, not one per tick).
    "alert_eval_interval_s": 5.0,
    "alert_min_interval_s": 60.0,
    # Bounded GCS alert table (rows beyond this drop the oldest).
    "alert_log_max_entries": 1000,
    # --- train ---
    "train_health_check_interval_s": 1.0,
    # GSPMD trainer: ZeRO-1 cross-replica sharded weight updates
    # (reduce-scatter grads, shard-local Adam on the 1/W optimizer
    # slice, allgather the param delta). RTPU_TRAIN_ZERO1=0 is the
    # replicated-update A/B arm (full optimizer state on every
    # replica, allreduce grads).
    "train_zero1": True,
    # MPMD pipeline: microbatches per GPipe round (bubble fraction is
    # (S-1)/(S-1+M) on parallel hardware; more microbatches = smaller
    # bubble, more in-flight activation memory).
    "train_pipeline_microbatches": 4,
    # --- LLM serving (llm/paged.py) ---
    # Prefix-cache entry ceiling: radix-tree nodes (continuous batching)
    # or token-tuple LRU entries (legacy arm) kept before LRU eviction
    # of refcount-1 leaves. Each entry pins one KV page.
    "prefix_cache_entries": 128,
    # --- serve-plane request observatory (llm/reqtrace.py) ---
    # Bounded per-process request-lifecycle event ring (an event is 4
    # small fields; overflow drops the oldest — steady-state serving
    # keeps the tail).
    "reqtrace_max_events": 8192,
    # Serve SLO thresholds for the default alert rules (alerts.py):
    # TTFT p95 over the window, max lease-queue age, and max KV-page
    # occupancy fraction before an alert fires.
    "serve_ttft_p95_slo_s": 2.0,
    "serve_queue_age_slo_s": 30.0,
    "serve_kv_occupancy_slo": 0.95,
    # --- RPC/transport observatory (_internal/rpc_metrics.py) ---
    # Any client call slower than this lands in the slow-RPC watchdog
    # ring with method + peer + creation-site attribution.
    "rpc_slow_call_s": 1.0,
    # Bounded watchdog ring (a row is 6 small fields; overflow drops
    # the oldest).
    "rpc_slow_ring_size": 256,
    # Rate limit for the SLOW_RPC GCS event the watchdog posts (one
    # event per window per process; the ring keeps everything).
    "rpc_slow_event_interval_s": 30.0,
    # Transport SLO thresholds for the default alert rules (alerts.py):
    # client-call p99 over the window, and max native-ring queue depth
    # before the ring_backpressure alert fires.
    "rpc_client_p99_slo_s": 5.0,
    "ring_backpressure_depth": 4096,
    # --- A/B kill switches (every switch lives here so a typo'd
    # RTPU_* spelling is caught by rtpulint rule L003 instead of
    # silently doing nothing) ---
    # Disable the flat-wire task codec; every spec rides the pickle path.
    "no_flat_wire": False,
    # Disable the native receive path (PR 11): frames are delivered raw
    # and decoded in Python, done streams ride the legacy pickled
    # oneway, and refcount decrements go one RPC per object — the
    # exact-legacy A/B arm. Receivers still understand both wire forms,
    # so mixed on/off processes interoperate.
    "no_native_decode": False,
    # Disable owner callsite capture on put()/submit.
    "no_callsites": False,
    # Disable the coalesced submit fast path.
    "no_submit_fastpath": False,
    # Disable asyncio eager task factory on the io loop.
    "no_eager_tasks": False,
    # Kill switch for the stack-sampling profiler: start_profiling
    # refuses and no sampler thread is ever spawned.
    "no_profiler": False,
    # Kill switch for the accelerator observability plane: zero
    # jax.monitoring listeners installed, device snapshots return
    # empty, StepTimer/report_step are no-ops.
    "no_accel_metrics": False,
    # Kill switch for the log & forensics plane: no stream stamping in
    # workers, no raylet rings, exact-legacy pump wiring (DEVNULL with
    # log_to_driver off), no postmortem assembly — zero extra threads.
    "no_log_plane": False,
    # Kill switch for the cross-rank step timeline: span() degrades to
    # a no-op context (one flag check), nothing is recorded or flushed,
    # and the collective straggler detector stops attributing waits.
    "no_steptrace": False,
    # Kill switch for the serve-plane request observatory: record()
    # degrades to one flag check, no lifecycle ring is ever
    # constructed, nothing piggybacks on the metrics flush —
    # exact-legacy behavior with zero rings and zero extra threads.
    "no_reqtrace": False,
    # Kill switch for the RPC/transport observatory: zero rpc/ring/chaos
    # series constructed, no slow-RPC watchdog ring, no frame-meta trace
    # propagation — exact-legacy frames on the wire, so mixed on/off
    # processes interoperate.
    "no_rpc_metrics": False,
    # --- event-loop stall sanitizer (_internal/lint/loopstall.py) ---
    # Armed together with the lock-order sanitizer (RTPU_SANITIZE=1):
    # any single callback that holds a ray_tpu-owned event loop longer
    # than this budget is recorded with its creation site. 0 disables
    # recording even when sanitized.
    "loopstall_budget_ms": 50.0,
    # --- overrides re-read from the environment at their use site
    # (tests monkeypatch them after CONFIG construction; registered here
    # so L003 can resolve the names) ---
    # Force the pure-asyncio RPC transport even when fastrpc built
    # (fastrpc.py reads the env at attach time).
    "disable_native_rpc": False,
    # Container runtime binary for image_uri runtime envs ("" = autodetect).
    "container_runtime": "",
    # TPU chip count override (0 = autodetect).
    "num_tpu_chips": 0,
    # Bind host for the device-object transfer server.
    "transfer_host": "127.0.0.1",
}

_ENV_PREFIX = "RTPU_"

# Process-plumbing environment variables: per-process bootstrap channel
# (raylet -> worker) and tooling gates, NOT tunable config flags — they
# carry identities/addresses, so they have no sensible default row in
# _DEFAULTS. rtpulint L003 resolves RTPU_* env reads against _DEFAULTS
# first, then this set.
BOOTSTRAP_ENV = frozenset({
    "RTPU_WORKER_ID", "RTPU_SESSION", "RTPU_NODE_ID", "RTPU_NODE_INDEX",
    "RTPU_RAYLET_ADDR", "RTPU_GCS_ADDR", "RTPU_WORKER_PROFILE",
    "RTPU_SANITIZE", "RTPU_NATIVE_CACHE", "RTPU_NATIVE_DEBUG",
})


def _coerce(value: str, default: Any) -> Any:
    if isinstance(default, bool):
        return value.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(value)
    if isinstance(default, float):
        return float(value)
    if isinstance(default, (dict, list)):
        return json.loads(value)
    return value


class _Config:
    def __init__(self):
        self._lock = threading.Lock()
        self._values = dict(_DEFAULTS)
        self._load_env()

    def _load_env(self):
        for name, default in _DEFAULTS.items():
            # Canonical spelling is RTPU_<NAME> (uppercase — what the
            # docs, tests, and kill-switch runbooks use); the historical
            # exact-case form is honored as a fallback. Before this,
            # uppercase overrides of lowercase flag names silently did
            # nothing (e.g. the RTPU_TESTING_RPC_FAILURE chaos spec
            # never reached CONFIG in spawned workers).
            env = os.environ.get(_ENV_PREFIX + name.upper())
            if env is None:
                env = os.environ.get(_ENV_PREFIX + name)
            if env is not None:
                self._values[name] = _coerce(env, default)

    def __getattr__(self, name: str) -> Any:
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(f"unknown config flag: {name}") from None

    def get(self, name: str, default: Any = None) -> Any:
        return self._values.get(name, default)

    def known_flags(self):
        """Registered flag names (for rtpulint L003 and tooling)."""
        return frozenset(_DEFAULTS)

    def apply_system_config(self, overrides: Dict[str, Any]):
        with self._lock:
            for name, value in overrides.items():
                if name not in _DEFAULTS:
                    raise ValueError(f"unknown config flag: {name}")
                self._values[name] = value

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._values)

    def reset(self):
        with self._lock:
            self._values = dict(_DEFAULTS)
            self._load_env()


CONFIG = _Config()
