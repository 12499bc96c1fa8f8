"""Accelerator observability plane: the device leg of the
observability quartet (PR 1 time, PR 3 memory, PR 5 CPU, this module
the accelerator itself).

Three concerns, one per-process module:

- **Device snapshots** — per-local-device HBM accounting via
  ``device.memory_stats()`` (TPU/GPU backends), with a
  ``live_buffers``-equivalent fallback that sums the addressable shard
  bytes of every live ``jax.Array`` per device — so the CPU backend
  (where ``memory_stats()`` is ``None``) reports real numbers and the
  whole plane is testable without hardware. Peak bytes are tracked as a
  process-lifetime watermark when the backend doesn't report one.

- **XLA compile tracking** — ``jax.monitoring`` listeners accumulate
  compile counts, cumulative compile seconds (all ``/jax/core/compile``
  phases), a per-function histogram (attributed to the nearest
  non-JAX caller frame, the PR-3 callsite idiom — compiles are rare and
  slow, a stack walk is noise), and compilation-cache hit/miss
  counters. Surfaced as ``rtpu_xla_compile_seconds_total`` /
  ``rtpu_xla_compiles_total`` / ``rtpu_xla_cache_{hits,misses}_total``.

- **Step telemetry** — :class:`StepTimer` / :func:`report_step` emit
  step-time histograms, tokens/s, an achieved-FLOP/s → MFU gauge
  (denominator from the shared ``accelerators.flops`` table), and
  goodput accounting that splits wall time into compile /
  device-compute / host-blocked buckets
  (``rtpu_goodput_seconds_total{bucket=...}``). Wired into the train
  controller's report fold, the paged-engine decode tick, and bench.py.
  ``StepTimer.phase(name)`` splits a step into named intervals that
  are also spans on the profiler's clock; the paged engine's whole
  continuous tick is the kind ``tick``, tiled by ten of them. A
  :class:`StepAccumulator` also keeps the DISTRIBUTION of its steps'
  extents (``extent_hist``) and the steps that took several times the
  usual (``slow``), each with what paused the process meanwhile
  (:func:`note_pause` / :class:`pause`: the collector, a metrics flush,
  a compile, a fetch that waits for the device). One that is asked to
  (``timeline=True``: the engine's ``tick``) keeps each of its flushes,
  stamped, for ten minutes (``timeline``), and a :class:`DryWatch` on
  the step's timer accounts for the time the device had run out of work
  (``dry_*``: how long, in how many gaps, under which phase).

JAX is never imported by this module at module scope. The compile
listeners arm once the process has imported jax; device snapshots only
touch JAX in a process whose backend is ALREADY initialised
(:func:`backend_initialized`) unless the caller forces it — a driver
that imported jax to build a config holds no chip, and opening the
backend from an observability sweep would take the chip away from its
own workers (see accelerators/tpu.py). ``force_jax=True`` is reserved
for the process the user is driving (cli devices / accel_summary
caller).

Kill switch: ``RTPU_NO_ACCEL_METRICS=1`` — zero listeners installed,
snapshots return empty, StepTimer/report_step/note_pause become no-ops
and no ``gc.callbacks`` hook is installed.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import gc
import itertools
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .config import CONFIG

logger = logging.getLogger(__name__)

_JAX_COMPILE_PREFIX = "/jax/core/compile"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_COMPILE_BOUNDARIES = [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                       10.0, 30.0, 60.0, 300.0]
_STEP_BOUNDARIES = [0.0001, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 60.0]


def accel_disabled() -> bool:
    return bool(CONFIG.no_accel_metrics)


# getpid() is a real syscall on every call and this container class
# (sandboxed kernels) makes syscalls ~100x pricier than a dict lookup —
# cache the tag string once per process (modules import post-spawn, so
# the cache can't leak across processes).
_pid_cache: List[Optional[str]] = [None]


def _pid() -> str:
    pid = _pid_cache[0]
    if pid is None:
        pid = _pid_cache[0] = str(os.getpid())
    return pid


# ---------------------------------------------------------------------------
# metric series (L004: one LazyMetrics factory, literal names)
# ---------------------------------------------------------------------------


def _build_accel_metrics():
    from types import SimpleNamespace

    from ..util.metrics import Counter, Gauge, Histogram
    return SimpleNamespace(
        # gauges carry pid+device: per-process series, last-write-wins
        # per tag tuple on the cross-process merge (see runtime_metrics)
        hbm_used=Gauge(
            "rtpu_accel_hbm_used_bytes",
            "HBM bytes in use on one local device (memory_stats, "
            "or live-buffer sum on backends without it)",
            tag_keys=("pid", "device")),
        hbm_peak=Gauge(
            "rtpu_accel_hbm_peak_bytes",
            "Peak HBM bytes on one local device (backend-reported, "
            "or a process-lifetime snapshot watermark)",
            tag_keys=("pid", "device")),
        hbm_limit=Gauge(
            "rtpu_accel_hbm_limit_bytes",
            "HBM capacity of one local device (0 when the backend "
            "does not report a limit)",
            tag_keys=("pid", "device")),
        compiles=Counter(
            "rtpu_xla_compiles_total",
            "XLA backend compilations performed by this process"),
        compile_seconds=Counter(
            "rtpu_xla_compile_seconds_total",
            "Cumulative seconds spent in jax trace/lower/backend "
            "compile phases"),
        compile_hist=Histogram(
            "rtpu_xla_compile_seconds",
            "Per-compilation backend_compile duration",
            boundaries=_COMPILE_BOUNDARIES),
        cache_hits=Counter(
            "rtpu_xla_cache_hits_total",
            "XLA compilation-cache hits observed via jax.monitoring"),
        cache_misses=Counter(
            "rtpu_xla_cache_misses_total",
            "XLA compilation-cache misses observed via jax.monitoring"),
        step_time=Histogram(
            "rtpu_step_time_seconds",
            "Wall time of one accelerator step (train step / decode "
            "tick / bench step)",
            boundaries=_STEP_BOUNDARIES,
            tag_keys=("kind",)),
        step_tokens=Counter(
            "rtpu_step_tokens_total",
            "Tokens processed by reported steps",
            tag_keys=("kind",)),
        tokens_per_sec=Gauge(
            "rtpu_step_tokens_per_sec",
            "Smoothed tokens/s of reported steps (EWMA)",
            tag_keys=("pid", "kind")),
        mfu=Gauge(
            "rtpu_step_mfu",
            "Achieved-FLOP/s / peak-FLOP/s of reported steps "
            "(denominator: accelerators.flops.PEAK_FLOPS)",
            tag_keys=("pid", "kind")),
        goodput=Counter(
            "rtpu_goodput_seconds_total",
            "Reported step wall time split into compile / "
            "device-compute / comm (host-plane collectives) / "
            "host-blocked buckets",
            tag_keys=("kind", "bucket")),
    )


from ..util.metrics import LazyMetrics  # noqa: E402 — after _build def

accel_metrics = LazyMetrics(_build_accel_metrics)


# ---------------------------------------------------------------------------
# XLA compile tracking (jax.monitoring listeners)
# ---------------------------------------------------------------------------


class _CompileTracker:
    """Accumulates jax.monitoring compile/cache events. One per process;
    listeners fire synchronously on whatever thread compiles, so all
    mutation happens under one uncontended lock (compiles are rare)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.installed = False
        self.compiles = 0
        self.compile_seconds = 0.0
        # backend_compile only: these spans are disjoint wall time
        # (trace/lower events NEST under outer traces, so their sum can
        # exceed the wall clock of an enclosing region — fine for a
        # cumulative counter, wrong for a goodput split)
        self.backend_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        # event name -> count (every /jax/ event, for the raw view)
        self.events: Dict[str, int] = {}
        # attribution -> {count, seconds} (backend compiles only)
        self.per_function: Dict[str, Dict[str, float]] = {}

    def summary(self) -> Dict[str, Any]:
        with self.lock:
            per_fn = sorted(
                ({"function": k, **v} for k, v in self.per_function.items()),
                key=lambda r: -r["seconds"])
            return {
                "installed": self.installed,
                "compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 6),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "events": dict(self.events),
                "per_function": per_fn[:50],
            }


_TRACKER = _CompileTracker()


def _attribute_compile() -> str:
    """Nearest caller frame outside jax/jaxlib/this module: the
    user-facing name a compile bills to (cheap relative to the compile
    itself — same tradeoff as the PR-3 put()/submit callsite capture)."""
    try:
        f = sys._getframe(2)
        while f is not None:
            fn = f.f_code.co_filename
            if ("/jax/" not in fn and "/jaxlib/" not in fn
                    and not fn.endswith("_internal/accel.py")
                    and not fn.endswith("contextlib.py")
                    and "importlib" not in fn):
                return (f"{f.f_code.co_name} "
                        f"({os.path.basename(fn)}:{f.f_lineno})")
            f = f.f_back
    except Exception:  # noqa: BLE001 — attribution is best-effort
        logger.debug("compile attribution walk failed", exc_info=True)
    return "<unknown>"


def _on_duration_event(event: str, duration_s: float, **_kw):
    # A raise here would propagate into jax's monitoring dispatch MID
    # COMPILE — the listener must never break user code.
    try:
        if not event.startswith(_JAX_COMPILE_PREFIX):
            return
        metrics = accel_metrics()
        metrics.compile_seconds.inc(float(duration_s))
        tracker = _TRACKER
        if event == _BACKEND_COMPILE_EVENT:
            site = _attribute_compile()
            metrics.compiles.inc()
            metrics.compile_hist.observe(float(duration_s))
            with tracker.lock:
                tracker.compiles += 1
                tracker.compile_seconds += float(duration_s)
                tracker.backend_seconds += float(duration_s)
                tracker.events[event] = tracker.events.get(event, 0) + 1
                agg = tracker.per_function.setdefault(
                    site, {"count": 0, "seconds": 0.0})
                agg["count"] += 1
                agg["seconds"] += float(duration_s)
            # the event comes when the compile ends: no span of its own
            # (XLA's are in the trace already)
            now = time.monotonic()
            note_pause("compile", now - float(duration_s), now)
        else:
            with tracker.lock:
                tracker.compile_seconds += float(duration_s)
                tracker.events[event] = tracker.events.get(event, 0) + 1
    except Exception:  # noqa: BLE001 — observability must not raise
        logger.debug("compile duration listener failed", exc_info=True)


def _on_event(event: str, **_kw):
    try:
        tracker = _TRACKER
        hit = "cache_hit" in event
        miss = "cache_miss" in event
        with tracker.lock:
            tracker.events[event] = tracker.events.get(event, 0) + 1
            if hit:
                tracker.cache_hits += 1
            elif miss:
                tracker.cache_misses += 1
        if hit:
            accel_metrics().cache_hits.inc()
        elif miss:
            accel_metrics().cache_misses.inc()
    except Exception:  # noqa: BLE001 — observability must not raise
        logger.debug("compile event listener failed", exc_info=True)


def ensure_installed() -> bool:
    """Install the jax.monitoring listeners once per process. Returns
    False — and installs NOTHING — under the kill switch or when jax
    isn't importable. Idempotent and cheap once installed."""
    if accel_disabled():
        return False
    tracker = _TRACKER
    if tracker.installed:
        return True
    # Import OUTSIDE tracker.lock: the post-import hook runs
    # ensure_installed while HOLDING jax's module import lock, so a
    # concurrent caller that held tracker.lock across this import
    # (blocking on that same import lock) would deadlock the pair.
    try:
        from jax import monitoring
    except Exception:  # noqa: BLE001 — jax genuinely unavailable
        logger.debug("jax.monitoring unavailable", exc_info=True)
        return False
    with tracker.lock:
        if tracker.installed:
            return True
        monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        monitoring.register_event_listener(_on_event)
        tracker.installed = True
    return True


def maybe_install() -> bool:
    """Task-boundary fast path: arm the listeners iff jax is already
    imported in this process. Two dict probes when already installed
    (or jax absent) — cheap enough for the executor's per-task call."""
    if _TRACKER.installed:
        return True
    if "jax" not in sys.modules:
        return False
    return ensure_installed()


class _JaxPostImportHook:
    """Meta-path watcher that arms the compile listeners the moment
    ``import jax`` COMPLETES anywhere in this process — the only way to
    count a process's FIRST compile, which usually happens inside the
    first task body, before any accel entry point runs. Inert for every
    other import (one string compare), removes itself after firing."""

    def find_spec(self, fullname, path=None, target=None):
        if fullname != "jax" or _TRACKER.installed:
            return None
        import importlib.machinery  # noqa: F401 — finders below need it
        for finder in sys.meta_path:
            if finder is self or not hasattr(finder, "find_spec"):
                continue
            spec = finder.find_spec(fullname, path, target)
            if spec is None or spec.loader is None:
                continue
            orig_exec = spec.loader.exec_module

            def exec_module(module, _orig=orig_exec):
                _orig(module)
                # jax/__init__ has fully executed; sys.modules["jax"]
                # is set, so registering listeners is safe now.
                try:
                    ensure_installed()
                except Exception:  # noqa: BLE001 — import must win
                    logger.debug("post-import accel install failed",
                                 exc_info=True)
                try:
                    sys.meta_path.remove(_IMPORT_HOOK)
                except ValueError:
                    pass

            spec.loader.exec_module = exec_module
            return spec
        return None


_IMPORT_HOOK = _JaxPostImportHook()


def install_import_hook() -> bool:
    """Called once at process boot (CoreWorker/raylet/GCS init). If jax
    is already imported, installs directly; otherwise registers the
    post-import watcher. Under the kill switch NOTHING is registered —
    not even the (inert) finder."""
    if accel_disabled():
        return False
    if maybe_install():
        return True
    if _IMPORT_HOOK not in sys.meta_path:
        # FRONT of meta_path: PathFinder would otherwise resolve jax
        # before this finder is ever consulted (find_spec delegates to
        # the rest of the chain, so ordering costs nothing).
        sys.meta_path.insert(0, _IMPORT_HOOK)
    return True


def _unregister(modules, names, callback) -> bool:
    """Take one of this module's listeners out of jax.monitoring, by the
    public name jax 0.9 has or the private one older jax had. False when
    neither exists or jax refused (it asserts the callback is there)."""
    unregister = next((getattr(m, n) for m, n in zip(modules, names)
                       if hasattr(m, n)), None)
    if unregister is None:
        logger.debug("jax.monitoring has none of %s", names)
        return False
    try:
        unregister(callback)
    except Exception:  # noqa: BLE001 — not registered, or the API moved
        logger.debug("jax.monitoring %s failed", names[0], exc_info=True)
        return False
    return True


def uninstall() -> None:
    """Listener removal (tests). ``installed`` turns False only when
    both callbacks are really gone: one jax still holds would be
    registered a SECOND time by the next ensure_installed(), every
    compile second counted twice and the device bucket clipped to 0."""
    try:  # import OUTSIDE the lock (see ensure_installed)
        import jax.monitoring as public
        from jax._src import monitoring as private
    except Exception:  # noqa: BLE001 — jax genuinely unavailable
        logger.debug("jax.monitoring unavailable", exc_info=True)
        return
    modules = (public, private)
    tracker = _TRACKER
    with tracker.lock:
        if not tracker.installed:
            return
        gone = _unregister(
            modules, ("unregister_event_duration_listener",
                      "_unregister_event_duration_listener_by_callback"),
            _on_duration_event)
        gone = _unregister(
            modules, ("unregister_event_listener",
                      "_unregister_event_listener_by_callback"),
            _on_event) and gone
        if gone:
            tracker.installed = False


def compile_seconds_total() -> float:
    with _TRACKER.lock:
        return _TRACKER.compile_seconds


def backend_compile_seconds_total() -> float:
    """Disjoint backend-compile wall seconds — what StepTimer's goodput
    split subtracts (see _CompileTracker.backend_seconds)."""
    with _TRACKER.lock:
        return _TRACKER.backend_seconds


def compile_summary() -> Dict[str, Any]:
    return _TRACKER.summary()


# ---------------------------------------------------------------------------
# device snapshots
# ---------------------------------------------------------------------------

# device id -> peak bytes watermark, for backends whose memory_stats()
# is None (CPU) or lacks peak_bytes_in_use.
_hbm_peak_seen: Dict[int, int] = {}
_PEAK_LOCK = threading.Lock()


def _live_buffer_bytes_by_device() -> Dict[int, int]:
    """live_buffers()-equivalent: sum every live jax.Array's addressable
    shard bytes per device. Exact for committed arrays; the fallback
    that makes the CPU backend report real HBM numbers."""
    import jax

    per_dev: Dict[int, int] = {}
    for arr in jax.live_arrays():
        try:
            for shard in arr.addressable_shards:
                dev_id = shard.device.id
                per_dev[dev_id] = per_dev.get(dev_id, 0) + \
                    int(shard.data.nbytes)
        except Exception:  # noqa: BLE001 — arrays can be deleted mid-walk
            logger.debug("live-array walk skipped one array",
                         exc_info=True)
    return per_dev


def backend_initialized() -> bool:
    """True once this process has opened a JAX backend (and so holds
    whatever chips it was given). Importing jax does not count."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def snapshot_devices(force_jax: bool = False) -> List[Dict[str, Any]]:
    """One row per local device: identity, HBM used/peak/limit, and the
    peak-FLOPs denominator. Empty when disabled, or when this process
    has not opened a backend (doing so from an observability sweep
    would grab the TPU chip lock) unless ``force_jax``."""
    if accel_disabled():
        return []
    if not force_jax and not backend_initialized():
        return []
    import jax

    from ..accelerators.flops import peak_flops

    ensure_installed()
    rows: List[Dict[str, Any]] = []
    live = None  # computed once, only if some device lacks memory_stats
    for dev in jax.local_devices():
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:  # noqa: BLE001 — backend-dependent API
            logger.debug("memory_stats failed on %s", dev, exc_info=True)
        if stats:
            used = int(stats.get("bytes_in_use", 0))
            peak = int(stats.get("peak_bytes_in_use", 0))
            limit = int(stats.get("bytes_limit", 0))
            source = "memory_stats"
        else:
            if live is None:
                live = _live_buffer_bytes_by_device()
            used = live.get(dev.id, 0)
            peak = 0
            limit = 0
            source = "live_buffers"
        with _PEAK_LOCK:
            watermark = max(_hbm_peak_seen.get(dev.id, 0), used, peak)
            _hbm_peak_seen[dev.id] = watermark
        rows.append({
            "index": dev.id,
            "process_index": dev.process_index,
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", dev.platform),
            "hbm_used_bytes": used,
            "hbm_peak_bytes": watermark,
            "hbm_limit_bytes": limit,
            "source": source,
            "peak_flops": peak_flops(dev),
        })
    metrics = accel_metrics()
    pid = _pid()
    for row in rows:
        tags = {"pid": pid, "device": str(row["index"])}
        metrics.hbm_used.set(row["hbm_used_bytes"], tags=tags)
        metrics.hbm_peak.set(row["hbm_peak_bytes"], tags=tags)
        metrics.hbm_limit.set(row["hbm_limit_bytes"], tags=tags)
    return rows


# Rate limit: one DEVICE_MEMORY_PRESSURE event per device per interval.
_pressure_last_emit: Dict[Any, float] = {}
_PRESSURE_LOCK = threading.Lock()


def check_pressure(rows: List[Dict[str, Any]],
                   watermark: Optional[float] = None
                   ) -> List[Dict[str, Any]]:
    """Device rows above the HBM watermark, rate-limited per device —
    the caller emits these as DEVICE_MEMORY_PRESSURE events into the
    GCS event log (the emission path differs by thread context)."""
    if watermark is None:
        watermark = CONFIG.accel_hbm_watermark
    out = []
    now = time.monotonic()
    for row in rows:
        limit = row.get("hbm_limit_bytes") or 0
        if limit <= 0:
            continue
        ratio = row["hbm_used_bytes"] / limit
        if ratio < watermark:
            continue
        key = row["index"]
        with _PRESSURE_LOCK:
            last = _pressure_last_emit.get(key, 0.0)
            if now - last < CONFIG.accel_pressure_min_interval_s:
                continue
            _pressure_last_emit[key] = now
        out.append({
            "device": row["index"],
            "device_kind": row["device_kind"],
            "hbm_used_bytes": row["hbm_used_bytes"],
            "hbm_limit_bytes": limit,
            "used_ratio": round(ratio, 4),
        })
    return out


def emit_pressure_event(message: str, fields: Optional[Dict[str, Any]]
                        = None) -> bool:
    """Best-effort DEVICE_MEMORY_PRESSURE publish from a USER thread
    (sync GCS bridge — never call from an io loop; async handlers
    schedule ``gcs.call("add_event", ...)`` themselves)."""
    try:
        from .core_worker import try_get_core_worker
        worker = try_get_core_worker()
        if worker is None:
            return False
        worker.gcs.call_sync(
            "add_event", event_type="DEVICE_MEMORY_PRESSURE",
            message=message, severity="WARNING",
            fields=dict(fields or {}, pid=os.getpid()), timeout=5)
        return True
    except Exception:  # noqa: BLE001 — observability is best-effort
        logger.debug("DEVICE_MEMORY_PRESSURE emit failed", exc_info=True)
        return False


# ---------------------------------------------------------------------------
# step telemetry (StepTimer / report_step) + goodput accounting
# ---------------------------------------------------------------------------

# kind -> fold of every reported step in this process
_step_stats: Dict[str, Dict[str, Any]] = {}
_STEP_LOCK = threading.Lock()
_EWMA_ALPHA = 0.2

# kind -> the 6 tag dicts report_step passes to metric ops, built once
# (report_step rides the decode tick — per-call dict builds showed up)
_step_tag_cache: Dict[str, Dict[str, Dict[str, str]]] = {}


def _step_tags(kind: str) -> Dict[str, Dict[str, str]]:
    tags = _step_tag_cache.get(kind)
    if tags is None:
        pid = _pid()
        tags = _step_tag_cache[kind] = {
            "kind": {"kind": kind},
            "compile": {"kind": kind, "bucket": "compile"},
            "device": {"kind": kind, "bucket": "device"},
            "comm": {"kind": kind, "bucket": "comm"},
            "host": {"kind": kind, "bucket": "host"},
            "pid_kind": {"pid": pid, "kind": kind},
        }
    return tags


_device_kind_cache: List[Optional[str]] = [None]


def _default_device_kind() -> str:
    """device_kind of local device 0, cached once a backend is open;
    "cpu" in a process that has none (a metrics fold must not
    initialize one)."""
    kind = _device_kind_cache[0]
    if kind is None:
        if not backend_initialized():
            return "cpu"
        import jax
        kind = getattr(jax.local_devices()[0], "device_kind", "cpu")
        _device_kind_cache[0] = kind
    return kind


def _sum_phases(total: Dict[Any, float],
                part: Optional[Dict[Any, float]]) -> None:
    """Add a step's seconds by phase name into a running fold."""
    if part:
        for name, seconds in part.items():
            total[name] = total.get(name, 0.0) + seconds


# A step's EXTENT is its wall plus what `StepTimer.outside()` added (the
# paged engine's visit: `between` + the visit). Upper edges of the
# buckets a StepAccumulator counts extents into: four a doubling from
# 1 ms to 2.048 s; bucket 0 is what lies under the first edge, the last
# what lies over the last.
_EXTENT_EDGES = [0.001 * 2.0 ** (i / 4.0) for i in range(45)]
# A step is SLOW, and kept whole, when its extent passes this multiple
# of the accumulator's running median extent. A visit of the paged
# engine that carries a prefill chunk is about twice a plain one, and
# one that also finishes a prompt reaches 3-4 x on the chat cell (44-64
# ms against 14.6; PERF.md §6, builder's, PR 38): neither is a stall. A
# collector pass or a profiler's stop is 7-25 x.
_SLOW_FACTOR = 4.0
# The running median moves towards each step's extent by the share 1/n
# of itself for the accumulator's first steps (a hundred steps bring it
# down from a first step that compiled), then by this share: steady to
# ~3 %, and a burst of eight odd steps moves it by a fifth.
_TYPICAL_GAIN = 1.0 / 32.0
# A StepAccumulator that keeps a timeline keeps every flush that ended in
# the newest this many seconds (`step_summary()`, `timeline`): a reader's
# two marks have been up to 200 s apart.
_TIMELINE_KEEP_S = 600.0
# Upper edges of the buckets a dry gap's length is counted into: the
# scale of `_EXTENT_EDGES`, from 0.09 ms on.
_GAP_EDGES = [0.001 * 2.0 ** (i / 4.0) for i in range(-14, 45)]
# what stopped the process for less explains no slow step
_PAUSE_MIN_S = 0.001
_PAUSE_KEEP = 256

# The newest pauses of this process, (what, start, end) on
# time.monotonic(): a ring written by whatever thread paused (one slot
# store a stamp; the collector's hook may run inside any allocation, so
# nothing here takes a lock or grows a container).
_pauses: List[Optional[Tuple[str, float, float]]] = [None] * _PAUSE_KEEP
_pause_seq = itertools.count()


def note_pause(what: str, t0: float, t1: float) -> None:
    """Something held this process (or its GIL, or its stepping thread)
    from ``t0`` to ``t1`` on ``time.monotonic()``. A slow step lists the
    pauses that overlap it (``step_summary()``, ``slow[*].pauses``).
    Pauses under 1 ms are dropped; a no-op under the kill switch."""
    if t1 - t0 >= _PAUSE_MIN_S and not accel_disabled():
        _pauses[next(_pause_seq) % _PAUSE_KEEP] = (what, t0, t1)


def _tracing() -> bool:
    """Whether a ``jax.profiler`` trace runs in this process, told from
    the profiler's own state where this jax has it (one attribute read);
    True where it cannot be told: a span outside a trace costs ~1 us."""
    profiler = sys.modules.get("jax._src.profiler")
    if profiler is None:
        return False
    try:
        return profiler._profile_state.profile_session is not None
    except AttributeError:
        return True


def _traced_span(name: str):
    """The span ``name``, entered, while a profiler trace runs; else
    None: outside a trace no span is built."""
    if _tracing():
        span = _annotation(name)
        if span is not None:
            span.__enter__()
            return span
    return None


class pause:
    """``with accel.pause("metrics_flush"):`` — :func:`note_pause` of
    the block, and while a profiler trace runs the span ``pause/<what>``
    on the thread that paused, from start to end: in an ``.xplane.pb`` an
    idle gap of the device then lies under the ``tick/<phase>`` it fell
    in and beside the ``pause/*`` that caused it."""

    __slots__ = ("_what", "_t0", "_span")

    def __init__(self, what: str):
        self._what = what
        self._t0 = 0.0
        self._span = None

    def __enter__(self):
        if not accel_disabled():
            self._t0 = time.monotonic()
            self._span = _traced_span("pause/" + self._what)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
            self._span = None
        if self._t0:
            note_pause(self._what, self._t0, time.monotonic())
        return False


_GC_WHAT = ("gc0", "gc1", "gc2")
# start and span of the collection under way (the collector runs one at a
# time, on whatever thread allocates)
_gc_open: List[Any] = [0.0, None]


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    # runs inside the collector, at every pass of every generation: two
    # clock reads and a comparison unless the pass took over 1 ms
    if phase == "start":
        _gc_open[0] = time.monotonic()
        _gc_open[1] = _traced_span(
            "pause/" + _GC_WHAT[info["generation"]])
    else:
        span = _gc_open[1]
        if span is not None:
            _gc_open[1] = None
            span.__exit__(None, None, None)
        note_pause(_GC_WHAT[info["generation"]], _gc_open[0],
                   time.monotonic())


def watch_gc() -> bool:
    """Stamp every garbage collection of this process as a pause
    ``gc<generation>`` (idempotent; nothing is installed under the kill
    switch). A serving replica calls it where it freezes its heap."""
    if accel_disabled():
        return False
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return True


def _overlapping(start: float, end: float) -> List[Dict[str, Any]]:
    """The stamped pauses that overlap [start, end], oldest first: what,
    the overlap (``t0`` to ``t1``, ``seconds``) and the whole pause's
    length (``pause_s``). Pauses may overlap each other (a flush holds
    its encodes), so their seconds do not add: take the union."""
    out = []
    for what, t0, t1 in sorted(filter(None, _pauses[:]),
                               key=lambda stamp: stamp[1]):
        lo, hi = max(start, t0), min(end, t1)
        if hi > lo:
            out.append({"what": what, "t0": lo, "t1": hi,
                        "seconds": hi - lo, "pause_s": t1 - t0})
    return out


def report_step(kind: str, wall_s: float, tokens: int = 0,
                device_s: float = 0.0, compile_s: float = 0.0,
                flops: float = 0.0,
                device_kind: Optional[str] = None,
                steps: int = 1,
                comm_s: float = 0.0,
                phases: Optional[Dict[str, float]] = None,
                cpu_s: float = 0.0,
                counters: Optional[Dict[str, float]] = None,
                phases_cpu: Optional[Dict[str, float]] = None,
                extent_hist: Optional[Dict[int, int]] = None,
                slow: Optional[List[Dict[str, Any]]] = None,
                end: Optional[float] = None, extent_s: float = 0.0,
                dry_by_phase: Optional[Dict[str, float]] = None,
                dry_gap_hist: Optional[Dict[int, int]] = None,
                dry_gap_max_s: float = 0.0
                ) -> Optional[Dict[str, float]]:
    """Fold one step (or ``steps`` uniform steps) into the process's
    step telemetry: step-time histogram, tokens/s EWMA gauge, MFU gauge
    (``flops`` = total FLOPs the interval performed, divided by wall
    and the shared peak table), and the compile/device/comm/host
    goodput split (``comm_s`` = host-plane collective time, so
    comm-bound and compute-bound steps are distinguishable;
    host-blocked = wall − compile − device − comm). ``phases`` (a
    StepTimer's named intervals, seconds by name) and ``cpu_s`` (the
    stepping thread's own CPU seconds) are summed into the kind's
    ``step_summary()`` row as they come, and so are ``counters`` (a
    StepTimer's ``count()``: events by name, whatever the step's owner
    counts), ``phases_cpu`` (the thread's CPU seconds inside each
    phase) and a StepAccumulator's ``extent_hist`` (steps by bucket of
    ``_EXTENT_EDGES``) and ``slow`` steps, which gain here the pauses
    stamped since (`note_pause`) that overlap them, and a DryWatch's
    ``dry_by_phase`` (seconds the device was dry, by the phase they lay
    under), ``dry_gap_hist`` (gaps by bucket of ``_GAP_EDGES``) and
    ``dry_gap_max_s`` (the longest of them). With
    ``end`` (``time.monotonic()`` at the flush of an accumulator that keeps
    a timeline; ``extent_s``: the steps' extents summed) the call is also
    kept as it came, one row of the kind's ``timeline``, for
    ``_TIMELINE_KEEP_S``. Returns the derived numbers, or None when the
    plane is disabled."""
    if accel_disabled() or wall_s <= 0:
        return None
    metrics = accel_metrics()
    per_step = wall_s / max(1, steps)
    tags = _step_tags(kind)
    if steps == 1:
        metrics.step_time.observe(per_step, tags=tags["kind"])
    else:
        # aggregated interval: observe the mean once per reported step
        # (bounded — an interval never unrolls into thousands of
        # histogram appends)
        for _ in range(min(steps, 64)):
            metrics.step_time.observe(per_step, tags=tags["kind"])
    compile_s = max(0.0, min(compile_s, wall_s))
    device_s = max(0.0, min(device_s, wall_s - compile_s))
    comm_s = max(0.0, min(comm_s, wall_s - compile_s - device_s))
    host_s = max(0.0, wall_s - compile_s - device_s - comm_s)
    if compile_s:
        metrics.goodput.inc(compile_s, tags=tags["compile"])
    if device_s:
        metrics.goodput.inc(device_s, tags=tags["device"])
    if comm_s:
        metrics.goodput.inc(comm_s, tags=tags["comm"])
    if host_s:
        metrics.goodput.inc(host_s, tags=tags["host"])
    tokens_per_s = None
    if tokens:
        metrics.step_tokens.inc(tokens, tags=tags["kind"])
        tokens_per_s = tokens / wall_s
    mfu = None
    if flops:
        from ..accelerators.flops import peak_flops_for_kind
        peak = peak_flops_for_kind(device_kind or _default_device_kind())
        mfu = (flops / wall_s) / peak
        metrics.mfu.set(mfu, tags=tags["pid_kind"])
    with _STEP_LOCK:
        agg = _step_stats.get(kind)
        if agg is None:   # built once a kind, not once a call
            agg = _step_stats[kind] = {
                "steps": 0, "wall_s": 0.0, "tokens": 0,
                "compile_s": 0.0, "device_s": 0.0, "comm_s": 0.0,
                "host_s": 0.0, "tokens_per_s": 0.0, "mfu": 0.0,
                "cpu_s": 0.0, "phases": {}, "counters": {},
                "phases_cpu": {}, "extent_hist": {},
                "slow_total": 0, "slow_seconds": 0.0,
                "dry_by_phase": {}, "dry_gap_hist": {},
                "dry_gap_max_s": 0.0,
                "timeline": None}
        agg["steps"] += steps
        agg["wall_s"] += wall_s
        agg["tokens"] += tokens
        agg["compile_s"] += compile_s
        agg["device_s"] += device_s
        agg["comm_s"] += comm_s
        agg["host_s"] += host_s
        agg["cpu_s"] += cpu_s
        _sum_phases(agg["phases"], phases)
        _sum_phases(agg["counters"], counters)
        _sum_phases(agg["phases_cpu"], phases_cpu)
        _sum_phases(agg["extent_hist"], extent_hist)
        _sum_phases(agg["dry_by_phase"], dry_by_phase)
        _sum_phases(agg["dry_gap_hist"], dry_gap_hist)
        if dry_gap_max_s > agg["dry_gap_max_s"]:
            agg["dry_gap_max_s"] = dry_gap_max_s
        for step in slow or ():
            step["pauses"] = _overlapping(
                step["end"] - step["extent_s"], step["end"])
            agg["slow_total"] += 1
            agg["slow_seconds"] += step["extent_s"]
        if end is not None:
            ring = agg["timeline"]
            if ring is None:
                ring = agg["timeline"] = collections.deque()
            # the caller's dicts, kept: a flush hands them over
            ring.append({
                "end": end, "steps": steps, "wall_s": wall_s,
                "extent_s": extent_s, "cpu_s": cpu_s,
                "phases": phases or {}, "phases_cpu": phases_cpu or {},
                "counters": counters or {},
                "extent_hist": extent_hist or {}, "slow": slow or [],
                "dry_by_phase": dry_by_phase or {},
                "dry_gap_hist": dry_gap_hist or {},
                "dry_gap_max_s": dry_gap_max_s})
            while ring[0]["end"] < end - _TIMELINE_KEEP_S:
                ring.popleft()
        if tokens_per_s is not None:
            prev = agg["tokens_per_s"]
            agg["tokens_per_s"] = tokens_per_s if not prev else \
                prev + _EWMA_ALPHA * (tokens_per_s - prev)
            metrics.tokens_per_sec.set(
                agg["tokens_per_s"], tags=tags["pid_kind"])
        if mfu is not None:
            agg["mfu"] = mfu
    return {"wall_s": wall_s, "tokens_per_s": tokens_per_s or 0.0,
            "mfu": mfu or 0.0, "compile_s": compile_s,
            "device_s": device_s, "comm_s": comm_s, "host_s": host_s}


def _hist_row(edges: List[float], hist: Dict[int, int]) -> Dict[str, Any]:
    return {"edges_s": list(edges),
            "counts": [int(hist.get(i, 0)) for i in range(len(edges) + 1)]}


def step_summary() -> List[Dict[str, Any]]:
    """Per-kind fold of every step this process reported, cumulative: a
    window is closed − opened. A kind folded through a StepAccumulator that
    keeps a timeline has these keys more. ``timeline``: its flushes of the
    newest ``_TIMELINE_KEEP_S`` seconds, oldest first, each a row of what
    one flush held (``end`` on ``time.monotonic()``, ``steps``, ``wall_s``,
    ``extent_s``, ``cpu_s``, ``phases``, ``phases_cpu``, ``counters``,
    ``slow``, ``dry_by_phase``, ``dry_gap_max_s`` and, by bucket,
    ``extent_hist`` and ``dry_gap_hist``), so a window can also be read from one summary: the
    rows that ended in it. ``slow``: the slow steps of those rows
    (``slow_total`` and ``slow_seconds`` count them all).
    ``extent_hist`` and ``dry_gap_hist``: ``edges_s`` (upper edges) and
    ``counts``, one longer (the last is the overflow). ``dry_by_phase``:
    seconds the device had no work, by the phase of the step they lay
    under (a DryWatch's; empty without one)."""
    with _STEP_LOCK:
        out = []
        for kind, agg in _step_stats.items():
            row = dict(agg, kind=kind, phases=dict(agg["phases"]),
                       counters=dict(agg["counters"]),
                       phases_cpu=dict(agg["phases_cpu"]))
            ring = row.pop("timeline")
            if ring is not None:
                row["timeline"] = list(ring)
                row["slow"] = [step for flush in ring
                               for step in flush["slow"]]
                row["extent_hist"] = _hist_row(
                    _EXTENT_EDGES, agg["extent_hist"])
                row["dry_gap_hist"] = _hist_row(
                    _GAP_EDGES, agg["dry_gap_hist"])
                row["dry_by_phase"] = dict(agg["dry_by_phase"])
            else:
                for name in ("slow_total", "slow_seconds", "extent_hist",
                             "dry_gap_hist", "dry_gap_max_s",
                             "dry_by_phase"):
                    del row[name]
            steps = max(1, int(agg["steps"]))
            row["mean_step_s"] = agg["wall_s"] / steps
            out.append(row)
    out.sort(key=lambda r: -r["wall_s"])
    return out


def extent_quantile(extent_hist: Dict[str, Any], q: float
                    ) -> Optional[float]:
    """Seconds under which the share ``q`` of the steps of an
    ``extent_hist`` (a ``step_summary()`` row's, or the difference of
    two) lie: interpolated inside the bucket on the logarithmic scale the
    edges follow; a quantile in the under- or overflow bucket reads as
    the edge beside it. None without a step."""
    edges, counts = extent_hist["edges_s"], extent_hist["counts"]
    total = sum(counts)
    if total <= 0:
        return None
    rank, seen = q * total, 0
    for bucket, count in enumerate(counts):
        if count and seen + count >= rank:
            if bucket == 0:
                return edges[0]
            if bucket == len(edges):
                return edges[-1]
            lo, hi = edges[bucket - 1], edges[bucket]
            return lo * (hi / lo) ** (max(0.0, rank - seen) / count)
        seen += count
    return edges[-1]


class StepAccumulator:
    """Amortizes report_step over hot loops: each step folds into a
    handful of float adds, and one aggregated ``report_step(steps=n)``
    fires every ``every`` steps — so a millisecond-scale decode tick
    pays ~a perf_counter pair, not six metric-series ops. The histogram
    sees mean-of-window observations (acceptable smoothing for a
    window of 16 uniform ticks); gauges/counters are exact.

    With ``timeline`` each step's extent (``wall_s`` unless the caller
    gives one that includes what lay outside it) is counted into a bucket
    of ``_EXTENT_EDGES`` and compared with ``_SLOW_FACTOR`` times the
    running median of this accumulator's extents: a slow step is kept
    whole and handed to ``report_step`` with the window (one ``bisect``,
    one add and one comparison a step), and every flush is stamped and
    kept as a row of the kind's ``timeline``. Without it (the default)
    the kind's row is its sums alone."""

    __slots__ = ("kind", "every", "device_kind", "timeline",
                 "_n", "_wall", "_tokens", "_device", "_compile",
                 "_comm", "_flops", "_cpu", "_phases", "_counters",
                 "_phases_cpu", "_hist", "_slow", "_typical", "_seen",
                 "_extent", "_dry_by", "_dry_hist", "_dry_max")

    def __init__(self, kind: str, every: int = 16,
                 device_kind: Optional[str] = None,
                 timeline: bool = False):
        self.kind = kind
        self.every = max(1, int(every))
        self.device_kind = device_kind
        self.timeline = timeline
        self._typical = 0.0
        self._seen = 0
        self._reset()

    def _reset(self):
        self._n = 0
        self._wall = self._device = self._compile = 0.0
        self._comm = self._flops = self._cpu = self._extent = 0.0
        self._tokens = 0
        self._phases: Dict[str, float] = {}
        self._counters: Dict[str, float] = {}
        self._phases_cpu: Dict[str, float] = {}
        self._hist: Dict[int, int] = {}
        self._slow: List[Dict[str, Any]] = []
        self._dry_by: Dict[str, float] = {}
        self._dry_hist: Dict[int, int] = {}
        self._dry_max = 0.0

    def add(self, wall_s: float, tokens: int = 0, device_s: float = 0.0,
            compile_s: float = 0.0, flops: float = 0.0,
            comm_s: float = 0.0,
            phases: Optional[Dict[str, float]] = None,
            cpu_s: float = 0.0,
            counters: Optional[Dict[str, float]] = None,
            phases_cpu: Optional[Dict[str, float]] = None,
            extent_s: Optional[float] = None,
            dry: Optional[Tuple[Dict[str, float], Dict[int, int],
                                float]] = None):
        """``dry``: what `DryWatch.take` gave the step's timer."""
        if self.timeline:
            self._extent_of(wall_s if extent_s is None else extent_s,
                            wall_s, cpu_s, phases, phases_cpu, counters,
                            dry)
        self._n += 1
        self._wall += wall_s
        self._tokens += tokens
        self._device += device_s
        self._compile += compile_s
        self._comm += comm_s
        self._flops += flops
        self._cpu += cpu_s
        _sum_phases(self._phases, phases)
        _sum_phases(self._counters, counters)
        _sum_phases(self._phases_cpu, phases_cpu)
        if self._n >= self.every:
            self.flush()

    def _extent_of(self, extent, wall_s, cpu_s, phases, phases_cpu,
                   counters, dry):
        """Count one step's extent and its share of the dry account; keep
        the step if it is slow."""
        by_phase = None
        if dry is not None:
            by_phase, gap_hist, gap_max = dry
            _sum_phases(self._dry_by, by_phase)
            _sum_phases(self._dry_hist, gap_hist)
            self._dry_max = max(self._dry_max, gap_max)
        self._extent += extent
        bucket = bisect.bisect_left(_EXTENT_EDGES, extent)
        hist = self._hist
        hist[bucket] = hist.get(bucket, 0) + 1
        typical = self._typical
        if extent > typical * _SLOW_FACTOR and bucket and typical:
            self._slow.append({
                "end": time.monotonic(), "extent_s": extent,
                "typical_s": typical, "wall_s": wall_s, "cpu_s": cpu_s,
                "phases": dict(phases or ()),
                "phases_cpu": dict(phases_cpu or ()),
                "counters": dict(counters or ()),
                "dry_s": sum((by_phase or {}).values()),
                "dry_by_phase": by_phase or {}})
        seen = self._seen = self._seen + 1
        gain = _TYPICAL_GAIN if seen > 32 else 1.0 / seen
        self._typical = typical * (
            1.0 + gain if extent > typical else 1.0 - gain) or extent

    def flush(self) -> Optional[Dict[str, float]]:
        n = self._n
        if not n:
            return None
        kept = {}
        if self.timeline:
            kept = dict(extent_hist=self._hist, slow=self._slow,
                        end=time.monotonic(), extent_s=self._extent,
                        dry_by_phase=self._dry_by,
                        dry_gap_hist=self._dry_hist,
                        dry_gap_max_s=self._dry_max)
        out = report_step(
            self.kind, self._wall, tokens=self._tokens,
            device_s=self._device, compile_s=self._compile,
            flops=self._flops, device_kind=self.device_kind, steps=n,
            comm_s=self._comm, phases=self._phases, cpu_s=self._cpu,
            counters=self._counters, phases_cpu=self._phases_cpu, **kept)
        self._reset()
        return out


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` — a host span on the
    profiler's clock, above the device's ops in any ``.xplane.pb`` of
    this process — or None in a process that has not imported jax: this
    module imports it for no span."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.profiler.TraceAnnotation(name)


# what phase() hands out under the kill switch
_NO_PHASE = contextlib.nullcontext()
# A phase that opens within this of its timer's last reading of the thread
# clock takes that reading for its own start, and so does the timer's exit:
# back-to-back phases read the clock once each, not twice (the call is a
# real syscall: 0.3 us here, 5.9 us on the benchmark's sandboxed host).
_CPU_REUSE_S = 20e-6


class StepTimer:
    """Times one step and reports it on exit.

    ::

        with StepTimer("decode", tokens=n, flops=2 * params * n) as t:
            with t.phase("stage"):
                args = host_side_prep()
            with t.device():
                out = jitted_step(*args)   # device-compute bucket
        # exit: wall split into compile (jax.monitoring delta during the
        # step) / device (time inside t.device()) / host (the rest);
        # t.phases == {"stage": ..., "device": ...} seconds by name

    ``phase(name)`` accumulates wall seconds under ``name`` and, while
    open, holds the span ``<kind>/<name>`` on the profiler's clock; the
    timer holds ``<kind>`` for its whole extent, so phases nest under
    it. ``device()`` and ``comm()`` are the phases the goodput split
    reads. ``cpu_s`` is ``time.thread_time()`` over the timer's extent:
    wall minus it is time this thread did not run (blocked on the
    device or on I/O, or waiting for the GIL); ``phases_cpu`` is the
    same clock inside each phase, so a phase's wall − CPU says which of
    them waited.

    ``sink``: a StepAccumulator to fold into instead of reporting
    immediately (hot loops — see the paged engine's tick). ``watch``: the
    owner's DryWatch, polled where a phase or a part ends and taken into
    the sink on exit. Near-zero
    when the plane is disabled: __enter__/__exit__ degrade to two
    attribute checks, phase() hands out one shared no-op, no span is
    built and nothing is reported."""

    __slots__ = ("kind", "tokens", "flops", "device_kind", "enabled",
                 "phases", "phases_cpu", "counters", "cpu_s", "result",
                 "sink", "watch", "_t0", "_c0", "_cpu0", "_span",
                 "_outside", "_cpu_at", "_cpu_read")

    def __init__(self, kind: str, tokens: int = 0, flops: float = 0.0,
                 device_kind: Optional[str] = None,
                 sink: Optional[StepAccumulator] = None,
                 watch: Optional["DryWatch"] = None):
        self.kind = kind
        self.tokens = tokens
        self.flops = flops
        self.device_kind = device_kind
        self.sink = sink
        self.enabled = not accel_disabled()
        self.watch = watch if self.enabled else None
        self.phases: Dict[str, float] = {}
        self.phases_cpu: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.cpu_s = 0.0
        self.result: Optional[Dict[str, float]] = None
        self._outside = 0.0
        self._t0 = 0.0
        self._c0 = 0.0
        self._cpu0 = 0.0
        # the last reading of the thread clock, and when (perf_counter)
        self._cpu_read = self._cpu_at = 0.0
        self._span = None

    @property
    def device_s(self) -> float:
        return self.phases.get("device", 0.0)

    @property
    def comm_s(self) -> float:
        return self.phases.get("comm", 0.0)

    def __enter__(self) -> "StepTimer":
        if self.enabled:
            ensure_installed()
            span = self._span = _annotation(self.kind)
            if span is not None:
                span.__enter__()
            self._c0 = backend_compile_seconds_total()
            self._cpu0 = self._cpu_read = time.thread_time()
            self._t0 = self._cpu_at = time.perf_counter()
        return self

    def phase(self, name: str):
        """``with timer.phase("admit"):`` — see the class docstring."""
        return _Phase(self, name) if self.enabled else _NO_PHASE

    def part(self, phase: str, name: str):
        """``with timer.part("prefill", "finish"):`` inside that phase —
        a piece of it timed apart. The phases tile the step, so a part is
        no phase: its wall seconds are the COUNTER ``<phase>_<name>_s``
        (a slow step keeps its own), and while a profiler trace runs it
        is the span ``<kind>/<phase>/<name>``."""
        return _Part(self, phase, name) if self.enabled else _NO_PHASE

    def device(self):
        """The device-compute bucket: the phase ``device``, less any
        backend compile that fell inside it."""
        return self.phase("device")

    def comm(self):
        """``with timer.comm():`` — host-plane collective time (gradient
        allreduce, loss reduction) lands in the ``comm`` goodput bucket
        instead of being misread as host-blocked."""
        return self.phase("comm")

    def outside(self, name: str, seconds: float) -> None:
        """A pre-measured interval that lies OUTSIDE this timer's extent
        (the paged engine's gap between two ticks): summed with the
        phases under ``name``, not part of ``wall_s`` but of the step's
        extent (StepAccumulator), no span — in a trace it is the space
        between two ``<kind>`` spans."""
        if self.enabled:
            self.phases[name] = self.phases.get(name, 0.0) + seconds
            self._outside += seconds
            if self.watch is not None:
                self.watch.mark(name, time.perf_counter())

    def count(self, name: str, n: float = 1) -> None:
        """``n`` more events of ``name`` in this step: summed by name
        into the kind's ``step_summary()`` row under ``counters``."""
        if self.enabled and n:
            self.counters[name] = self.counters.get(name, 0) + n

    def __exit__(self, exc_type, exc, tb):
        if not self.enabled:
            return False
        end = time.perf_counter()
        wall = end - self._t0
        self.cpu_s = (self._cpu_read if end - self._cpu_at < _CPU_REUSE_S
                      else time.thread_time()) - self._cpu0
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            return False
        compile_s = backend_compile_seconds_total() - self._c0
        if self.sink is not None:
            watch = self.watch
            self.sink.add(wall, tokens=self.tokens,
                          device_s=self.device_s, compile_s=compile_s,
                          flops=self.flops, comm_s=self.comm_s,
                          phases=self.phases, cpu_s=self.cpu_s,
                          counters=self.counters,
                          phases_cpu=self.phases_cpu,
                          extent_s=wall + self._outside,
                          dry=watch.take(self) if watch is not None
                          else None)
        else:
            self.result = report_step(
                self.kind, wall, tokens=self.tokens,
                device_s=self.device_s, compile_s=compile_s,
                flops=self.flops, device_kind=self.device_kind,
                comm_s=self.comm_s, phases=self.phases,
                cpu_s=self.cpu_s, counters=self.counters,
                phases_cpu=self.phases_cpu)
        return False


class _Phase:
    """One named interval of a StepTimer. Its seconds run from before
    its span opens to after it closes, so back-to-back phases tile the
    timer's wall with nothing between them but a ``with`` statement.

    The phase ``device`` is the goodput split's device-compute bucket: a
    span that straddles an XLA recompile (the first call of a
    freshly-traced step fn compiles INSIDE the span) would bill the
    compile seconds as device compute; the disjoint backend-compile
    window the tracker already measures is subtracted, so those seconds
    land in the compile bucket alone."""

    __slots__ = ("_timer", "_name", "_t0", "_c0", "_cpu0", "_span")

    def __init__(self, timer: StepTimer, name: str):
        self._timer = timer
        self._name = name
        self._t0 = 0.0
        self._c0 = 0.0
        self._cpu0 = 0.0
        self._span = None

    def __enter__(self):
        timer = self._timer
        t0 = self._t0 = time.perf_counter()
        self._cpu0 = timer._cpu_read if t0 - timer._cpu_at < _CPU_REUSE_S \
            else time.thread_time()
        if self._name == "device":
            self._c0 = backend_compile_seconds_total()
        if timer.watch is not None:
            timer.watch.label = self._name
        span = self._span = _annotation(
            self._timer.kind + "/" + self._name)
        if span is not None:
            span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        name = self._name
        timer = self._timer
        # the thread clock first: its read (a real syscall, slow on a
        # crowded host) lies inside the phase it measures, so the phases
        # go on tiling the step
        read = timer._cpu_read = time.thread_time()
        now = timer._cpu_at = time.perf_counter()
        seconds = now - self._t0
        cpu = timer.phases_cpu
        cpu[name] = cpu.get(name, 0.0) + read - self._cpu0
        if name == "device":
            seconds = max(0.0, seconds - (
                backend_compile_seconds_total() - self._c0))
        phases = timer.phases
        phases[name] = phases.get(name, 0.0) + seconds
        if timer.watch is not None:
            timer.watch.mark(name, now)
        return False


class _Part:
    """See :meth:`StepTimer.part`."""

    __slots__ = ("_timer", "_phase", "_name", "_t0", "_span")

    def __init__(self, timer: StepTimer, phase: str, name: str):
        self._timer = timer
        self._phase = phase
        self._name = name
        self._t0 = 0.0
        self._span = None

    def __enter__(self):
        self._span = _traced_span(
            f"{self._timer.kind}/{self._phase}/{self._name}")
        watch = self._timer.watch
        now = self._t0 = time.perf_counter()
        if watch is not None:
            # what of the phase lay before the part is the phase's own
            watch.mark(self._phase, now)
            watch.label = self._phase + "/" + self._name
        return self

    def __exit__(self, exc_type, exc, tb):
        now = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        self._timer.count(f"{self._phase}_{self._name}_s", now - self._t0)
        watch = self._timer.watch
        if watch is not None:
            watch.mark(watch.label, now)
            watch.label = self._phase
        return False


class DryWatch:
    """The account of a DRY device that an engine keeps of itself: how
    long the device had run out of work, in how many gaps, and under which
    phase of the host's step. The engine hands the device every program
    it runs and the device runs them in order, so an output of the newest
    one (the HANDLE: an array that nothing donates before the next
    dispatch) is ready exactly when the device's queue is empty, and
    ``is_ready()`` asks without waiting (0.2-0.4 us). Not DRAINED: that is
    the engine reading its tokens with nothing dispatched behind them.

    The engine calls ``dispatching()`` just before it hands the device a
    program and ``dispatched(handle)`` just after; the step's timer
    (``StepTimer(watch=...)``) calls ``mark`` where a phase, a part or the
    time between two steps ends, and the engine calls ``poll()`` inside
    its long loops. While the last poll read "busy" each of them polls
    once. The first that reads "ready" bounds the moment the device ran
    dry to the interval since the poll before it: the gap's LOWER length
    runs from this poll to the next program's dispatch, its UPPER length
    from the poll before, and the account takes their mean, half of that
    interval, under the phase it lay in. What follows until the dispatch
    is dry in full, under the phase each interval lay in, so the seconds
    by phase sum to the mean of the two bounds.

    ``take(timer)`` moves what has been counted since the last take into
    the step: the counters ``dispatches``, ``dry_dispatches`` (those that
    found the device dry), ``dry_s_lower`` and ``dry_s_upper``, and for the
    step's accumulator the seconds by phase, the gaps that ended by bucket
    of ``_GAP_EDGES`` and the longest of them. A gap that spans two steps
    leaves each the seconds that lay in it and counts as a gap where it
    ends. While a profiler trace runs a gap is also the span
    ``dry/<phase>``, from the poll that found the device ready to the
    dispatch that ended the gap. ``totals`` is the same account since the
    watch was made (the engine's ``stats()["dry"]``)."""

    __slots__ = ("label", "totals", "_handle", "_busy", "_at", "_gap",
                 "_span", "_n", "_dry_n", "_lower", "_upper", "_by",
                 "_hist", "_max")

    def __init__(self):
        # the phase, or phase/part, that is open on the stepping thread
        self.label = ""
        self.totals: Dict[str, Any] = {
            "dispatches": 0, "dry_dispatches": 0, "dry_s": 0.0,
            "dry_s_lower": 0.0, "dry_s_upper": 0.0, "by_phase": {},
            "gap_max_s": 0.0}
        self._handle = None
        self._busy = False     # what the last poll read
        self._at = 0.0         # perf_counter at the last poll or mark
        self._gap = 0.0        # the open gap's length so far (the mean)
        self._span = None
        self._take()

    def _take(self):
        self._n = self._dry_n = 0
        self._lower = self._upper = 0.0
        self._by: Dict[str, float] = {}
        self._hist: Dict[int, int] = {}
        self._max = 0.0

    def mark(self, label: str, now: float) -> None:
        """The interval since the last mark lay under ``label`` and ends
        ``now`` (``time.perf_counter()``)."""
        handle = self._handle
        if handle is None:
            return
        seconds = now - self._at
        self._at = now
        if self._busy:
            try:
                if not handle.is_ready():
                    return
            except RuntimeError:
                pass   # deleted: donated by a program of somebody else's
            self._busy = False
            self._span = _traced_span("dry/" + label)
            self._upper += seconds
            seconds *= 0.5
        else:
            self._lower += seconds
            self._upper += seconds
        self._gap += seconds
        self._by[label] = self._by.get(label, 0.0) + seconds

    def poll(self) -> None:
        """One poll from inside a loop of the open phase; nothing once the
        device has been found dry (the next mark counts the interval)."""
        if self._busy:
            self.mark(self.label, time.perf_counter())

    def dispatching(self) -> None:
        """Just before a program is handed to the device: the one poll
        that says whether this dispatch finds it dry."""
        self._n += 1
        if self._busy:
            self.mark(self.label or "outside", time.perf_counter())

    def dispatched(self, handle) -> None:
        """Just after: the device has work again, and ``handle`` is ready
        when it has done all of it."""
        now = time.perf_counter()
        if self._handle is not None and not self._busy:
            self.mark(self.label or "outside", now)
            gap, self._gap = self._gap, 0.0
            bucket = bisect.bisect_left(_GAP_EDGES, gap)
            self._hist[bucket] = self._hist.get(bucket, 0) + 1
            self._max = max(self._max, gap)
            self._dry_n += 1
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None
        self._handle = handle
        self._busy = True
        self._at = now

    def waited(self, array) -> None:
        """The caller has just waited for ``array``: if that is the handle
        (a read with nothing dispatched behind it), the device was busy
        until now."""
        if self._busy and array is self._handle:
            self._at = time.perf_counter()

    def idle(self) -> None:
        """The owner has no work left, so none for the device: what
        follows is nobody's wait, and the open gap is no gap."""
        self._handle = None
        self._busy = False
        self._gap = 0.0
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def take(self, timer: "StepTimer"
             ) -> Optional[Tuple[Dict[str, float], Dict[int, int], float]]:
        """See the class docstring. None if no second was dry."""
        if not self._n and not self._upper:
            return None
        totals = self.totals
        timer.count("dispatches", self._n)
        timer.count("dry_dispatches", self._dry_n)
        timer.count("dry_s_lower", self._lower)
        timer.count("dry_s_upper", self._upper)
        totals["dispatches"] += self._n
        totals["dry_dispatches"] += self._dry_n
        out = None
        if self._upper:
            out = self._by, self._hist, self._max
            totals["dry_s_lower"] += self._lower
            totals["dry_s_upper"] += self._upper
            totals["dry_s"] += 0.5 * (self._lower + self._upper)
            _sum_phases(totals["by_phase"], self._by)
            totals["gap_max_s"] = max(totals["gap_max_s"], self._max)
        self._take()
        return out


# ---------------------------------------------------------------------------
# the per-process report (get_accel_report RPC body)
# ---------------------------------------------------------------------------


def accel_report(force_jax: bool = False) -> Dict[str, Any]:
    """Everything this process knows about its accelerators: device
    rows, compile tracking, step telemetry, and any pressure rows the
    caller should publish. ``devices`` stays empty in processes that
    have no backend open (see snapshot_devices) unless ``force_jax``."""
    disabled = accel_disabled()
    report: Dict[str, Any] = {
        "pid": os.getpid(),
        "disabled": disabled,
        # the clock of `steps[*].slow[*].end` and of the pauses
        "now": time.monotonic(),
        "jax_initialized": backend_initialized(),
        "devices": [],
        "compile": compile_summary(),
        "steps": step_summary(),
        "pressure": [],
    }
    if disabled:
        return report
    devices = snapshot_devices(force_jax=force_jax)
    report["devices"] = devices
    report["jax_initialized"] = report["jax_initialized"] or force_jax
    report["pressure"] = check_pressure(devices)
    return report
