"""Core runtime microbenchmarks
(reference: python/ray/_private/ray_perf.py — the canonical microbenchmark
set whose published numbers are in BASELINE.md / release/perf_metrics/
microbenchmark.json).

Run: python -m ray_tpu.perf [--quick]
Prints one JSON line per metric: {"metric", "value", "unit", "baseline",
"vs_baseline"} where baseline is the reference's published number on its
own hardware (m4.16xlarge-class) — an envelope comparison, not
like-for-like hardware."""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

# Reference numbers: release/perf_metrics/microbenchmark.json (BASELINE.md).
BASELINES = {
    "tasks_sync_per_s": 901.0,
    "tasks_async_per_s": 7_419.0,
    "tasks_async_multi_client_per_s": 19_295.0,
    "actor_calls_sync_per_s": 1_826.0,
    "actor_calls_async_per_s": 7_926.0,
    "actor_calls_async_nn_per_s": 24_809.0,
    "put_small_per_s": 4_795.0,
    "get_small_per_s": 9_177.0,
    "put_gib_per_s": 20.35,
    "pg_create_remove_per_s": 751.0,
}

_CLIENT_SCRIPT = r"""
import faulthandler, json, os, sys, time
sys.path.insert(0, {repo!r})
# a wedged client must dump its stack and die, not hang the bench
faulthandler.dump_traceback_later(120, exit=True)
import ray_tpu

idx = int(sys.argv[1]); n = int(sys.argv[2]); out = sys.argv[3]
ray_tpu.init(address={addr!r}, log_to_driver=False)

@ray_tpu.remote
def noop():
    return None

ray_tpu.get([noop.remote() for _ in range(100)])  # warm a worker lease
ready = out + ".ready"
open(ready, "w").close()
go = os.path.join(os.path.dirname(out), "go")
while not os.path.exists(go):
    time.sleep(0.02)
# re-arm: the first timer bounded connect+warmup; the flood on a
# contended box legitimately takes minutes
faulthandler.cancel_dump_traceback_later()
faulthandler.dump_traceback_later(600, exit=True)
t0 = time.perf_counter()
ray_tpu.get([noop.remote() for _ in range(n)])
t1 = time.perf_counter()
with open(out, "w") as f:
    json.dump({{"t0": t0, "t1": t1, "n": n}}, f)
# results are on disk; a slow/hung disconnect must not stall the bench
faulthandler.cancel_dump_traceback_later()
os._exit(0)
"""


def _await_full_cpus(timeout_s: float = 60.0, stable_samples: int = 5):
    """Wait out lease reclamation. Dead benchmark drivers (the
    multi-client clients os._exit) hold their leases until the GCS
    driver-liveness sweep reclaims them (~10 s); starting the next
    bench before that measures reclamation latency — or, on a
    cold/starved cluster, hangs the client warmup outright. The calling
    driver's own live actors hold CPUs too, so "free == total" may be
    unreachable — exit when either every CPU is free OR the free count
    has STOPPED RISING for `stable_samples` seconds (reclamation
    finished; what's still held is held by live owners)."""
    from ray_tpu.util.state.api import list_nodes
    deadline = time.monotonic() + timeout_s
    last_free, stable = -1.0, 0
    while time.monotonic() < deadline:
        nodes = list_nodes()
        free = sum(n_["resources_available"].get("CPU", 0)
                   for n_ in nodes)
        total = sum(n_["resources_total"].get("CPU", 0) for n_ in nodes)
        if free >= total:
            return
        if free > last_free:
            last_free, stable = free, 0
        else:
            stable += 1
            if stable >= stable_samples:
                return
        time.sleep(1.0)


def multi_client_bench(n_clients: int = 4, n_per: int = 1000,
                       results: Optional[Dict[str, float]] = None,
                       metric: str = "tasks_async_multi_client_per_s"):
    """Aggregate async task throughput from N separate DRIVER PROCESSES
    against one cluster (reference: ray_perf.py 'tasks async (multi
    client)'; baseline 19,295/s). Assumes a cluster is already up in this
    process (main() calls it after the single-client suite).

    Always takes the round-5 cold-cluster-safe path: (1) wait for all
    leased CPUs to come back before spawning clients — a previous
    bench's dead drivers must not starve this run's warmup (the r4
    cold-cluster hang, re-trippable by any harness that runs this bench
    more than once, e.g. the --shards A/B); (2) each client warms a
    worker lease and checks in via a ready-file barrier before the
    timed flood."""
    import glob
    import os
    import subprocess
    import sys
    import tempfile

    from ray_tpu._internal.config import CONFIG
    from ray_tpu._internal.core_worker import get_core_worker
    _await_full_cpus()
    host, port = get_core_worker().gcs.address
    addr = f"{host}:{port}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    workdir = tempfile.mkdtemp(prefix="rtpu-mc-")
    script = os.path.join(workdir, "client.py")
    with open(script, "w") as f:
        f.write(_CLIENT_SCRIPT.format(repo=repo, addr=addr))
    # Clients are their own drivers: the A/B arm under test must reach
    # them (apply_system_config doesn't cross process boundaries).
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RTPU_OWNER_SHARDS=str(CONFIG.owner_shards))
    procs = []
    outs = []
    for i in range(n_clients):
        out = os.path.join(workdir, f"client-{i}.json")
        outs.append(out)
        with open(os.path.join(workdir, f"client-{i}.err"), "w") as err:
            procs.append(subprocess.Popen(
                [sys.executable, script, str(i), str(n_per), out],
                env=env, stdout=subprocess.DEVNULL, stderr=err))
        # the child holds its own inherited fd; ours closes immediately
    deadline = time.monotonic() + 150
    while len(glob.glob(os.path.join(workdir, "*.ready"))) < n_clients:
        if time.monotonic() > deadline:
            chunks = []
            for p in glob.glob(os.path.join(workdir, "*.err")):
                with open(p) as f:
                    chunks.append(f.read()[-2000:])
            raise TimeoutError(
                "multi-client workers failed to connect; client stderr:"
                "\n" + "\n".join(chunks))
        time.sleep(0.05)
    open(os.path.join(workdir, "go"), "w").close()
    for p in procs:
        p.wait(timeout=300)
    spans = []
    for out in outs:
        with open(out) as f:
            spans.append(json.load(f))
    total = sum(s["n"] for s in spans)
    # Clients share a monotonic-ish clock (same machine): aggregate rate
    # over the union window.
    wall = max(s["t1"] for s in spans) - min(s["t0"] for s in spans)
    rate = total / wall
    if results is not None:
        results[metric] = rate
    _report(metric, rate, "tasks/s")
    return rate


def codec_bench(n: int = 20000, results: Optional[Dict[str, float]] = None
                ) -> Dict[str, float]:
    """Flat-wire codec vs pickle on a representative no-arg actor-call
    spec: encode/decode ns per spec and wire bytes per task. Runs
    in-process (no cluster) — this is the per-call CPU the submit and
    execute hot paths actually pay."""
    import pickle

    from ray_tpu._internal import task_spec as ts
    from ray_tpu._internal.ids import ActorID, JobID, TaskID
    from ray_tpu.remote_function import pack_args

    job = JobID.from_int(1)
    spec = ts.TaskSpec(
        task_id=TaskID.of(job), job_id=job, task_type=ts.ACTOR_TASK,
        function=ts.FunctionDescriptor("bench", "Sink", ""),
        args=pack_args((), {}), num_returns=1, resources={},
        owner_address=("127.0.0.1", 50000), owner_worker_id=b"w" * 28,
        name="Sink.ping", actor_id=ActorID.of(job), method_name="ping",
        sequence_number=7)
    tmpl = ts.make_template(spec)
    delta = ts.encode_delta(spec, tmpl.method_name)
    ts.register_template(tmpl.tid, tmpl.data)
    reg = ts.lookup_template(tmpl.tid)
    pickled = pickle.dumps(spec, protocol=5)

    def _ns(fn) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e9

    out = {
        "codec_flat_encode_ns": _ns(
            lambda: ts.encode_delta(spec, tmpl.method_name)),
        "codec_flat_decode_ns": _ns(
            lambda: ts.release_spec(ts.decode_delta(delta, reg))),
        "codec_pickle_encode_ns": _ns(
            lambda: pickle.dumps(spec, protocol=5)),
        "codec_pickle_decode_ns": _ns(lambda: pickle.loads(pickled)),
        "codec_flat_bytes_per_task": float(len(delta)),
        "codec_pickle_bytes_per_task": float(len(pickled)),
    }
    out.update(_recv_side_bench(spec, tmpl, delta, reg, n))
    for metric, value in out.items():
        _report(metric, value,
                "bytes" if metric.endswith("per_task") else
                ("ids/us" if metric.endswith("ids_per_us") else
                 ("decrs/us" if metric.endswith("decrs_per_us") else
                  "ns")))
    if results is not None:
        results.update(out)
    return out


def _recv_side_bench(spec, tmpl, delta, reg, n: int):
    """Receive-path microbench (PERF.md round 14): the in-ring C decode
    vs the Python decode it replaces, the done-stream id walk (pooled
    borrowed keys vs per-id TaskID construction), and the batched
    decref fold vs the legacy per-object handler path."""
    from ray_tpu._internal import native_decode as nd
    from ray_tpu._internal import rpc
    from ray_tpu._internal.core_worker import (ReferenceCounter,
                                               _pack_actor_batch)
    from ray_tpu._internal.ids import ObjectID, TaskID
    from ray_tpu._native import fastrpc as fp

    out = {}
    # -- C delta decode (64-delta actor batch amortizes the ctypes
    # call; the decode itself runs in the C classifier exactly as the
    # epoll thread runs it) vs the Python decode of the same frame.
    batch = 64
    payload = _pack_actor_batch(("127.0.0.1", 50123),
                                [(tmpl.tid, tmpl.data)],
                                [(tmpl.tid, delta)] * batch)
    body = rpc.pack_frame(0, rpc.FLAG_RAW, b"push_actor_tasks",
                          payload)[4:]
    decoded = fp.test_decode(body)
    if decoded is not None and decoded[0] == 4:
        import ctypes
        reuse = ctypes.create_string_buffer(len(body) + (1 << 16))
        reps = max(1, n // batch)
        t0 = time.perf_counter()
        for _ in range(reps):
            fp.test_decode(body, buf=reuse)
        out["recv_c_delta_decode_ns"] = \
            (time.perf_counter() - t0) / (reps * batch) * 1e9
        # Python consumption of the decoded records (record parse +
        # freelist fill) — the per-spec Python residue left after C.
        rec_payload = decoded[1]
        from ray_tpu._internal import task_spec as ts_fill

        def _consume():
            _done_to, _tmpls, recs = nd.parse_actor_batch_record(
                rec_payload)
            for _tid, _known, fields in recs:
                ts_fill.release_spec(
                    ts_fill.spec_from_fields(reg, *fields))
        t0 = time.perf_counter()
        for _ in range(reps):
            _consume()
        out["recv_decoded_fill_ns"] = \
            (time.perf_counter() - t0) / (reps * batch) * 1e9
    # Python-side decode of the same batch (what the A/B kill switch
    # runs): per-frame walk + decode_delta per spec.
    from ray_tpu._internal import task_spec as ts_mod
    from ray_tpu._internal.core_worker import _unpack_actor_batch

    def _py_decode():
        _done_to, _tmpls, frames = _unpack_actor_batch(payload)
        for _tid, d in frames:
            ts_mod.release_spec(ts_mod.decode_delta(d, reg))
    reps = max(1, n // batch)
    t0 = time.perf_counter()
    for _ in range(reps):
        _py_decode()
    out["recv_py_delta_decode_ns"] = \
        (time.perf_counter() - t0) / (reps * batch) * 1e9

    # -- done-stream id walk: fresh bytes + TaskID per id (pre-PR-11)
    # vs borrowed keys over the one contiguous buffer.
    n_ids = 4096
    ids = b"".join(TaskID.of(spec.job_id).binary() for _ in range(n_ids))
    table = {}
    for key in TaskID.iter_borrowed(ids):
        table[TaskID(bytes(key.binary()))] = None
    sz = TaskID.SIZE

    def _legacy_walk():
        for i in range(n_ids):
            table.get(TaskID(ids[i * sz:(i + 1) * sz]))

    def _pooled_walk():
        get = table.get
        for key in TaskID.iter_borrowed(ids):
            get(key)
    t0 = time.perf_counter()
    _legacy_walk()
    out["recv_done_legacy_ids_per_us"] = \
        n_ids / ((time.perf_counter() - t0) * 1e6)
    t0 = time.perf_counter()
    _pooled_walk()
    out["recv_done_pooled_ids_per_us"] = \
        n_ids / ((time.perf_counter() - t0) * 1e6)

    # -- decref folds: one contiguous fold through the batch handler vs
    # the legacy per-object path (hex round trip + one locked
    # decrement per id, as one borrow_decref RPC per object paid).
    class _Sink:
        rpc_address = ("127.0.0.1", 1)

        def _free_owned_object(self, *a, **k):
            pass

        def queue_borrow_decref(self, *a, **k):
            pass

        def fire_and_forget(self, *a, **k):
            pass

    n_oids = 4096
    oids = [ObjectID.from_random() for _ in range(n_oids)]
    rc = ReferenceCounter(_Sink())
    for oid in oids:
        rc.add_borrower(oid)
        rc.add_borrower(oid)  # stays alive through one decrement round
    fold = b"".join(o.binary() for o in oids)
    t0 = time.perf_counter()
    rc.remove_borrowers_fold(
        [ObjectID(b) for b in nd.iter_fold_ids(fold)])
    out["recv_fold_decrs_per_us"] = \
        n_oids / ((time.perf_counter() - t0) * 1e6)
    hexes = [o.hex() for o in oids]
    t0 = time.perf_counter()
    for h in hexes:
        rc.remove_borrower(ObjectID(bytes.fromhex(h)))
    out["recv_legacy_decrs_per_us"] = \
        n_oids / ((time.perf_counter() - t0) * 1e6)
    return out


def callsite_bench(n: int = 200_000,
                   results: Optional[Dict[str, float]] = None
                   ) -> Dict[str, float]:
    """Memory-observability callsite capture on the submit hot path:
    ns per _capture_callsite() call (warm render cache), with the
    RTPU_NO_CALLSITES=1 kill switch. The timed loop is compiled with a
    NON-package co_filename — perf.py itself lives under ray_tpu/, so a
    direct call here would classify every frame as a package frame and
    benchmark the capture-miss walk instead of the real user-frame hit
    path that put()/submit pays. Runs in-process (no cluster)."""
    from ray_tpu._internal import core_worker as cw

    src = ("def _user_bench(capture, count, perf_counter):\n"
           "    t0 = perf_counter()\n"
           "    for _ in range(count):\n"
           "        capture()\n"
           "    return (perf_counter() - t0) / count * 1e9\n")
    ns: Dict[str, Any] = {}
    exec(compile(src, "/bench/user_code.py", "exec"), ns)
    _user_bench = ns["_user_bench"]

    capture = cw._capture_callsite
    _user_bench(capture, 100, time.perf_counter)  # warm the cache
    warm = _user_bench(capture, n, time.perf_counter)
    saved = cw._NO_CALLSITES
    cw._NO_CALLSITES = True
    try:
        disabled = _user_bench(capture, n, time.perf_counter)
    finally:
        cw._NO_CALLSITES = saved
    out = {
        "callsite_capture_ns": warm,
        "callsite_disabled_ns": disabled,
        # fraction of a ~200us per-call driver submit budget (PERF.md)
        "callsite_pct_of_submit": warm / 200_000.0 * 100.0,
    }
    for metric, value in out.items():
        _report(metric, value,
                "%" if metric.endswith("of_submit") else "ns")
    if results is not None:
        results.update(out)
    return out


def rpc_bench(n: int = 2000,
              results: Optional[Dict[str, float]] = None
              ) -> Dict[str, float]:
    """Transport-observatory overhead: per-call latency of a real-socket
    loopback echo with instrumentation on vs the RTPU_NO_RPC_METRICS
    kill switch, interleaved (on/off/on/off...) so clock drift and
    allocator state cancel instead of biasing one side, plus the
    lock-free frpc_ring_stats read cost. Runs in-process (no cluster)."""
    import asyncio

    from ray_tpu._internal import rpc, rpc_metrics
    from ray_tpu._internal.config import CONFIG

    async def _run(count: int) -> float:
        server = rpc.RpcServer("perf-rpc")

        async def echo(x=0):
            return x
        server.register("echo", echo)
        await server.start("127.0.0.1", 0)
        # Defeat the in-process fast path: the observatory instruments
        # the wire, so the bench must cross it.
        with rpc._local_servers_lock:
            rpc._local_servers.pop(server.address, None)
        client = rpc.RpcClient(server.address)
        for i in range(100):
            await client.call("echo", x=i)  # warm
        t0 = time.perf_counter()
        for i in range(count):
            await client.call("echo", x=i)
        per_call = (time.perf_counter() - t0) / count
        await client.close()
        await server.stop()
        return per_call * 1e6

    def _with_switch(disabled: bool) -> float:
        saved = CONFIG.no_rpc_metrics
        CONFIG.no_rpc_metrics = disabled
        rpc_metrics._reset_for_tests()
        try:
            return asyncio.run(_run(n))
        finally:
            CONFIG.no_rpc_metrics = saved
            rpc_metrics._reset_for_tests()

    on_runs, off_runs = [], []
    for _ in range(3):
        on_runs.append(_with_switch(False))
        off_runs.append(_with_switch(True))
    on_us, off_us = min(on_runs), min(off_runs)
    out: Dict[str, float] = {
        "rpc_call_us": on_us,
        "rpc_call_nometrics_us": off_us,
        "rpc_metrics_overhead_pct": (on_us - off_us) / off_us * 100.0,
    }
    from ray_tpu._native.fastrpc import NativeIO
    io = NativeIO.get()
    if io is not None and io.ring_stats() is not None:
        k = 20_000
        t0 = time.perf_counter()
        for _ in range(k):
            io.ring_stats()
        out["ring_stats_read_ns"] = (time.perf_counter() - t0) / k * 1e9
    for metric, value in out.items():
        unit = ("%" if metric.endswith("pct")
                else "ns" if metric.endswith("ns") else "us")
        _report(metric, value, unit)
    if results is not None:
        results.update(out)
    return out


def sampler_bench(results: Optional[Dict[str, float]] = None
                  ) -> Dict[str, float]:
    """Stack-sampler overhead: wall time of a fixed pure-Python workload
    with the profiler off (the RTPU_NO_PROFILER / default state: zero
    threads, zero cost) vs continuously sampling at 10 and 100 Hz, plus
    the direct per-pass cost of one sweep over all threads. Runs
    in-process (no cluster)."""
    from ray_tpu._internal import profiler

    def _workload() -> float:
        t0 = time.perf_counter()
        x = 0
        for i in range(5_000_000):
            x += i * i
        return time.perf_counter() - t0

    _workload()  # warm
    # min-of-5: on a shared 1-core box scheduler noise dwarfs the
    # sampler's true cost; the minimum is the least-perturbed run.
    base = min(_workload() for _ in range(5))
    out = {"sampler_off_workload_s": base}
    for hz in (10, 100):
        start = profiler.start_profiling(hz=hz)
        assert start["running"], start
        try:
            timed = min(_workload() for _ in range(5))
        finally:
            profiler.stop_profiling()
            profiler.get_profile(clear=True)  # drop the ring
        out[f"sampler_{hz}hz_workload_s"] = timed
        out[f"sampler_{hz}hz_overhead_pct"] = \
            max(0.0, (timed - base) / base * 100.0)
    # direct cost of one sampling pass (what every tick pays, ~N frames
    # deep x M threads wide)
    s = profiler.StackSampler(hz=100, ring_size=4096)
    for _ in range(50):
        s._sample_once()
    t0 = time.perf_counter()
    reps = 500
    for _ in range(reps):
        s._sample_once()
    out["sampler_pass_us"] = (time.perf_counter() - t0) / reps * 1e6
    for metric, value in out.items():
        unit = "%" if metric.endswith("pct") else \
            ("us" if metric.endswith("us") else "s")
        _report(metric, value, unit)
    if results is not None:
        results.update(out)
    return out


def accel_bench(results: Optional[Dict[str, float]] = None
                ) -> Dict[str, float]:
    """Accelerator-plane overhead: device snapshot cost (the
    get_accel_report hot part), report_step direct cost, and the
    per-step telemetry tax on the REAL paged decode loop — one tiny
    engine built with the plane on and one with the kill switch set,
    decoding the same workload (the off-vs-on A/B that proves the
    default-on plane is sub-noise). Runs in-process (no cluster)."""
    import numpy as np

    from ray_tpu._internal import accel
    from ray_tpu._internal.config import CONFIG
    from ray_tpu.llm import GenerationRequest, PagedEngineConfig, \
        PagedLLMEngine
    from ray_tpu.models.llama import LlamaConfig

    out: Dict[str, float] = {}
    accel.ensure_installed()
    # warm device state so the snapshot walks real buffers
    import jax.numpy as jnp
    keep = [jnp.ones((64, 64)) for _ in range(8)]
    accel.snapshot_devices(force_jax=True)
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        accel.snapshot_devices()
    out["accel_snapshot_us"] = (time.perf_counter() - t0) / reps * 1e6
    del keep

    t0 = time.perf_counter()
    reps = 20_000
    for _ in range(reps):
        accel.report_step("perf", 0.001, tokens=4, device_s=0.0005,
                          flops=1e6, device_kind="cpu")
    out["accel_report_step_us"] = \
        (time.perf_counter() - t0) / reps * 1e6

    # Decode-loop A/B: the engine caches the kill-switch state at
    # construction, so each arm builds its own engine on shared params
    # (one compile). Arms INTERLEAVE round-robin and each takes its
    # min-of-rounds — on a contended box back-to-back arms measure
    # machine drift, not the plane.
    model = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=256,
        remat=False, use_flash=False, attention_impl="reference")
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 128, size=12)) for _ in range(8)]
    engine_cfg = dict(max_batch=4, max_len=64, page_size=8,
                      num_pages=64, prefill_buckets=(16,))
    params = None  # first build inits; second reuses (one compile)

    def _build_engine(disabled: bool) -> PagedLLMEngine:
        CONFIG.apply_system_config({"no_accel_metrics": disabled})
        try:
            engine = PagedLLMEngine(
                PagedEngineConfig(model=model, **engine_cfg),
                params=params)
            engine.generate(prompts[:2], max_new_tokens=4)  # warm
            return engine
        finally:
            CONFIG.apply_system_config({"no_accel_metrics": False})

    def _round(engine) -> float:
        for i, p in enumerate(prompts):
            engine.submit(GenerationRequest(
                prompt_tokens=p, max_new_tokens=16, request_id=str(i)))
        done, ticks = 0, 0
        t0 = time.perf_counter()
        while done < len(prompts):
            done += len(engine.step())
            ticks += 1
        return (time.perf_counter() - t0) / max(1, ticks)

    off_engine = _build_engine(disabled=True)
    params = off_engine.params  # share: one init, one compile cache
    on_engine = _build_engine(disabled=False)
    best = {"off": None, "on": None}
    for _ in range(5):
        for key, engine in (("off", off_engine), ("on", on_engine)):
            tick = _round(engine)
            if best[key] is None or tick < best[key]:
                best[key] = tick
    out["accel_off_decode_tick_us"] = best["off"] * 1e6
    out["accel_on_decode_tick_us"] = best["on"] * 1e6
    out["accel_decode_overhead_pct"] = max(0.0, (
        out["accel_on_decode_tick_us"] - out["accel_off_decode_tick_us"])
        / out["accel_off_decode_tick_us"] * 100.0)
    for metric, value in out.items():
        unit = "%" if metric.endswith("pct") else "us"
        _report(metric, value, unit)
    if results is not None:
        results.update(out)
    return out


def logplane_bench(results: Optional[Dict[str, float]] = None
                   ) -> Dict[str, float]:
    """Log-plane overhead: the worker-side per-line stamp tax (what
    every print()/log record pays), raylet-side parse + ring-append
    cost, and a cluster A/B — the same print-heavy workload timed with
    the plane ON (ring-only capture) vs the RTPU_NO_LOG_PLANE kill
    switch (legacy DEVNULL), both with log_to_driver off. The A/B
    proves default-on capture rides within machine noise."""
    from ray_tpu._internal import logplane

    out: Dict[str, float] = {}
    line = "a typical task log line with some payload attached: 12345"
    reps = 200_000
    t0 = time.perf_counter()
    for _ in range(reps):
        logplane.stamp_line(line, "INFO")
    out["logplane_stamp_ns"] = (time.perf_counter() - t0) / reps * 1e9
    stamped = logplane.stamp_line(line, "INFO")
    t0 = time.perf_counter()
    for _ in range(reps):
        logplane.parse_line(stamped)
    out["logplane_parse_ns"] = (time.perf_counter() - t0) / reps * 1e9
    ring = logplane.LogRing("w" * 8, pid=1, maxlen=2000)
    t0 = time.perf_counter()
    for _ in range(reps):
        ring.append("stdout", "INFO", line, task="ab" * 8)
    out["logplane_ring_append_ns"] = \
        (time.perf_counter() - t0) / reps * 1e9

    # Cluster A/B: each arm spawns its own workers (the pipe wiring is
    # fixed at spawn), min-of-rounds inside each arm. The kill switch
    # rides the environment so worker subprocesses inherit it.
    def _arm(disabled: bool) -> float:
        import os

        import ray_tpu
        if disabled:
            os.environ["RTPU_NO_LOG_PLANE"] = "1"
        from ray_tpu._internal.config import CONFIG
        CONFIG.reset()
        try:
            ray_tpu.init(num_cpus=2, log_to_driver=False,
                         object_store_memory=128 * 1024 * 1024)

            @ray_tpu.remote
            def chatty(n):
                # a realistic logging task: some work per line, not a
                # pure print loop (which would benchmark /dev/null)
                x = 0
                for i in range(n):
                    for j in range(2000):
                        x += j * j
                    print("bench line", i, x % 97)
                return n

            ray_tpu.get(chatty.remote(20), timeout=120)  # warm worker
            best = None
            for _ in range(3):
                t0 = time.perf_counter()
                ray_tpu.get([chatty.remote(250) for _ in range(4)],
                            timeout=120)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            return best
        finally:
            ray_tpu.shutdown()
            os.environ.pop("RTPU_NO_LOG_PLANE", None)
            CONFIG.reset()

    off_s = _arm(disabled=True)
    on_s = _arm(disabled=False)
    total_lines = 250 * 4
    out["logplane_off_chatty_s"] = off_s
    out["logplane_on_chatty_s"] = on_s
    out["logplane_chatty_overhead_pct"] = \
        max(0.0, (on_s - off_s) / off_s * 100.0)
    # the honest per-line figure: what one captured line costs end to
    # end (stamp + pipe + parse + ring) vs the DEVNULL legacy path
    out["logplane_per_line_us"] = \
        max(0.0, (on_s - off_s)) / total_lines * 1e6
    for metric, value in out.items():
        unit = "%" if metric.endswith("pct") else \
            ("s" if metric.endswith("_s") else
             ("us" if metric.endswith("_us") else "ns"))
        _report(metric, value, unit)
    if results is not None:
        results.update(out)
    return out


def _rate(n: int, fn: Callable[[], None]) -> float:
    start = time.perf_counter()
    fn()
    return n / (time.perf_counter() - start)


def _report(metric: str, value: float, unit: str):
    baseline = BASELINES.get(metric)
    row = {"metric": metric, "value": round(value, 2), "unit": unit,
           "baseline": baseline,
           "vs_baseline": round(value / baseline, 3) if baseline else None}
    print(json.dumps(row), flush=True)
    return row


def main(quick: bool = False) -> Dict[str, float]:
    import ray_tpu

    scale = 1 if quick else 4
    results = {}
    codec_bench(n=5000 if quick else 20000, results=results)
    ray_tpu.init(num_cpus=8, object_store_memory=2 * 1024**3)

    @ray_tpu.remote
    def noop():
        return None

    @ray_tpu.remote
    class Sink:
        def ping(self):
            return None

        async def aping(self):
            return None

    # Warm up the worker pool + dispatch path (the reference benchmark
    # also measures steady state, not worker cold-start).
    ray_tpu.get([noop.remote() for _ in range(200)])

    n = 200 * scale
    results["tasks_sync_per_s"] = _rate(
        n, lambda: [ray_tpu.get(noop.remote()) for _ in range(n)])
    _report("tasks_sync_per_s", results["tasks_sync_per_s"], "tasks/s")

    n = 1000 * scale
    ray_tpu.get([noop.remote() for _ in range(n)])  # warm burst
    results["tasks_async_per_s"] = _rate(
        n, lambda: ray_tpu.get([noop.remote() for _ in range(n)]))
    _report("tasks_async_per_s", results["tasks_async_per_s"], "tasks/s")

    actor = Sink.remote()
    ray_tpu.get(actor.ping.remote())
    n = 500 * scale
    results["actor_calls_sync_per_s"] = _rate(
        n, lambda: [ray_tpu.get(actor.ping.remote()) for _ in range(n)])
    _report("actor_calls_sync_per_s", results["actor_calls_sync_per_s"],
            "calls/s")

    n = 2000 * scale
    results["actor_calls_async_per_s"] = _rate(
        n, lambda: ray_tpu.get([actor.ping.remote() for _ in range(n)]))
    _report("actor_calls_async_per_s", results["actor_calls_async_per_s"],
            "calls/s")

    # n:n — 4 async actors, 4 submitting threads.
    import threading
    actors = [Sink.options(max_concurrency=16).remote() for _ in range(4)]
    ray_tpu.get([a.aping.remote() for a in actors for _ in range(50)])
    n_per = 500 * scale

    def _pound(a):
        ray_tpu.get([a.aping.remote() for _ in range(n_per)])

    def _nn():
        threads = [threading.Thread(target=_pound, args=(a,))
                   for a in actors]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    results["actor_calls_async_nn_per_s"] = _rate(4 * n_per, _nn)
    _report("actor_calls_async_nn_per_s",
            results["actor_calls_async_nn_per_s"], "calls/s")

    try:
        multi_client_bench(n_clients=2 if quick else 4,
                           n_per=500 * scale, results=results)
    except Exception as e:  # noqa: BLE001 — keep the rest of the suite
        print(json.dumps({"metric": "tasks_async_multi_client_per_s",
                          "error": str(e)}), flush=True)

    small = np.zeros(8, np.int64)
    n = 1000 * scale
    results["put_small_per_s"] = _rate(
        n, lambda: [ray_tpu.put(small) for _ in range(n)])
    _report("put_small_per_s", results["put_small_per_s"], "puts/s")

    ref = ray_tpu.put(small)
    results["get_small_per_s"] = _rate(
        n, lambda: [ray_tpu.get(ref) for _ in range(n)])
    _report("get_small_per_s", results["get_small_per_s"], "gets/s")

    # Put throughput: 40 x 25 MiB numpy arrays through plasma (the
    # reference benchmark also puts numpy — pickle-5 out-of-band, the
    # array body memcpys straight into the store mmap).
    chunk = np.random.randint(0, 255, 25 * 1024**2, np.uint8)
    reps = 10 if quick else 40
    start = time.perf_counter()
    refs = [ray_tpu.put(chunk) for _ in range(reps)]
    dt = time.perf_counter() - start
    del refs
    results["put_gib_per_s"] = reps * 25 / 1024 / dt
    _report("put_gib_per_s", results["put_gib_per_s"], "GiB/s")

    # The multi-client bench leaves 4 dead drivers whose leases the
    # GCS driver-liveness sweep reclaims (~10 s). Wait for the CPUs to
    # come back so the PG bench measures PG throughput, not
    # dead-driver reclamation latency.
    _await_full_cpus()

    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)
    n = 50 * scale

    def _pg_cycle():
        for _ in range(n):
            pg = placement_group([{"CPU": 1}])
            pg.wait(timeout_seconds=30)
            remove_placement_group(pg)
    results["pg_create_remove_per_s"] = _rate(n, _pg_cycle)
    _report("pg_create_remove_per_s", results["pg_create_remove_per_s"],
            "pgs/s")

    ray_tpu.shutdown()
    return results


def shards_bench(shard_counts=(1, 2, 4), quick: bool = False,
                 decode_arms=(True, False)) -> Dict[str, float]:
    """Owner-shard x native-decode A/B: the workloads the sharded core
    and the in-ring receive decode target — sync tasks, n:n async actor
    calls (4 async actors x 4 submitting threads) and the multi-client
    flood (4 separate driver processes) — at each shard count, paired
    with native decode on and off (`RTPU_NO_NATIVE_DECODE`), one fresh
    cluster per arm. ``shards=1`` + decode-off is the exact-legacy
    path; only paired same-window ratios are signal. Feeds the PERF.md
    round-10/round-14 tables. Decode arms set the ENV flag so spawned
    raylets/workers inherit it (CONFIG alone would only flip the
    driver)."""
    import os

    from ray_tpu._internal.config import CONFIG

    scale = 1 if quick else 4
    results: Dict[str, float] = {}
    saved_nd = os.environ.get("RTPU_NO_NATIVE_DECODE")
    try:
        _shards_bench_arms(shard_counts, decode_arms, scale, quick,
                           results)
    finally:
        if saved_nd is None:
            os.environ.pop("RTPU_NO_NATIVE_DECODE", None)
        else:
            os.environ["RTPU_NO_NATIVE_DECODE"] = saved_nd
        CONFIG.reset()
    return results


def _shards_bench_arms(shard_counts, decode_arms, scale, quick, results):
    import os
    import threading

    import ray_tpu
    from ray_tpu._internal.config import CONFIG

    for decode_on in decode_arms:
        os.environ["RTPU_NO_NATIVE_DECODE"] = "" if decode_on else "1"
        CONFIG.reset()
        tag = "" if decode_on else "_nodecode"
        for count in shard_counts:
            CONFIG.apply_system_config({"owner_shards": int(count)})
            ray_tpu.init(num_cpus=8, object_store_memory=2 * 1024**3)
            try:
                from ray_tpu._internal.core_worker import get_core_worker
                got = len(get_core_worker().shards)
                if got != count:
                    raise RuntimeError(
                        f"arm shards={count}: driver came up with {got}")

                @ray_tpu.remote
                def noop():
                    return None

                @ray_tpu.remote
                class Sink:
                    async def aping(self):
                        return None

                # sync tasks (one at a time, full lease + push + reply
                # round trip per call)
                ray_tpu.get([noop.remote() for _ in range(20)])
                n_sync = 100 * scale
                metric = f"tasks_sync_per_s_shards{count}{tag}"
                results[metric] = _rate(
                    n_sync,
                    lambda: [ray_tpu.get(noop.remote())
                             for _ in range(n_sync)])
                _report(metric, results[metric], "tasks/s")

                actors = [Sink.options(max_concurrency=16).remote()
                          for _ in range(4)]
                ray_tpu.get([a.aping.remote() for a in actors
                             for _ in range(50)])
                n_per = 500 * scale

                def _pound(a):
                    ray_tpu.get([a.aping.remote() for _ in range(n_per)])

                def _nn():
                    threads = [threading.Thread(target=_pound, args=(a,))
                               for a in actors]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                metric = f"actor_calls_async_nn_per_s_shards{count}{tag}"
                results[metric] = _rate(4 * n_per, _nn)
                _report(metric, results[metric], "calls/s")
                per_shard = [(row["shard"], row["submits"])
                             for row in get_core_worker().shards.stats()]
                print(json.dumps(
                    {"metric": f"shard_submits_shards{count}{tag}",
                     "per_shard": per_shard}), flush=True)
                mc_metric = \
                    f"tasks_async_multi_client_per_s_shards{count}{tag}"
                try:
                    multi_client_bench(
                        n_clients=2 if quick else 4, n_per=500 * scale,
                        results=results, metric=mc_metric)
                except Exception as e:  # noqa: BLE001 — keep other arms
                    print(json.dumps({"metric": mc_metric,
                                      "error": str(e)}), flush=True)
            finally:
                ray_tpu.shutdown()


def failover_bench(quick: bool = False) -> Dict[str, float]:
    """GCS durability + failover numbers (PERF.md round-13):

    - persist-path overhead per mutation, A/B across
      RTPU_GCS_PERSIST=off|legacy|wal (the WAL's O(record) append vs the
      legacy whole-snapshot rewrite),
    - recovery time (snapshot + WAL-tail replay) for a populated store,
    - time-to-first-task-after-restart on a live cluster (in-process GCS
      restart at the same address; raylet re-registers, a fresh actor
      schedules on the new incarnation).
    """
    import os
    import tempfile

    from ray_tpu._internal.config import CONFIG
    from ray_tpu._internal.gcs import GcsServer
    from ray_tpu._internal.rpc import EventLoopThread

    results: Dict[str, float] = {}
    loop = EventLoopThread.get()
    n_fast = 2000 if quick else 10000
    n_legacy = 100 if quick else 300  # whole-snapshot per op: keep small

    for mode in ("off", "legacy", "wal"):
        CONFIG.apply_system_config({"gcs_persist": mode})
        tmp = tempfile.mkdtemp(prefix=f"rtpu-failover-{mode}-")
        path = os.path.join(tmp, "gcs.db")
        gcs = GcsServer("perf", persist_path=path)
        loop.run_sync(gcs.start())
        # add_job persists in EVERY mode (legacy rewrote the whole
        # snapshot per call — the n must stay small there; the WAL
        # appends three O(record) rows).
        n = n_legacy if mode == "legacy" else n_fast

        async def _pound(gcs=gcs, n=n):
            import asyncio
            for i in range(n):
                await gcs.handle_add_job(driver_address=None,
                                         namespace="bench")
                # One loop tick per mutation, as real RPC arrivals pay:
                # the group-commit fsync callback fires per tick — a
                # no-yield loop would amortize ALL fsyncs into one.
                await asyncio.sleep(0)
        start = time.perf_counter()
        loop.run_sync(_pound())
        per_op_us = (time.perf_counter() - start) / n * 1e6
        results[f"gcs_mutation_{mode}_us"] = per_op_us
        _report(f"gcs_mutation_{mode}_us", per_op_us, "us/op")
        if mode == "wal":
            # ... plus the fine-grained KV append path (new in wal mode)
            payload = b"x" * 256

            async def _kv(gcs=gcs, n=n_fast):
                import asyncio
                for i in range(n):
                    await gcs.handle_kv_put(ns="bench", key=f"k{i}",
                                            value=payload)
                    await asyncio.sleep(0)  # fsync per tick (see above)
            start = time.perf_counter()
            loop.run_sync(_kv())
            kv_us = (time.perf_counter() - start) / n_fast * 1e6
            results["gcs_kv_append_wal_us"] = kv_us
            _report("gcs_kv_append_wal_us", kv_us, "us/op")
            loop.run_sync(gcs.stop())
            start = time.perf_counter()
            gcs2 = GcsServer("perf", persist_path=path)
            loop.run_sync(gcs2.start())
            recovery_ms = (time.perf_counter() - start) * 1e3
            assert len(gcs2.kv.get("bench", {})) == n_fast
            assert len(gcs2.jobs) == n
            results["gcs_recovery_ms"] = recovery_ms
            _report("gcs_recovery_ms", recovery_ms, "ms")
            loop.run_sync(gcs2.stop())
        else:
            loop.run_sync(gcs.stop())
        CONFIG.reset()

    # -- time-to-first-task-after-restart on a live cluster ------------
    import ray_tpu
    from ray_tpu._internal.node import Node
    CONFIG.apply_system_config({"gcs_persist": "wal"})
    tmp = tempfile.mkdtemp(prefix="rtpu-failover-e2e-")
    path = os.path.join(tmp, "gcs.db")
    node = Node(head=True, resources={"CPU": 4}, gcs_persist_path=path)
    node.start()
    ray_tpu.init(_node=node, log_to_driver=False)
    try:
        @ray_tpu.remote
        class Probe:
            def ping(self):
                return 1

        warm = Probe.remote()
        ray_tpu.get(warm.ping.remote(), timeout=60)
        port = node.gcs_address[1]
        start = time.perf_counter()
        loop.run_sync(node.gcs.stop())
        new_gcs = GcsServer(node.session_name, persist_path=path)
        loop.run_sync(new_gcs.start(port=port))
        node.gcs = new_gcs
        # First NEW control-plane work on the new incarnation: schedule
        # a fresh actor and run one call on it.
        fresh = Probe.remote()
        ray_tpu.get(fresh.ping.remote(), timeout=120)
        ttft_ms = (time.perf_counter() - start) * 1e3
        results["gcs_restart_first_task_ms"] = ttft_ms
        _report("gcs_restart_first_task_ms", ttft_ms, "ms")
        # The pre-restart actor still answers (zero lost state).
        ray_tpu.get(warm.ping.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()
        CONFIG.reset()
    return results


def collectives_bench(world: int = 8, mb: int = 64,
                      dcn_gbps: float = 0.01) -> Dict[str, float]:
    """Collective-backend A/B (PR-12): allreduce size sweep
    (256KB / 4MB / `mb`MB float32) x algorithm (ring / tree / hier,
    hier+int8) across `world` single-process ranks on a virtual
    two-slice topology, with a quantization-error column and measured
    per-link bytes.

    The slice boundary is EMULATED: this box has no real DCN, so
    cross-slice sends pay nbytes/(dcn_gbps GB/s) of sender-side delay
    (0 disables). The default 0.01 GB/s preserves the REAL per-chip
    ICI:DCN bandwidth ratio (~100:1 on v4/v5p pods — ~900 GB/s ICI vs
    single-digit GB/s DCN per chip) against this box's ~1 GB/s
    effective in-process transport playing the ICI role; without a
    slow cross-slice link the topology doesn't exist and every
    equal-byte schedule ties on a compute-bound core. The dcn/ici BYTE
    columns are measured from the group ledger, not modeled — they
    hold on any hardware. Run the bench on an otherwise idle box
    (see PERF.md machine calibration)."""
    import ray_tpu

    ray_tpu.init(num_cpus=world + 1)

    @ray_tpu.remote(num_cpus=1)
    class R:
        def __init__(self, rank, world, group):
            self.rank, self.world, self.group = rank, world, group

        def join(self, algo, quant, num_slices, gbps):
            from ray_tpu._internal.config import CONFIG
            from ray_tpu.util.collective import collective as col
            CONFIG.apply_system_config({"collective_algo": algo,
                                        "collective_quant": quant})
            col.init_collective_group(self.world, self.rank,
                                      group_name=self.group,
                                      num_slices=num_slices,
                                      dcn_emulate_gbps=gbps)
            return True

        def allreduce(self, n_elems, check):
            from ray_tpu.util.collective import collective as col
            x = np.random.RandomState(1000 + self.rank) \
                .standard_normal(n_elems).astype(np.float32)
            t0 = time.perf_counter()
            out = col.allreduce(x, group_name=self.group)
            dt = time.perf_counter() - t0
            err = None
            if check:  # exact fp64 reference (regenerate every rank)
                exact = np.zeros(n_elems, np.float64)
                for r in range(self.world):
                    exact += np.random.RandomState(1000 + r) \
                        .standard_normal(n_elems)
                err = float(np.abs(out.astype(np.float64) - exact).max()
                            / np.abs(exact).max())
            return dt, err

        def bytes_sent(self):
            from ray_tpu.util.collective import collective as col
            return col._group(self.group).bytes_sent()

    sizes = [(256 * 1024, "256KB"), (4 << 20, "4MB"),
             (mb << 20, f"{mb}MB")]
    arms = [("ring", "off"), ("tree", "off"), ("hier", "off"),
            ("hier", "int8")]
    results: Dict[str, float] = {}
    rows = []
    for algo, quant_arm in arms:
        group = f"cb-{algo}-{quant_arm}"
        ranks = [R.remote(r, world, group) for r in range(world)]
        ray_tpu.get([a.join.remote(algo, quant_arm, 2, dcn_gbps)
                     for a in ranks], timeout=180)
        # warm connections + compile nothing: one small round
        ray_tpu.get([a.allreduce.remote(1 << 12, False) for a in ranks],
                    timeout=180)
        prev = ray_tpu.get([a.bytes_sent.remote() for a in ranks],
                           timeout=60)
        for nbytes, label in sizes:
            n_elems = nbytes // 4
            check = nbytes <= (4 << 20)  # fp64 reference is O(W*N)
            t0 = time.perf_counter()
            outs = ray_tpu.get([a.allreduce.remote(n_elems, check)
                                for a in ranks], timeout=900)
            wall = time.perf_counter() - t0
            cur = ray_tpu.get([a.bytes_sent.remote() for a in ranks],
                              timeout=60)
            dcn = sum(c["dcn"] - p["dcn"] for c, p in zip(cur, prev))
            ici = sum(c["ici"] - p["ici"] for c, p in zip(cur, prev))
            prev = cur
            errs = [e for _dt, e in outs if e is not None]
            err = max(errs) if errs else float("nan")
            arm_key = f"{algo}_{quant_arm}_{label}"
            results[arm_key] = wall
            results[f"{arm_key}_dcn_mb"] = dcn / 2**20
            rows.append((algo, quant_arm, label, wall, dcn / 2**20,
                         ici / 2**20, err))
            _report(f"allreduce_{arm_key}_x{world}", wall, "s")
        for a in ranks:
            ray_tpu.kill(a)
        del ranks
    print(f"\n| algo | quant | size | wall s | dcn MB | ici MB "
          f"| max rel err |")
    print("|---|---|---|---|---|---|---|")
    for algo, q, label, wall, dcn_mb, ici_mb, err in rows:
        err_s = f"{err:.2e}" if err == err else "-"
        print(f"| {algo} | {q} | {label} | {wall:.3f} | {dcn_mb:.2f} "
              f"| {ici_mb:.2f} | {err_s} |")
    big = sizes[-1][1]
    results["hier_vs_ring_speedup"] = \
        results[f"ring_off_{big}"] / results[f"hier_off_{big}"]
    results["dcn_bytes_ratio_int8"] = \
        results[f"hier_off_{big}_dcn_mb"] / \
        max(1e-9, results[f"hier_int8_{big}_dcn_mb"])
    _report("hier_vs_ring_speedup", results["hier_vs_ring_speedup"], "x")
    _report("dcn_bytes_ratio_int8", results["dcn_bytes_ratio_int8"], "x")
    ray_tpu.shutdown()
    return results


if __name__ == "__main__":
    # Host-plane benches, CPU by design: this process runs JAX itself, so
    # on a chip host it stays off the chip by environment (read when jax
    # is first imported), not by asking jax what it found.
    os.environ["JAX_PLATFORMS"] = "cpu"
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--collectives", action="store_true")
    parser.add_argument("--codec", action="store_true",
                        help="flat-codec microbench only (no cluster)")
    parser.add_argument("--callsites", action="store_true",
                        help="callsite-capture microbench only "
                             "(no cluster)")
    parser.add_argument("--sampler", action="store_true",
                        help="stack-sampler overhead microbench only "
                             "(no cluster)")
    parser.add_argument("--rpc", action="store_true",
                        help="transport-observatory overhead "
                             "microbench: loopback call cost with "
                             "metrics on vs RTPU_NO_RPC_METRICS, plus "
                             "the ring-stats read cost (no cluster)")
    parser.add_argument("--accel", action="store_true",
                        help="accelerator-plane overhead microbench: "
                             "snapshot cost + decode-loop on/off A/B "
                             "(no cluster)")
    parser.add_argument("--logplane", action="store_true",
                        help="log-plane overhead microbench: per-line "
                             "stamp/parse/ring cost + print-heavy "
                             "cluster A/B (plane on vs kill switch)")
    parser.add_argument("--failover", action="store_true",
                        help="GCS durability/failover bench: per-"
                             "mutation persist A/B (off/legacy/wal), "
                             "recovery time, time-to-first-task after "
                             "an in-process GCS restart")
    parser.add_argument("--shards", nargs="?", const="1,2,4",
                        default=None, metavar="N,N,...",
                        help="owner-shard A/B: n:n + multi-client at "
                             "each shard count (default 1,2,4)")
    parser.add_argument("--world", type=int, default=8)
    parser.add_argument("--mb", type=int, default=64)
    parser.add_argument("--dcn-gbps", type=float, default=0.01,
                        help="emulated cross-slice (DCN) bandwidth for "
                             "--collectives (GB/s; 0 disables the "
                             "sender-side delay; default keeps the "
                             "real ~100:1 ICI:DCN per-chip ratio)")
    args = parser.parse_args()
    if args.collectives:
        collectives_bench(world=args.world, mb=args.mb,
                          dcn_gbps=args.dcn_gbps)
    elif args.codec:
        codec_bench()
    elif args.callsites:
        callsite_bench()
    elif args.sampler:
        sampler_bench()
    elif args.rpc:
        rpc_bench()
    elif args.accel:
        accel_bench()
    elif args.logplane:
        logplane_bench()
    elif args.failover:
        failover_bench(quick=args.quick)
    elif args.shards:
        shards_bench(tuple(int(x) for x in args.shards.split(",")),
                     quick=args.quick)
    else:
        main(quick=args.quick)
