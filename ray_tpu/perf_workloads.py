"""ML-workload benchmarks covering the five BASELINE.json configs
(VERDICT r2 item 3): RLlib PPO / IMPALA sampling+learning rates, Serve
HTTP throughput + latency, Data pipeline throughput, and LLM engine
decode throughput. The Train number comes from bench.py on the TPU.

Plus the **standing chaos soak** (``--which soak`` / ``bench_soak``):
sustained serve+train-style load on a real multi-process cluster
(external killable GCS, subprocess raylets) under a seeded fault
script — scheduled transport chaos, a full rolling restart of every
worker raylet through the graceful-drain path, and a ``kill -9`` of
the GCS mid-rollout — gated on SLOs (zero lost/doubled tasks, zero
dropped serve streams, bounded p99 during failover) and recorded as a
JSON artifact like the mesh-sustained bench.

Plus the **LLM serving saturation bench** (``--which serve_saturation``
/ ``bench_serve_saturation``): a sustained mixed-length streaming
load through the real serve proxy — gated on SLOs (p95 TTFT, zero
dropped streams, zero leaked KV pages) and recorded as
``tests/artifacts_serve_saturation.json``. The same
run regression-gates request-lifecycle tracing overhead (reqtrace
on/off req/s within noise) and exports the per-request serve timeline
to ``tests/artifacts_requests_timeline.json``.

Run: python -m ray_tpu.perf_workloads \
    [--which all|ppo|impala|serve|data|llm|soak|serve_saturation]
Prints one JSON line per metric.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import time


def _report(metric: str, value: float, unit: str, **extra):
    print(json.dumps({"metric": metric, "value": round(value, 2),
                      "unit": unit, **extra}), flush=True)


def bench_ppo(iters: int = 12):
    from ray_tpu.rllib import PPOConfig
    algo = (PPOConfig().environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                         rollout_fragment_length=128)
            .training(lr=1e-3, num_epochs=10, minibatch_size=256)
            .build())
    algo.train()  # warm/compile
    t0 = time.perf_counter()
    steps = 0
    learner_rates = []
    for _ in range(iters):
        result = algo.train()
        steps += result["num_env_steps_sampled"]
        learner_rates.append(result["learner_samples_per_s"])
    wall = time.perf_counter() - t0
    algo.stop()
    _report("ppo_env_steps_per_s", steps / wall, "steps/s")
    _report("ppo_learner_samples_per_s",
            sum(learner_rates) / len(learner_rates), "samples/s")


def bench_impala(iters: int = 20):
    from ray_tpu.rllib import ImpalaConfig
    algo = (ImpalaConfig().environment("CartPole-v1")
            .env_runners(num_env_runners=2, num_envs_per_env_runner=32,
                         rollout_fragment_length=32)
            .training(lr=1e-3, train_batch_slots=64, num_epochs=2)
            .build())
    algo.train()
    t0 = time.perf_counter()
    trained = 0
    for _ in range(iters):
        result = algo.train()
        trained += result["num_env_steps_trained_this_iter"]
    wall = time.perf_counter() - t0
    algo.stop()
    _report("impala_env_steps_trained_per_s", trained / wall, "steps/s")


def bench_serve(seconds: float = 10.0, concurrency: int = 8):
    import threading
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    def echo(request):
        return {"ok": True}

    serve.run(echo.bind(), name="bench", route_prefix="/bench")
    base = f"{serve.api.get_http_address()}/bench"
    # warm
    for _ in range(5):
        urllib.request.urlopen(base, timeout=10).read()
    latencies = []
    lock = threading.Lock()
    stop_at = time.perf_counter() + seconds

    def pound():
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            urllib.request.urlopen(base, timeout=30).read()
            dt = time.perf_counter() - t0
            with lock:
                latencies.append(dt)
    threads = [threading.Thread(target=pound) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    latencies.sort()
    n = len(latencies)
    _report("serve_requests_per_s", n / wall, "req/s")
    _report("serve_p50_ms", latencies[n // 2] * 1000, "ms")
    _report("serve_p95_ms", latencies[int(n * 0.95)] * 1000, "ms")
    serve.shutdown()


def bench_data(rows: int = 200_000):
    import numpy as np

    import ray_tpu.data as rd

    t0 = time.perf_counter()
    ds = rd.range(rows).map_batches(
        lambda b: {"x": np.asarray(b["id"]) * 2},
        batch_size=8192)
    total = 0
    for batch in ds.iter_batches(batch_size=8192):
        total += len(batch["x"])
    wall = time.perf_counter() - t0
    assert total == rows
    _report("data_rows_per_s", rows / wall, "rows/s")


def bench_llm(steps: int = 40):
    import numpy as np

    from ray_tpu.llm import PagedEngineConfig, PagedLLMEngine
    from ray_tpu.models.llama import LlamaConfig

    model = LlamaConfig(vocab_size=1024, hidden_size=256,
                        intermediate_size=512, num_layers=4, num_heads=8,
                        num_kv_heads=8, max_seq_len=512, remat=False,
                        use_flash=False, attention_impl="reference")
    engine = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=8, max_len=256, page_size=16,
        num_pages=256, prefill_buckets=(32,)))
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 1024, size=16)) for _ in range(8)]
    engine.generate(prompts, max_new_tokens=4)  # compile
    t0 = time.perf_counter()
    engine.generate(prompts, max_new_tokens=steps)
    wall = time.perf_counter() - t0
    _report("llm_decode_tokens_per_s", 8 * steps / wall, "tok/s",
            note="tiny CPU model; engine-overhead measurement, "
                 "HBM-bound decode is the TPU bench")


# ---------------------------------------------------------------------------
# serve_saturation: sustained mixed-length streaming load with SLO gates,
# recorded as tests/artifacts_serve_saturation.json
# ---------------------------------------------------------------------------


def _sat_tiny_model():
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=4, max_seq_len=256, remat=False,
                       use_flash=False, attention_impl="reference")


def _sat_engine_config(num_pages: int = 96):
    from ray_tpu.llm import PagedEngineConfig
    return PagedEngineConfig(
        model=_sat_tiny_model(), max_batch=8, max_len=128, page_size=8,
        num_pages=num_pages, prefill_buckets=(16, 32, 64))


def _sat_mixed_workload(seed: int, n: int):
    """Mixed-length saturation mix: 1/3 short chat turns with long
    answers, 1/3 medium, 1/3 long doc-grounded prompts with short
    answers."""
    import numpy as np
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        kind = i % 3
        if kind == 0:
            plen, max_new = rng.randint(4, 12), 64
        elif kind == 1:
            plen, max_new = rng.randint(24, 48), 48
        else:
            plen, max_new = rng.randint(64, 100), 24
        reqs.append(([int(t) for t in rng.randint(1, 128, size=plen)],
                     int(max_new)))
    return reqs


def _drive_engine_arm(engine, workload) -> dict:
    """Submit the whole workload up front (saturation) and step the
    engine to drain, recording per-request TTFT, throughput,
    preemptions, and the page-ledger balance."""
    from ray_tpu.llm import GenerationRequest
    outputs: dict = {}
    t_submit: dict = {}
    t_first: dict = {}

    def make_cbs(i):
        def on_tok(request, token):
            if i not in t_first:
                t_first[i] = time.perf_counter()

        def on_done(request, tokens):
            outputs[i] = tokens
        return on_tok, on_done

    t0 = time.perf_counter()
    for i, (prompt, max_new) in enumerate(workload):
        on_tok, on_done = make_cbs(i)
        t_submit[i] = time.perf_counter()
        engine.submit(
            GenerationRequest(prompt_tokens=prompt,
                              max_new_tokens=max_new,
                              request_id=f"sat-{i}"),
            done_callback=on_done, token_callback=on_tok)
    steps = 0
    while engine.has_work():
        engine.step()
        steps += 1
        assert steps < 100_000
    wall = time.perf_counter() - t0
    ttfts = sorted(t_first[i] - t_submit[i] for i in t_first)
    gen_tokens = sum(len(t) for t in outputs.values())
    stats = engine.stats()
    return {
        "requests": len(workload),
        "wall_s": round(wall, 3),
        "requests_per_s": round(len(workload) / wall, 2),
        "decode_tokens_per_s": round(gen_tokens / wall, 1),
        "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4),
        "ttft_p95_s": round(ttfts[int(len(ttfts) * 0.95)], 4),
        "preemptions": stats["preemptions"],
        "leaked_pages": stats["leaked_pages"],
        "outputs": outputs,
    }


def reqtrace_overhead_ab(seed: int = 1234, n_requests: int = 24,
                         rounds: int = 3) -> dict:
    """Paired A/B (same seed, same params, same workload): request
    lifecycle tracing ON (default) vs the RTPU_NO_REQTRACE kill switch.
    Both arms interleave round-robin and the BEST round per arm is
    compared (the round-11 idiom: on a contended container, min-wall
    is the only stable estimator — single-shot walls swing 50%+).
    Regression gate: tracing stays within machine noise — the traced
    arm's best req/s must hold >= 0.8x the untraced arm's (a real
    per-event regression shows up far below that). Token parity is
    gated too: tracing must never perturb scheduling."""
    from ray_tpu._internal.config import CONFIG
    from ray_tpu.llm import PagedLLMEngine

    workload = _sat_mixed_workload(seed, n_requests)
    _warmup = [([1] * 8, 2), ([2] * 30, 2), ([3] * 60, 2),
               ([4] * 70, 2), ([5] * 90, 2), ([6] * 100, 2),
               ([7] * 24 + [1], 2), ([7] * 24 + [2], 2)]
    # a tight pool (40 pages) so the arms see real page pressure —
    # parks/preemptions are where tracing records most
    CONFIG.apply_system_config({"prefix_cache_entries": 12})
    try:
        on_engine = PagedLLMEngine(_sat_engine_config(num_pages=40))
        off_engine = PagedLLMEngine(_sat_engine_config(num_pages=40),
                                    params=on_engine.params)
        _drive_engine_arm(on_engine, _warmup)
        _drive_engine_arm(off_engine, _warmup)
        on_rows, off_rows = [], []
        for _ in range(max(1, int(rounds))):
            CONFIG.apply_system_config({"no_reqtrace": True})
            try:
                off_rows.append(_drive_engine_arm(off_engine, workload))
            finally:
                CONFIG.apply_system_config({"no_reqtrace": False})
            on_rows.append(_drive_engine_arm(on_engine, workload))
    finally:
        CONFIG.apply_system_config({"prefix_cache_entries": 128})
    parity_ok = all(row["outputs"] == on_rows[0]["outputs"]
                    for row in on_rows + off_rows)
    on_row = max(on_rows, key=lambda r: r["requests_per_s"])
    off_row = max(off_rows, key=lambda r: r["requests_per_s"])
    for row in on_rows + off_rows:
        row.pop("outputs")
    result = {
        "seed": seed,
        "rounds": len(on_rows),
        "reqtrace_on": on_row,
        "reqtrace_off": off_row,
        "reqtrace_on_req_per_s_rounds":
        [r["requests_per_s"] for r in on_rows],
        "reqtrace_off_req_per_s_rounds":
        [r["requests_per_s"] for r in off_rows],
        "gates": {
            "token_parity": parity_ok,
            "overhead_within_noise": on_row["requests_per_s"]
            >= 0.8 * off_row["requests_per_s"],
        },
    }
    result["passed"] = all(result["gates"].values())
    return result


class _SatLLMServer:
    """LLMServer + a stats op the saturation client polls for the
    zero-leaked-pages SLO (the proxy only routes __call__, so the leak
    probe rides the same HTTP path as the load), + a reqtrace flush op
    so the driver can collect the replica's request-lifecycle ring
    deterministically (no waiting on the metrics-flush cadence)."""

    def __new__(cls, engine_config, params=None):
        from ray_tpu.llm.serving import LLMServer

        class _Server(LLMServer):
            async def __call__(self, http_request):
                body = http_request.json()
                if body.get("op") == "leak_check":
                    return self._engine.stats()
                if body.get("op") == "reqtrace_flush":
                    import asyncio

                    from ray_tpu.llm import reqtrace
                    # gcs.put must run off the replica's io loop
                    ok = await asyncio.get_event_loop() \
                        .run_in_executor(None, reqtrace.flush)
                    return {"flushed": ok}
                return await super().__call__(http_request)
        return _Server(engine_config, params=params)


def _sat_stream_once(host: str, port: int, body: dict,
                     timeout_s: float = 240.0) -> dict:
    """One streaming request over a raw socket; returns token count and
    time-to-first-token (first chunk with a token line)."""
    import socket

    payload = json.dumps(body).encode()
    s = socket.create_connection((host, int(port)), timeout=timeout_s)
    t0 = time.perf_counter()
    ttft = None
    tokens = []
    try:
        s.sendall((f"POST /llm HTTP/1.1\r\nHost: x\r\n"
                   f"Content-Length: {len(payload)}\r\n"
                   "Connection: close\r\n\r\n").encode() + payload)
        data = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            data += chunk
            # the proxy only writes chunks that carry tokens (or an
            # error), so the first body line IS the first token batch
            if ttft is None and b'"tokens"' in data:
                ttft = time.perf_counter() - t0
    finally:
        s.close()
    head, _, rest = data.partition(b"\r\n\r\n")
    if b"200" not in head.split(b"\r\n", 1)[0]:
        raise RuntimeError(f"stream request failed: {head[:120]!r}")
    error = None
    buf = rest
    while buf:
        line, _, buf = buf.partition(b"\r\n")
        if not line:
            continue
        try:
            n = int(line, 16)
        except ValueError:
            continue
        if n == 0:
            break
        chunk, buf = buf[:n], buf[n + 2:]
        for ln in chunk.decode().splitlines():
            if not ln.strip():
                continue
            rec = json.loads(ln)
            tokens.extend(rec.get("tokens", []))
            if rec.get("error"):
                error = rec["error"]
    return {"tokens": tokens, "ttft_s": ttft, "error": error}


def bench_serve_saturation(seed: int = 1234, clients: int = 3,
                           requests_per_client: int = 5,
                           slo_ttft_p95_s: float = 30.0,
                           artifact_path: str =
                           "tests/artifacts_serve_saturation.json",
                           timeline_artifact_path: str =
                           "tests/artifacts_requests_timeline.json",
                           skip_cluster: bool = False) -> dict:
    """Sustained mixed-length streaming saturation through the REAL
    serve proxy. SLO gates: p95 TTFT bounded, zero dropped streams, zero
    leaked KV pages. Also runs the
    reqtrace on/off overhead A/B (regression gate: tracing within
    noise) and exports the per-request lifecycle chrome trace next to
    the SLO artifact."""
    import threading

    result = {"seed": seed, "reqtrace_ab": reqtrace_overhead_ab(seed)}

    if not skip_cluster:
        import ray_tpu
        from ray_tpu import serve

        ray_tpu.init(num_cpus=4, object_store_memory=300 * 1024 * 1024)
        try:
            app = serve.deployment(
                _SatLLMServer, name="satllm",
                max_ongoing_requests=64).bind(_sat_engine_config())
            serve.run(app, name="llm", route_prefix="/llm",
                      wait_for_ready_timeout_s=240)
            addr = serve.api.get_http_address().replace("http://", "")
            host, port = addr.rsplit(":", 1)
            # warm the engine (first request pays the jit compiles)
            _sat_stream_once(host, int(port),
                             {"prompt_tokens": [1, 2, 3],
                              "max_new_tokens": 2, "stream": True})
            workload = _sat_mixed_workload(
                seed + 2, clients * requests_per_client)
            streams: list = []
            lock = threading.Lock()

            def client(cid):
                for r in range(requests_per_client):
                    prompt, max_new = workload[
                        cid * requests_per_client + r]
                    try:
                        out = _sat_stream_once(
                            host, int(port),
                            {"prompt_tokens": prompt,
                             "max_new_tokens": max_new, "stream": True})
                        ok = (out["error"] is None
                              and len(out["tokens"]) == max_new)
                        row = {"ok": ok, "ttft_s": out["ttft_s"],
                               "tokens": len(out["tokens"]),
                               "expected": max_new,
                               "error": out["error"]}
                    except Exception as e:  # noqa: BLE001 — gated below
                        row = {"ok": False, "ttft_s": None, "tokens": 0,
                               "expected": max_new, "error": repr(e)}
                    with lock:
                        streams.append(row)

            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            import urllib.request
            stats = json.loads(urllib.request.urlopen(
                urllib.request.Request(
                    f"http://{host}:{port}/llm",
                    data=json.dumps({"op": "leak_check"}).encode(),
                    method="POST"), timeout=60).read())
            dropped = [s for s in streams if not s["ok"]]
            ttfts = sorted(s["ttft_s"] for s in streams
                           if s["ttft_s"] is not None)
            p95 = ttfts[int(len(ttfts) * 0.95)] if ttfts \
                else float("inf")
            sat = {
                "streams": len(streams),
                "wall_s": round(wall, 2),
                "requests_per_s": round(len(streams) / wall, 2),
                "ttft_p50_s": round(ttfts[len(ttfts) // 2], 4)
                if ttfts else None,
                "ttft_p95_s": round(p95, 4),
                "dropped": dropped[:10],
                "preemptions": stats.get("preemptions"),
                "leaked_pages": stats.get("leaked_pages"),
                "slo": {
                    "zero_dropped_streams": bool(streams) and not dropped,
                    "ttft_p95_bounded": p95 <= slo_ttft_p95_s,
                    "zero_leaked_pages":
                    stats.get("leaked_pages") == 0,
                },
            }
            sat["passed"] = all(sat["slo"].values())
            result["serve_saturation"] = sat
            # requests-timeline artifact: flush the replica's reqtrace
            # ring into the GCS on demand, then fold every flushed
            # lifecycle into one chrome trace next to the SLO gates
            if timeline_artifact_path:
                from ray_tpu.llm import reqtrace
                if not reqtrace.reqtrace_disabled():
                    flushed = json.loads(urllib.request.urlopen(
                        urllib.request.Request(
                            f"http://{host}:{port}/llm",
                            data=json.dumps(
                                {"op": "reqtrace_flush"}).encode(),
                            method="POST"), timeout=60).read())
                    from ray_tpu.util import state as rt_state
                    trace = rt_state.serve_timeline(
                        timeline_artifact_path)
                    sat["requests_timeline"] = {
                        "path": timeline_artifact_path,
                        "spans": len(trace),
                        "replica_flushed": flushed.get("flushed"),
                    }
            serve.shutdown()
        finally:
            ray_tpu.shutdown()

    result["passed"] = (result["reqtrace_ab"]["passed"]
                        and result.get("serve_saturation",
                                       {}).get("passed", True))
    rab = result["reqtrace_ab"]
    _report("serve_sat_reqtrace_on_req_per_s",
            rab["reqtrace_on"]["requests_per_s"], "req/s")
    _report("serve_sat_reqtrace_off_req_per_s",
            rab["reqtrace_off"]["requests_per_s"], "req/s")
    _report("serve_sat_passed", 1.0 if result["passed"] else 0.0,
            "bool", gates={
                "reqtrace_" + k: v for k, v in rab["gates"].items()})
    if artifact_path:
        with open(artifact_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


class _SoakStreamer:
    """Streaming serve deployment for the soak: each request opens a
    token stream the proxy relays as chunked ndjson (the LLM serving
    wire shape), paced so streams span the fault windows."""

    def __init__(self, chunks: int = 40, delay_s: float = 0.15):
        self._chunks = chunks
        self._delay = delay_s
        self._streams = {}
        self._opened = 0

    def __call__(self, request):
        import uuid
        sid = uuid.uuid4().hex
        self._streams[sid] = 0
        self._opened += 1
        return {"__rtpu_stream__": sid}

    def stream_next(self, sid):
        sent = self._streams.get(sid)
        if sent is None or sent >= self._chunks:
            self._streams.pop(sid, None)
            return {"tokens": [], "done": True}
        time.sleep(self._delay)
        self._streams[sid] = sent + 1
        return {"tokens": [f"tok-{sent}"],
                "done": sent + 1 >= self._chunks}

    def cancel_stream(self, sid):
        self._streams.pop(sid, None)
        return True


def _soak_stream_once(host: str, port: int, path: str,
                      expected_chunks: int, timeout_s: float):
    """One streaming client request over a raw socket; returns the
    number of token lines received (== expected on a healthy stream)."""
    import socket

    s = socket.create_connection((host, int(port)), timeout=timeout_s)
    try:
        s.sendall((f"GET {path} HTTP/1.1\r\nHost: x\r\n"
                   "Content-Length: 2\r\n"
                   "Connection: close\r\n\r\n{}").encode())
        data = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            data += chunk
    finally:
        s.close()
    head, _, body = data.partition(b"\r\n\r\n")
    if b"200" not in head.split(b"\r\n", 1)[0]:
        raise RuntimeError(f"stream request failed: {head[:120]!r}")
    tokens = body.count(b"tok-")
    return tokens


def bench_soak(duration_s: float = 45.0, seed: int = 1234,
               nodes: int = 2, wave_size: int = 24,
               stream_chunks: int = 30, stream_delay_s: float = 0.15,
               drain_timeout_s: float = 20.0,
               slo_wave_p99_s: float = 20.0,
               slo_recover_s: float = 10.0,
               chaos_schedule: str = "",
               artifact_path: str = "") -> dict:
    """Standing chaos soak (ROADMAP item 5): sustained mixed load —
    a train-style task flood with an exactly-once audit trail plus
    streaming serve clients — on a multi-process cluster while a
    SEEDED fault script runs: scheduled transport chaos from t=0, a
    graceful rolling restart of every worker raylet, and one GCS
    ``kill -9`` mid-rollout. Gates: zero lost / zero doubled tasks,
    zero dropped streams, wave p99 under ``slo_wave_p99_s`` and
    post-fault recovery under ``slo_recover_s``. Returns (and
    optionally writes) the artifact dict."""
    import os
    import tempfile
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.state import api as state_api

    tmpdir = tempfile.mkdtemp(prefix="rtpu-soak-")
    persist = os.path.join(tmpdir, "gcs.db")
    audit = os.path.join(tmpdir, "audit.log")
    # The control-plane fault script: duplicate heartbeat replies from
    # t=0 (idempotency drill), a heartbeat delay window opening at 25%
    # of the run and closing at 60% — deterministic under the seed.
    schedule = chaos_schedule or (
        f"0:heartbeat:dup:0.05,"
        f"{duration_s * 0.25:g}:heartbeat:delay:0.3:0.05,"
        f"{duration_s * 0.6:g}:heartbeat:delay:0")
    cluster = Cluster(
        head_node_args={"num_cpus": 2},
        external_gcs=True, gcs_persist_path=persist,
        gcs_env={"RTPU_GCS_PERSIST": "wal",
                 "RTPU_CHAOS_SCHEDULE": schedule,
                 "RTPU_CHAOS_SEED": str(seed)})
    result = {"duration_s": duration_s, "seed": seed,
              "chaos_schedule": schedule, "nodes": nodes}
    try:
        cluster.connect()
        worker_nodes = [cluster.add_node(num_cpus=2)
                        for _ in range(nodes)]
        cluster.wait_for_nodes()
        # Arm the same schedule on the driver+raylet side registries.
        state_api.set_chaos(seed=seed, schedule=schedule)

        @ray_tpu.remote(num_cpus=1)
        def bump(i, marker):
            fd = os.open(marker, os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                         0o644)
            try:
                os.write(fd, f"{i}\n".encode())
            finally:
                os.close(fd)
            time.sleep(0.02)
            return i

        from ray_tpu.util.scheduling_strategies import \
            NodeAffinitySchedulingStrategy
        head_id = next(n["node_id"] for n in state_api.list_nodes()
                       if n["is_head"])
        streamer = serve.deployment(_SoakStreamer).options(
            ray_actor_options={
                "num_cpus": 0,
                # replicas live on the head (off the rolled nodes): a
                # drained replica's in-flight streams are killed by
                # contract — the zero-dropped-streams SLO exercises the
                # proxy + GCS failover planes under the rollout
                "scheduling_strategy": NodeAffinitySchedulingStrategy(
                    head_id, soft=True)})
        serve.run(streamer.bind(stream_chunks, stream_delay_s),
                  name="soak", route_prefix="/soak")
        addr = serve.api.get_http_address()
        host, port = addr.rsplit("://", 1)[-1].rsplit(":", 1)

        stop = threading.Event()
        wave_lat: list = []        # (t_rel, wall_s, n_tasks)
        task_errors: list = []
        submitted = []
        streams: list = []         # (t_rel, chunks_received, error)
        t0 = time.monotonic()

        def task_thread():
            base = 0
            while not stop.is_set():
                idx = list(range(base, base + wave_size))
                base += wave_size
                submitted.extend(idx)
                w0 = time.monotonic()
                try:
                    got = ray_tpu.get(
                        [bump.remote(i, audit) for i in idx],
                        timeout=180)
                    assert got == idx
                except Exception as e:  # noqa: BLE001 — gated below
                    task_errors.append(repr(e))
                    return
                wave_lat.append((round(w0 - t0, 2),
                                 time.monotonic() - w0, len(idx)))

        def stream_thread():
            while not stop.is_set():
                s0 = time.monotonic()
                try:
                    n = _soak_stream_once(
                        host, port, "/soak", stream_chunks,
                        timeout_s=duration_s + 120)
                    streams.append((round(s0 - t0, 2), n, None))
                except Exception as e:  # noqa: BLE001 — gated below
                    streams.append((round(s0 - t0, 2), 0, repr(e)))
                    return

        from ray_tpu._internal.threads import spawn_daemon
        threads = [spawn_daemon(task_thread, name="rtpu-soak-tasks"),
                   spawn_daemon(stream_thread, name="rtpu-soak-stream")]

        # --- the fault script (wall-clock scheduled, seed-stable) ----
        faults = []

        def _at(frac, name, fn):
            target = t0 + duration_s * frac
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            f0 = time.monotonic()
            fn()
            faults.append({"at_s": round(f0 - t0, 2), "fault": name,
                           "took_s": round(time.monotonic() - f0, 2)})

        replacements = {}

        def _roll(i):
            def _do():
                replacements[i] = cluster.restart_node(
                    worker_nodes[i], timeout_s=drain_timeout_s)
            return _do

        def _gcs_bounce():
            cluster.kill_gcs()
            time.sleep(0.5)
            cluster.restart_gcs()

        _at(0.15, "rolling_restart_node_0", _roll(0))
        _at(0.40, "gcs_kill9_restart", _gcs_bounce)
        if nodes > 1:
            _at(0.60, "rolling_restart_node_1", _roll(1))
        # run out the clock under load, then stop and settle
        remaining = t0 + duration_s - time.monotonic()
        if remaining > 0:
            time.sleep(remaining)
        last_fault_rel = faults[-1]["at_s"] + faults[-1]["took_s"]
        stop.set()
        for t in threads:
            t.join(timeout=duration_s + 180)

        # --- SLO gates ----------------------------------------------
        with open(audit) as f:
            executed = sorted(int(x) for x in f.read().split())
        lost = sorted(set(submitted) - set(executed))
        doubled = sorted(x for x in set(executed)
                         if executed.count(x) > 1)
        lats = sorted(w for (_t, w, _n) in wave_lat)
        p99 = lats[int(len(lats) * 0.99)] if lats else float("inf")
        p50 = lats[len(lats) // 2] if lats else float("inf")
        # time-to-recover: the gap from the last fault to the FIRST
        # wave completion after it (NOT that wave's own latency — a
        # long wedge followed by fast waves must not pass this gate)
        recover = [t_rel + w - last_fault_rel
                   for (t_rel, w, _n) in wave_lat
                   if t_rel + w >= last_fault_rel]
        recover_s = min(recover) if recover else None
        dropped_streams = [s for s in streams
                           if s[2] is not None or s[1] != stream_chunks]
        result.update({
            "waves": len(wave_lat),
            "tasks_submitted": len(submitted),
            "tasks_lost": lost[:10],
            "tasks_doubled": doubled[:10],
            "task_errors": task_errors,
            "wave_p50_s": round(p50, 3),
            "wave_p99_s": round(p99, 3),
            "streams_completed": len(streams),
            "streams_dropped": dropped_streams[:10],
            "recover_wave_s": round(recover_s, 3)
            if recover_s is not None else None,
            "faults": faults,
            "slo": {
                "zero_lost": not lost and not task_errors,
                "zero_doubled": not doubled,
                "zero_dropped_streams": bool(streams)
                and not dropped_streams,
                "p99_bounded": p99 <= slo_wave_p99_s,
                "recovered": recover_s is not None
                and recover_s <= slo_recover_s,
            },
        })
        result["passed"] = all(result["slo"].values())
        _report("soak_wave_p99_s", p99, "s")
        _report("soak_streams_completed", len(streams), "streams")
        _report("soak_passed", 1.0 if result["passed"] else 0.0, "bool",
                slo=result["slo"])
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 — teardown best-effort
            logging.getLogger(__name__).debug(
                "serve shutdown after soak failed", exc_info=True)
    finally:
        cluster.shutdown()
    if artifact_path:
        with open(artifact_path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    # Host-plane workloads, CPU by design (hidden-64 toys): this process
    # runs JAX itself, so on a chip host it stays off the chip by
    # environment (read when jax is first imported).
    os.environ["JAX_PLATFORMS"] = "cpu"
    parser = argparse.ArgumentParser()
    parser.add_argument("--which", default="all")
    parser.add_argument("--soak-seconds", type=float, default=45.0)
    parser.add_argument("--soak-seed", type=int, default=1234)
    parser.add_argument("--soak-artifact", default="")
    parser.add_argument("--saturation-seed", type=int, default=1234)
    parser.add_argument("--saturation-artifact",
                        default="tests/artifacts_serve_saturation.json")
    args = parser.parse_args()
    which = args.which
    if which == "soak":
        # builds its OWN multi-process cluster (killable external GCS)
        bench_soak(duration_s=args.soak_seconds, seed=args.soak_seed,
                   artifact_path=args.soak_artifact)
        return
    if which == "serve_saturation":
        # does its own init (in-process engine A/B first, then the
        # serve-proxy streaming soak)
        bench_serve_saturation(seed=args.saturation_seed,
                               artifact_path=args.saturation_artifact)
        return
    import ray_tpu
    ray_tpu.init(num_cpus=8, object_store_memory=1 << 30)
    try:
        if which in ("all", "ppo"):
            bench_ppo()
        if which in ("all", "impala"):
            bench_impala()
        if which in ("all", "data"):
            bench_data()
        if which in ("all", "llm"):
            bench_llm()
        if which in ("all", "serve"):
            bench_serve()
    finally:
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
