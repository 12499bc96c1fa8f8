"""Serve-plane request observatory (PR 18).

Unit layers first (event ring bound, flush/collect roundtrip, the
bucket decomposition and percentile folds over synthetic lifecycles,
the serve SLO default rules with deterministic evaluate_once), then
the engine arm: a deterministic page-pressure run whose PREEMPTED/
PARKED/RESUMED spans must show up in the serve timeline and whose TTFT
inflation why_slow must charge to the park bucket, the park-seconds
histogram satellite, per-tenant folds, request-id echo through the
real serve proxy, and the RTPU_NO_REQTRACE kill switch in a subprocess
(zero rings, zero extra threads)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ray_tpu._internal.config import CONFIG
from ray_tpu.llm import (GenerationRequest, PagedEngineConfig,
                         PagedLLMEngine)
from ray_tpu.llm import reqtrace
from ray_tpu.models.llama import LlamaConfig


def _override(**kv):
    old = {k: getattr(CONFIG, k) for k in kv}
    CONFIG.apply_system_config(kv)
    return old


def tiny_model():
    return LlamaConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=4, max_seq_len=256, remat=False,
                       use_flash=False, attention_impl="reference")


class FakeGcs:
    def __init__(self):
        self.kv = {}

    def put(self, ns, key, value):
        self.kv[(ns, key)] = value

    def get(self, ns, key):
        return self.kv.get((ns, key))

    def keys(self, ns, prefix):
        return [k for (n, k) in self.kv if n == ns
                and k.startswith(prefix)]


# ---------------------------------------------------------------------------
# recorder ring + flush/collect
# ---------------------------------------------------------------------------


def test_event_ring_bounded_keeps_newest():
    old = _override(reqtrace_max_events=8)
    try:
        rec = reqtrace._Recorder()
        for i in range(50):
            rec.record(f"r{i}", reqtrace.QUEUED, float(i), {})
        evs = rec.events()
        assert len(evs) == 8
        assert evs[-1][0] == "r49"
    finally:
        CONFIG.apply_system_config(old)


def test_record_flush_collect_merges_across_processes():
    reqtrace.clear()
    reqtrace.record("req-a", reqtrace.QUEUED, engine="paged",
                    tenant="acme", dropped=None)
    reqtrace.record("req-a", reqtrace.ADMITTED, shared_pages=2)
    gcs = FakeGcs()
    assert reqtrace.flush(gcs=gcs, key="111")
    # a second process's ring (the proxy) carries the ROUTED event
    gcs.put(reqtrace.REQTRACE_KV_NS, "222", json.dumps(
        {"pid": 222, "events":
         [["req-a", reqtrace.ROUTED, 0.0, {"route": "/llm"}]]}).encode())
    payloads = reqtrace.collect(gcs)
    assert len(payloads) == 2
    rows = reqtrace.request_events(payloads)["req-a"]
    # time-ordered cross-process merge; None args dropped at record()
    assert [r["event"] for r in rows] == [
        reqtrace.ROUTED, reqtrace.QUEUED, reqtrace.ADMITTED]
    assert rows[1]["args"] == {"engine": "paged", "tenant": "acme"}
    reqtrace.clear()


# ---------------------------------------------------------------------------
# bucket decomposition + folds over a synthetic lifecycle
# ---------------------------------------------------------------------------


def _payload(events):
    return {"pid": 1, "events": events}


def test_why_slow_buckets_sum_to_wall_clock():
    # queue 1s -> park 2s -> prefill window 1s (0.6 compute, 0.2
    # compile inside one chunk) -> decode 3s -> finished
    evs = [
        ["r1", reqtrace.QUEUED, 10.0, {"tenant": "acme"}],
        ["r1", reqtrace.PARKED, 11.0, {"reason": "no_pages"}],
        ["r1", reqtrace.ADMITTED, 13.0, {}],
        ["r1", reqtrace.RESUMED, 13.0, {}],
        ["r1", reqtrace.PREFILL_CHUNK, 13.8,
         {"tokens": 32, "dur_s": 0.8, "compile_s": 0.2}],
        ["r1", reqtrace.DECODE, 14.0, {"ttft_s": 4.0, "park_s": 2.0}],
        ["r1", reqtrace.FINISHED, 17.0, {"tokens": 24}],
    ]
    report = reqtrace.why_slow("r1", [_payload(evs)])
    assert report["request_id"] == "r1"
    assert report["outcome"] == reqtrace.FINISHED
    assert report["tenant"] == "acme"
    assert report["e2e_s"] == pytest.approx(7.0)
    b = report["e2e_buckets"]
    assert b["queue"] == pytest.approx(1.0)
    assert b["park"] == pytest.approx(2.0)
    assert b["prefill_compute"] == pytest.approx(0.6)
    assert b["compile"] == pytest.approx(0.2)
    assert b["decode"] == pytest.approx(3.0)
    # prefill window (1s) minus compute minus compile = interleave
    assert b["other"] == pytest.approx(0.2)
    assert sum(b.values()) == pytest.approx(report["e2e_s"])
    # TTFT horizon clips at the first DECODE: no decode bucket yet
    assert report["ttft_s"] == pytest.approx(4.0)
    tb = report["ttft_buckets"]
    assert tb["decode"] == pytest.approx(0.0)
    assert tb["park"] == pytest.approx(2.0)
    assert sum(tb.values()) == pytest.approx(report["ttft_s"])
    # unique-prefix lookup resolves; ambiguous/unknown ids report it
    assert reqtrace.why_slow("r", [_payload(evs)])["request_id"] == "r1"
    assert "error" in reqtrace.why_slow("zz", [_payload(evs)])


def test_fold_requests_by_tenant_percentiles():
    evs = []
    for i, (tenant, ttft) in enumerate(
            [("acme", 0.1), ("acme", 0.3), ("beta", 0.2)]):
        rid = f"f{i}"
        t0 = 10.0 * i
        evs += [
            [rid, reqtrace.QUEUED, t0, {"tenant": tenant}],
            [rid, reqtrace.ADMITTED, t0 + 0.01, {}],
            [rid, reqtrace.DECODE, t0 + ttft, {}],
            [rid, reqtrace.FINISHED, t0 + 1.0, {}],
        ]
    evs += [["f3", reqtrace.QUEUED, 50.0, {}]]  # unlabeled, in flight
    fold = reqtrace.fold_requests([_payload(evs)], by="tenant")
    assert fold["by"] == "tenant"
    assert set(fold["groups"]) == {"acme", "beta", "-"}
    acme = fold["groups"]["acme"]
    assert acme["requests"] == 2 and acme["finished"] == 2
    # upper-nearest-rank percentiles: p50 of [0.1, 0.3] is the 2nd
    assert acme["ttft_p50_s"] == pytest.approx(0.3)
    assert acme["ttft_p95_s"] == pytest.approx(0.3)
    assert acme["e2e_p95_s"] == pytest.approx(1.0)
    assert fold["groups"]["-"]["in_flight"] == 1
    assert fold["groups"]["-"]["ttft_p50_s"] is None


def test_chrome_trace_states_and_instants():
    evs = [
        ["r1", reqtrace.QUEUED, 1.0, {}],
        ["r1", reqtrace.ADMITTED, 2.0, {}],
        ["r1", reqtrace.DECODE, 3.0, {}],
        ["r1", reqtrace.PREEMPTED, 4.0, {"reason": "page_pressure"}],
        ["r1", reqtrace.PARKED, 4.0, {"reason": "page_pressure"}],
        ["r1", reqtrace.ADMITTED, 5.0, {}],
        ["r1", reqtrace.RESUMED, 5.0, {}],
        ["r1", reqtrace.DECODE, 5.5, {}],
        ["r1", reqtrace.FINISHED, 6.0, {}],
    ]
    rows = reqtrace.to_chrome_trace([_payload(evs)])
    spans = [(r["name"], r["ts"], r["dur"]) for r in rows
             if r["ph"] == "X"]
    assert ("queue", 1.0e6, 1.0e6) in spans
    assert ("park", 4.0e6, 1.0e6) in spans
    assert ("decode", 3.0e6, 1.0e6) in spans
    instants = [r["name"] for r in rows if r["ph"] == "i"]
    assert "preempted" in instants and "resumed" in instants
    assert "finished" in instants
    assert all(r["tid"] == "r1" and r["pid"] == "serve" for r in rows)


# ---------------------------------------------------------------------------
# serve SLO default rules (deterministic evaluate_once)
# ---------------------------------------------------------------------------


def _hist_snap(name, boundaries, buckets, total, count):
    return {"name": name, "kind": "histogram", "tag_keys": ["engine"],
            "series": [[["paged"], {"boundaries": list(boundaries),
                                    "buckets": list(buckets),
                                    "sum": total, "count": count}]]}


def _gauge_snap(name, value):
    return {"name": name, "kind": "gauge", "tag_keys": ["engine"],
            "series": [[["paged"], value]]}


def test_serve_slo_rules_fire_and_stay_quiet():
    from ray_tpu._internal.alerts import AlertEngine, default_rules
    rules = [r for r in default_rules()
             if r.name.startswith("serve_")]
    assert {r.name for r in rules} == {
        "serve_ttft_p95", "serve_queue_age", "serve_kv_occupancy"}
    emitted = []
    engine = AlertEngine(rules=rules, emit=emitted.append)
    # hot: TTFT p95 needs the 5s bucket (> 2s SLO), queue age 40s
    # (> 30s), pool 97% full (> 95%)
    hot = [
        _hist_snap("rtpu_llm_ttft_seconds", [0.5, 5.0],
                   [10, 10], 30.0, 20),
        _gauge_snap("rtpu_lease_queue_age_seconds", 40.0),
        _gauge_snap("rtpu_llm_kv_page_utilization", 0.97),
    ]
    fired = engine.evaluate_once(snapshots=hot, now=100.0)
    assert {r["rule"] for r in fired} == {
        "serve_ttft_p95", "serve_queue_age", "serve_kv_occupancy"}
    assert all(r["severity"] == "WARNING" for r in fired)
    # healthy: every p95/max sits under its SLO — nothing fires
    cool_engine = AlertEngine(rules=[r for r in default_rules()
                                     if r.name.startswith("serve_")],
                              emit=lambda r: None)
    cool = [
        _hist_snap("rtpu_llm_ttft_seconds", [0.5, 5.0],
                   [20, 0], 2.0, 20),
        _gauge_snap("rtpu_lease_queue_age_seconds", 1.0),
        _gauge_snap("rtpu_llm_kv_page_utilization", 0.40),
    ]
    assert cool_engine.evaluate_once(snapshots=cool, now=100.0) == []


# ---------------------------------------------------------------------------
# engine arm: deterministic page pressure -> park/preempt in the trace
# ---------------------------------------------------------------------------


def _park_count(reason=None):
    from ray_tpu.llm._metrics import llm_metrics
    snap = llm_metrics().park_seconds.snapshot()
    ei = snap["tag_keys"].index("engine")
    ri = snap["tag_keys"].index("reason")
    return sum(value["count"] for tag_values, value in snap["series"]
               if tag_values[ei] == "paged"
               and (reason is None or tag_values[ri] == reason))


def _drain(engine):
    steps = 0
    while engine.has_work():
        engine.step()
        steps += 1
        assert steps < 100_000


def test_page_pressure_lifecycle_timeline_and_why_slow():
    """A 13-usable-page pool under 6 requests must park admissions and
    preempt decoders; the traced lifecycles must show it — PARKED/
    PREEMPTED/RESUMED spans in the serve timeline, TTFT inflation
    charged to the park bucket by why_slow, the park-seconds histogram
    observed, and per-tenant folds carrying the labels down from
    GenerationRequest."""
    reqtrace.clear()
    park_count0 = _park_count()
    engine = PagedLLMEngine(PagedEngineConfig(
        model=tiny_model(), max_batch=4, max_len=64, page_size=8,
        num_pages=14, prefill_buckets=(16, 32, 64)))
    rng = np.random.RandomState(4)
    results = {}
    for i in range(6):
        # 4-page prompts against 13 usable pages: admission itself
        # blocks (no_pages park before the first token) AND decode
        # growth preempts (page_pressure park after it)
        prompt = [int(t) for t in rng.randint(1, 128, size=30)]

        def on_done(request, tokens, i=i):
            results[i] = tokens
        engine.submit(
            GenerationRequest(prompt_tokens=prompt, max_new_tokens=30,
                              request_id=f"pp-{i}",
                              tenant="acme" if i % 2 else "beta",
                              route="/llm"),
            done_callback=on_done)
    _drain(engine)
    assert engine.stats()["preemptions"] > 0
    assert len(results) == 6 and engine.page_leak_check() == 0

    # park histogram satellite: at least one no_pages park observed
    assert _park_count() > park_count0

    payloads = [reqtrace._recorder().payload()]
    rows = reqtrace.to_chrome_trace(payloads)
    names = {r["name"] for r in rows}
    assert {"queue", "prefill", "decode", "park"} <= names
    assert {"preempted", "resumed", "finished"} <= {
        r["name"] for r in rows if r["ph"] == "i"}

    by_rid = reqtrace.request_events(payloads)
    assert set(by_rid) == {f"pp-{i}" for i in range(6)}
    # every request ends FINISHED with full token accounting
    preempted = []
    for rid, evs in by_rid.items():
        kinds = [e["event"] for e in evs]
        assert kinds[0] == reqtrace.QUEUED
        assert kinds[-1] == reqtrace.FINISHED
        assert evs[-1]["args"]["tokens"] == 30
        if reqtrace.PREEMPTED in kinds:
            preempted.append(rid)
    assert preempted, "page pressure must preempt at least one request"

    # why_slow: a preempted request's e2e carries park time, and a
    # request parked before admission has its TTFT charged to park
    report = reqtrace.why_slow(preempted[0], payloads)
    assert report["preemptions"] >= 1
    assert report["e2e_buckets"]["park"] > 0
    parked_ttfts = [
        reqtrace.why_slow(rid, payloads) for rid in by_rid
        if any(e["event"] == reqtrace.PARKED
               and e["ts"] < next(x["ts"] for x in by_rid[rid]
                                  if x["event"] == reqtrace.DECODE)
               for e in by_rid[rid])]
    assert parked_ttfts, "admission parks must precede a first token"
    assert any(r["ttft_buckets"]["park"] > 0 for r in parked_ttfts)
    for r in parked_ttfts:
        assert sum(r["ttft_buckets"].values()) == pytest.approx(
            r["ttft_s"], abs=1e-4)

    # per-tenant fold: labels rode GenerationRequest into QUEUED
    fold = reqtrace.fold_requests(payloads, by="tenant")
    assert set(fold["groups"]) == {"acme", "beta"}
    assert fold["groups"]["acme"]["requests"] == 3
    assert fold["groups"]["beta"]["finished"] == 3
    assert fold["groups"]["acme"]["ttft_p95_s"] is not None
    by_route = reqtrace.fold_requests(payloads, by="route")
    assert by_route["groups"]["/llm"]["requests"] == 6
    reqtrace.clear()


# ---------------------------------------------------------------------------
# serve plane: request-id echo through the real proxy
# ---------------------------------------------------------------------------


def _raw_http(host, port, method, path, body, headers=None):
    import socket
    payload = json.dumps(body).encode()
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    s = socket.create_connection((host, int(port)), timeout=240)
    s.sendall((f"{method} {path} HTTP/1.1\r\nHost: x\r\n{extra}"
               f"Content-Length: {len(payload)}\r\n"
               "Connection: close\r\n\r\n").encode() + payload)
    data = b""
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        data += chunk
    s.close()
    head, _, raw = data.partition(b"\r\n\r\n")
    return head.decode("latin1"), raw


def _chunk_lines(raw):
    lines = []
    buf = raw
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
            if ln.strip():
                lines.append(json.loads(ln))
    return lines


@pytest.mark.timeout_s(600)
def test_request_id_propagates_and_echoes(llm_cluster):
    """X-RTPU-Request-Id end-to-end: the client's id is accepted by the
    proxy, threaded through router -> replica -> engine, echoed on the
    chunked-stream preamble AND every ndjson batch, and stamped on the
    engine's lifecycle events; absent a client id the proxy mints one
    and still echoes it on plain responses."""
    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMServer

    cfg = PagedEngineConfig(model=tiny_model(), max_batch=2, max_len=96,
                            page_size=8, num_pages=64,
                            prefill_buckets=(8, 16))
    app = serve.deployment(LLMServer, name="rt").bind(cfg)
    serve.run(app, name="llm", route_prefix="/llm",
              wait_for_ready_timeout_s=240)
    addr = serve.get_http_address().replace("http://", "")
    host, port = addr.rsplit(":", 1)

    head, raw = _raw_http(
        host, port, "POST", "/llm",
        {"prompt_tokens": [1, 2, 3], "max_new_tokens": 6,
         "stream": True},
        headers={"X-RTPU-Request-Id": "client-chosen-id",
                 "X-RTPU-Tenant": "acme"})
    assert "X-RTPU-Request-Id: client-chosen-id" in head
    lines = _chunk_lines(raw)
    token_lines = [ln for ln in lines if ln.get("tokens")]
    assert token_lines
    assert all(ln["request_id"] == "client-chosen-id"
               for ln in token_lines)

    # no client id: the proxy mints one and echoes it on the plain path
    head2, _ = _raw_http(host, port, "POST", "/llm",
                         {"prompt_tokens": [4, 5], "max_new_tokens": 2})
    minted = [ln.split(":", 1)[1].strip()
              for ln in head2.split("\r\n")
              if ln.lower().startswith("x-rtpu-request-id:")]
    assert minted and len(minted[0]) == 32
    serve.shutdown()


# ---------------------------------------------------------------------------
# kill switch: zero rings, zero flushes, zero extra threads
# ---------------------------------------------------------------------------


_KILL_SWITCH_SCRIPT = """
import threading, time
import ray_tpu.llm.reqtrace as rt
assert rt.reqtrace_disabled()
for i in range(100):
    rt.record(f"r{i}", rt.QUEUED, tenant="acme")
assert rt._RECORDER is None, "kill switch must never construct a ring"
assert rt.events() == []
assert rt.flush(gcs=object(), key="x") is False
time.sleep(0.05)
assert threading.active_count() == 1, threading.enumerate()
print("KILLSWITCH-OK")
"""


def test_kill_switch_subprocess_zero_rings_zero_threads():
    env = dict(os.environ, RTPU_NO_REQTRACE="1")
    out = subprocess.run(
        [sys.executable, "-c", _KILL_SWITCH_SCRIPT], env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "KILLSWITCH-OK" in out.stdout


def test_kill_switch_record_noop_in_process():
    old = _override(no_reqtrace=True)
    try:
        reqtrace.clear()
        before = reqtrace.events()
        reqtrace.record("kx", reqtrace.QUEUED)
        assert reqtrace.events() == before
        assert reqtrace.flush(gcs=FakeGcs()) is False
    finally:
        CONFIG.apply_system_config(old)


@pytest.mark.parametrize("count", [0, 1, reqtrace._FLUSH_SLICE,
                                   reqtrace._FLUSH_SLICE + 1, 300])
def test_flush_encodes_the_whole_ring_in_slices(count):
    """The flusher encodes the ring a slice at a time (it shares the GIL
    with the engine's thread): what it puts is `json.dumps(payload())`."""
    rec = reqtrace._Recorder()
    for i in range(count):
        rec.record(f"r{i % 7}", reqtrace.STREAMED, float(i),
                   {"polls": i, "hold_sum_s": 0.25, "route": "/llm"})
    sliced = json.loads(reqtrace._payload_json(rec.events()))
    assert sliced == json.loads(json.dumps(rec.payload()))
    assert len(sliced["events"]) == count
