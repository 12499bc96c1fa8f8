"""Observability tests: metrics registry + Prometheus exposition,
dashboard REST (state + jobs + metrics endpoints), job submission
lifecycle incl. stop and logs, CLI status/list against a live head
(reference coverage: dashboard/modules/job/tests, tests/test_metrics_*,
util/state tests)."""

import json
import os
import sys
import time
import urllib.request

import pytest

import ray_tpu


@pytest.fixture
def obs_cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=200 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _post(url, payload, timeout=15):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_registry_and_prometheus_text():
    from ray_tpu.util.metrics import (Counter, Gauge, Histogram,
                                      prometheus_text)
    c = Counter("test_requests_total", "reqs", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    g = Gauge("test_inflight", "gauge")
    g.set(7)
    h = Histogram("test_latency_s", "hist", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = prometheus_text([c.snapshot(), g.snapshot(), h.snapshot()])
    assert 'test_requests_total{route="/a"} 3.0' in text
    assert 'test_requests_total{route="/b"} 1.0' in text
    assert "test_inflight 7.0" in text
    assert 'test_latency_s_bucket{le="0.1"} 1' in text
    assert 'test_latency_s_bucket{le="+Inf"} 3' in text
    assert "test_latency_s_count 3" in text
    with pytest.raises(ValueError):
        c.inc(tags={"bogus": "x"})
    with pytest.raises(ValueError):
        c.inc(-1)


def test_prometheus_text_label_escaping_roundtrip():
    """Tag values carrying commas/quotes/newlines survive the snapshot →
    exposition pipeline intact (the old ",".join series keys split them
    apart at the wrong places)."""
    from ray_tpu.util.metrics import Counter, prometheus_text
    c = Counter("test_escape_total", "esc", tag_keys=("k",))
    nasty = 'a,b"c\nd\\e'
    c.inc(tags={"k": nasty})
    c.inc(tags={"k": nasty})  # same series, not two
    c.inc(tags={"k": "plain"})
    text = prometheus_text([c.snapshot()])
    assert 'test_escape_total{k="a,b\\"c\\nd\\\\e"} 2.0' in text
    assert 'test_escape_total{k="plain"} 1.0' in text
    # exactly one # TYPE line per metric, no duplicate series lines
    assert text.count("# TYPE test_escape_total counter") == 1
    assert text.count("test_escape_total{") == 2


def test_prometheus_text_multiprocess_merge():
    """Same series reported by several processes folds into ONE sample
    line: counters sum, gauges last-write-wins, histograms merge
    buckets/sum/count (duplicate sample lines are invalid exposition)."""
    import copy

    from ray_tpu.util.metrics import (Counter, Gauge, Histogram,
                                      prometheus_text)
    c = Counter("test_merge_total", "c", tag_keys=("k",))
    c.inc(2, tags={"k": "x"})
    g = Gauge("test_merge_gauge", "g")
    g.set(5)
    h = Histogram("test_merge_hist", "h", boundaries=[1.0, 10.0])
    h.observe(0.5)
    h.observe(20.0)
    snap_c, snap_g, snap_h = c.snapshot(), g.snapshot(), h.snapshot()
    other_c = copy.deepcopy(snap_c)
    other_g = copy.deepcopy(snap_g)
    other_g["series"][0][1] = 9.0
    other_h = copy.deepcopy(snap_h)
    text = prometheus_text(
        [snap_c, snap_g, snap_h, other_c, other_g, other_h])
    assert 'test_merge_total{k="x"} 4.0' in text
    assert text.count("test_merge_total{") == 1
    assert "test_merge_gauge 9.0" in text          # last snapshot wins
    assert 'test_merge_hist_bucket{le="1.0"} 2' in text
    assert 'test_merge_hist_bucket{le="+Inf"} 4' in text
    assert "test_merge_hist_count 4" in text
    assert "test_merge_hist_sum 41.0" in text


def test_prometheus_text_empty_histogram():
    """A histogram declared but never observed renders its metadata
    lines alone (and never crashes the exposition)."""
    from ray_tpu.util.metrics import Histogram, prometheus_text
    h = Histogram("test_empty_hist", "never observed",
                  boundaries=[1.0])
    text = prometheus_text([h.snapshot()])
    assert "# TYPE test_empty_hist histogram" in text
    assert "# HELP test_empty_hist never observed" in text
    assert "test_empty_hist_bucket" not in text
    # legacy dict-form snapshots (older KV payloads) still render
    legacy = {"name": "test_legacy_total", "kind": "counter",
              "description": "", "tag_keys": ["k"],
              "series": {"v": 3.0}}
    assert 'test_legacy_total{k="v"} 3.0' in prometheus_text([legacy])


# ---------------------------------------------------------------------------
# dashboard REST + jobs
# ---------------------------------------------------------------------------

def test_dashboard_state_and_job_lifecycle(obs_cluster, tmp_path):
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    address = start_dashboard()

    # Run something so state endpoints have content.
    @ray_tpu.remote
    def noop():
        return 1
    ray_tpu.get([noop.remote() for _ in range(3)])

    status, body = _get(f"{address}/-/healthz")
    assert body == b"ok"
    _s, body = _get(f"{address}/api/cluster_status")
    snap = json.loads(body)
    assert snap["resources_total"].get("CPU", 0) >= 4
    _s, body = _get(f"{address}/api/nodes")
    assert len(json.loads(body)) == 1
    time.sleep(1.5)  # task event flush
    _s, body = _get(f"{address}/api/tasks")
    assert any(t["name"].endswith("noop") for t in json.loads(body))
    _s, body = _get(f"{address}/metrics")
    assert b"# TYPE" in body or body == b"\n"  # exposition shape

    # Job submission end to end over HTTP.
    client = JobSubmissionClient(address)
    marker = tmp_path / "ran.txt"
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('hello-from-job'); "
                   f"open('{marker}','w').write('1')\"")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if client.get_job_status(job_id) in JobStatus.TERMINAL:
            break
        time.sleep(0.25)
    assert client.get_job_status(job_id) == JobStatus.SUCCEEDED
    assert marker.exists()
    assert "hello-from-job" in client.get_job_logs(job_id)
    jobs = client.list_jobs()
    assert any(j["submission_id"] == job_id for j in jobs)


def test_job_stop_and_failure(obs_cluster):
    from ray_tpu.job_submission import JobManager, JobStatus
    manager = JobManager()

    # Failing entrypoint -> FAILED with rc message.
    fail_id = manager.submit_job(
        entrypoint=f"{sys.executable} -c 'import sys; sys.exit(3)'")
    status = manager.wait_until_finished(fail_id, timeout_s=60)
    assert status == JobStatus.FAILED
    assert "rc=3" in manager.get_job_info(fail_id)["message"]

    # Long-running entrypoint -> stop() -> STOPPED.
    stop_id = manager.submit_job(
        entrypoint=f"{sys.executable} -c 'import time; time.sleep(600)'")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if manager.get_job_status(stop_id) == JobStatus.RUNNING:
            break
        time.sleep(0.2)
    assert manager.stop_job(stop_id)
    status = manager.wait_until_finished(stop_id, timeout_s=60)
    assert status == JobStatus.STOPPED


# ---------------------------------------------------------------------------
# CLI (in-process invocation against a live head)
# ---------------------------------------------------------------------------

def test_cli_status_list_timeline(obs_cluster, tmp_path, capsys):
    from ray_tpu import cli

    @ray_tpu.remote
    def touch():
        return "x"
    ray_tpu.get(touch.remote())
    time.sleep(1.2)

    class A:
        address = None
    cli.cmd_status(A())
    out = capsys.readouterr().out
    assert "nodes: 1" in out

    class L:
        address = None
        what = "actors"
        limit = 10
    cli.cmd_list(L())

    class T:
        address = None
        output = str(tmp_path / "trace.json")
    cli.cmd_timeline(T())
    trace = json.loads((tmp_path / "trace.json").read_text())
    assert isinstance(trace, list)


def test_cli_head_process_roundtrip(tmp_path):
    """Real `start --head` subprocess: address file, remote status, stop."""
    import subprocess
    env = dict(os.environ)
    try:
        os.unlink("/tmp/rtpu/head_address")
    except FileNotFoundError:
        pass
    head = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.cli", "start", "--head",
         "--num-cpus", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if os.path.exists("/tmp/rtpu/head_address"):
                break
            time.sleep(0.2)
        assert os.path.exists("/tmp/rtpu/head_address")
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.cli", "status"],
            env=env, capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "nodes: 1" in out.stdout
        out = subprocess.run(
            [sys.executable, "-m", "ray_tpu.cli", "submit", "--wait",
             "--", sys.executable, "-c", "print(40+2)"],
            env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "42" in out.stdout
        assert "SUCCEEDED" in out.stdout
    finally:
        head.terminate()
        try:
            head.wait(timeout=15)
        except subprocess.TimeoutExpired:
            head.kill()
        try:
            os.unlink("/tmp/rtpu/head_address")
        except FileNotFoundError:
            pass


def test_worker_logs_stream_to_driver(obs_cluster, capfd):
    """Worker print() output arrives at the driver via the WORKER_LOGS
    pubsub stream (reference: _private/log_monitor.py)."""
    import time

    import ray_tpu

    @ray_tpu.remote
    def shout():
        print("hello-from-worker-xyzzy")
        return 1

    assert ray_tpu.get(shout.remote(), timeout=120) == 1
    deadline = time.monotonic() + 30
    seen = ""
    while time.monotonic() < deadline:
        out, _err = capfd.readouterr()
        seen += out
        if "hello-from-worker-xyzzy" in seen:
            break
        time.sleep(0.3)
    assert "hello-from-worker-xyzzy" in seen
    assert "(pid=" in seen


def test_profile_capture_endpoints(obs_cluster):
    """On-demand worker profiling: pystack collapsed stacks and a jax
    xplane zip (reference: dashboard/modules/reporter/
    profile_manager.py:82)."""
    import time
    import zipfile
    import io as _io

    import ray_tpu
    from ray_tpu._internal.core_worker import get_core_worker

    @ray_tpu.remote
    class Busy:
        def spin(self, seconds):
            t0 = time.monotonic()
            x = 0
            while time.monotonic() - t0 < seconds:
                x += 1
            return x

        def pid(self):
            import os
            return os.getpid()

    actor = Busy.remote()
    pid = ray_tpu.get(actor.pid.remote(), timeout=120)
    spin_ref = actor.spin.remote(4.0)
    worker = get_core_worker()
    raylet = worker.clients.get(worker.raylet_address)
    reply = raylet.call_sync("profile_worker", pid=pid, kind="pystack",
                             duration_s=1.0, timeout=90)
    assert reply.get("format") == "collapsed-stacks"
    text = reply["data"].decode()
    assert "spin" in text  # the busy method shows up in sampled stacks
    reply = raylet.call_sync("profile_worker", pid=pid, kind="jax",
                             duration_s=0.5, timeout=120)
    assert reply.get("format") == "xplane-zip"
    zf = zipfile.ZipFile(_io.BytesIO(reply["data"]))
    assert len(zf.namelist()) >= 1
    ray_tpu.get(spin_ref, timeout=120)


def test_trace_context_propagates_to_tasks(obs_cluster):
    """Span context crosses the submit boundary: a task launched inside
    trace_span() sees the caller's (trace_id, span_id) and its own
    nested spans share the trace id (reference:
    util/tracing/tracing_helper.py:54-88)."""
    from ray_tpu.util.tracing import get_trace_context, trace_span

    @ray_tpu.remote
    def probe():
        from ray_tpu.util.tracing import (get_trace_context as g,
                                          trace_span as ts)
        inherited = g()
        with ts("inner") as (tid, sid):
            return {"inherited": inherited, "inner": (tid, sid)}

    with trace_span("outer") as (trace_id, span_id):
        out = ray_tpu.get(probe.remote(), timeout=120)
    assert tuple(out["inherited"]) == (trace_id, span_id)
    assert out["inner"][0] == trace_id        # same trace
    assert out["inner"][1] != span_id         # its own span
    # outside the span nothing leaks
    assert ray_tpu.get(probe.remote(), timeout=120)["inherited"] is None


def test_node_agent_stats_route(obs_cluster):
    """Per-node agent stats via the head (reference: dashboard/agent.py
    + reporter_agent.py): /api/nodes/<id>/stats proxies to that node's
    raylet and reports host memory, load, and per-worker RSS."""
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.util import state as st

    address = start_dashboard()

    @ray_tpu.remote
    def warm():
        return 1
    ray_tpu.get(warm.remote())  # ensure at least one worker exists

    node_id = st.list_nodes()[0]["node_id"]
    _s, body = _get(f"{address}/api/nodes/{node_id}/stats")
    stats = json.loads(body)
    assert stats["node_id"] == node_id
    assert stats["mem_total_bytes"] > 0
    assert len(stats["loadavg"]) == 3
    assert stats["resources_total"].get("CPU", 0) >= 4
    workers = stats["workers"]
    assert workers and any(w.get("rss_bytes", 0) > 0 for w in workers)
    assert all({"worker_id", "pid", "state"} <= set(w) for w in workers)


@pytest.mark.timeout_s(600)
def test_llm_serving_flight_recorder(tmp_path, monkeypatch, capsys):
    """End-to-end flight recorder over a real LLM serving request:
    /metrics exposes populated TTFT + per-token-latency histograms with
    correct label escaping, the timeline shows the task's
    SUBMITTED→RUNNING→FINISHED phases, and get_trace() assembles a span
    tree crossing the driver→replica process hop."""
    # Replica worker processes inherit a fast flush so the scrape
    # assertions don't wait out the 5 s default interval.
    monkeypatch.setenv("RTPU_metrics_report_interval_s", "1.0")
    import ray_tpu
    ray_tpu.init(num_cpus=4, object_store_memory=300 * 1024 * 1024)
    try:
        from ray_tpu import cli, serve
        from ray_tpu.dashboard import start_dashboard
        from ray_tpu.llm import build_llm_deployment
        from ray_tpu.llm.paged import PagedEngineConfig
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.util import metrics as metrics_mod
        from ray_tpu.util import state as st
        from ray_tpu.util.tracing import trace_span

        model = LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=256,
            remat=False, use_flash=False, attention_impl="reference")
        cfg = PagedEngineConfig(model=model, max_batch=2, max_len=96,
                                page_size=8, num_pages=64,
                                prefill_buckets=(8, 16))
        app = build_llm_deployment(cfg)
        handle = serve.run(app, name="llm", route_prefix="/llm",
                           http_options=serve.HTTPOptions(port=0),
                           wait_for_ready_timeout_s=240)

        # One normal task too, so the timeline has a LEASED phase row.
        @ray_tpu.remote
        def warmup():
            return 1
        assert ray_tpu.get(warmup.remote(), timeout=120) == 1

        with trace_span("client") as (trace_id, _span_id):
            out = handle.generate.remote(
                [1, 2, 3], max_new_tokens=4).result(timeout_s=240)
        assert out["num_generated"] == 4

        # -- /metrics: populated LLM histograms + label escaping -------
        from ray_tpu.util.metrics import Counter
        c = Counter("test_e2e_escape_total", "esc", tag_keys=("k",))
        c.inc(tags={"k": 'multi,part"value'})
        assert metrics_mod.flush_now()  # driver-side snapshots
        address = start_dashboard()
        deadline = time.monotonic() + 60
        text = ""
        while time.monotonic() < deadline:
            _s, body = _get(f"{address}/metrics")
            text = body.decode()
            if "rtpu_llm_ttft_seconds_bucket" in text and \
                    "rtpu_llm_token_latency_seconds_bucket" in text:
                break
            time.sleep(0.5)

        def _count_of(metric):
            for line in text.splitlines():
                if line.startswith(metric + "_count"):
                    return float(line.rsplit(" ", 1)[1])
            return 0.0
        assert _count_of("rtpu_llm_ttft_seconds") >= 1, text[:2000]
        assert _count_of("rtpu_llm_token_latency_seconds") >= 1
        assert 'engine="paged"' in text
        assert 'test_e2e_escape_total{k="multi,part\\"value"} 1.0' in text
        assert "# TYPE rtpu_llm_ttft_seconds histogram" in text

        # -- timeline: SUBMITTED→RUNNING→FINISHED phase rows -----------
        deadline = time.monotonic() + 30
        rows = []
        while time.monotonic() < deadline:
            rows = [r for r in st.list_tasks(limit=100_000)
                    if r["state"] == "FINISHED"
                    and {"SUBMITTED", "RUNNING",
                         "FINISHED"} <= set(r["phases"])]
            if rows and any(r["name"] and "warmup" in r["name"]
                            and "LEASED" in r["phases"] for r in rows):
                break
            time.sleep(0.5)
        assert rows, "no finished task rows with full phase history"
        warm = next(r for r in rows if "warmup" in (r["name"] or ""))
        assert warm["phases"].index("SUBMITTED") < \
            warm["phases"].index("RUNNING") < \
            warm["phases"].index("FINISHED")
        assert "LEASED" in warm["phases"] and warm["leased_at"] is not None
        trace_events = st.timeline(str(tmp_path / "trace.json"))
        names = {ev["name"] for ev in trace_events}
        assert any("[queued]" in n for n in names if n)
        run_rows = [ev for ev in trace_events
                    if ev["args"].get("state") == "FINISHED"
                    and ev["cat"] in ("task", "actor_task")]
        assert run_rows and all(
            ev["tid"].startswith("worker-pid-") for ev in run_rows)

        # -- get_trace: span tree across the process hop ---------------
        deadline = time.monotonic() + 30
        tree = {}
        while time.monotonic() < deadline:
            tree = st.get_trace(trace_id)
            if tree["num_spans"] >= 2 and tree["num_processes"] >= 2:
                break
            time.sleep(0.5)
        assert tree["num_spans"] >= 2, tree
        assert tree["num_processes"] >= 2, tree  # driver + replica pids
        root = next(r for r in tree["roots"] if r["name"] == "client")
        assert root["children"], tree  # the replica-side execution span
        child_names = {c["name"] for c in root["children"]}
        assert any(n.startswith("task:") for n in child_names), tree

        # -- the CLI renders the same tree ----------------------------
        class T:
            address = None
            json = False
            limit = 20
        T.trace_id = trace_id
        cli.cmd_trace(T())
        out = capsys.readouterr().out
        assert "spans across" in out and "client" in out
    finally:
        try:
            from ray_tpu import serve
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


def test_dashboard_web_frontend_serves_spa(obs_cluster):
    """GET / returns the single-page frontend and the APIs it consumes
    return renderable data (reference: the React app in
    dashboard/client/src/ — here one dependency-free page; DOM-level
    assertions on the tab + table skeleton the JS fills in)."""
    from ray_tpu.dashboard import start_dashboard

    @ray_tpu.remote
    class Marker:
        def ping(self):
            return 1

    marker = Marker.remote()
    ray_tpu.get(marker.ping.remote())

    address = start_dashboard()
    status, body = _get(f"{address}/")
    assert status == 200
    page = body.decode()
    assert "<!DOCTYPE html>" in page
    # the SPA's structural DOM: tab bar + one button per state table
    for tab_name in ("cluster", "actors", "tasks", "pgs", "jobs",
                     "metrics"):
        assert f'data-tab="{tab_name}"' in page, tab_name
    # the table renderers the tabs build (ids the JS fills)
    for table_id in ("nodes-table", "actors-table", "tasks-table",
                     "jobs-table", "metrics-table"):
        assert table_id in page, table_id
    # sparkline + log-tail affordances exist
    assert "sparkline" in page and "showLogs" in page
    # /index.html is an alias
    _s, body2 = _get(f"{address}/index.html")
    assert body2 == body
    # and the data the page fetches actually renders rows: the actor
    # listing contains our marker actor
    _s, actors = _get(f"{address}/api/actors")
    assert any(a.get("class_name", "").endswith("Marker")
               for a in json.loads(actors)), actors
