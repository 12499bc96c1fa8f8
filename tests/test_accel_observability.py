"""Accelerator observability plane tests: CPU-backend device snapshots
(live-buffer fallback), jax.monitoring compile capture, step-telemetry
fold + MFU gauge arithmetic, goodput split, the cluster surfaces
(accel_summary / /api/devices / cli devices / cli status), pressure
events, and the RTPU_NO_ACCEL_METRICS kill switch (zero listeners)."""

import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _series(metric):
    """{tag_tuple: value} of one metric's current snapshot."""
    snap = metric.snapshot()
    return {tuple(tags): value for tags, value in snap["series"]}


# ---------------------------------------------------------------------------
# units: device snapshot, compile capture, step fold, pressure
# ---------------------------------------------------------------------------

def test_cpu_device_snapshot_live_buffer_fallback():
    """memory_stats() is None on the CPU backend; the snapshot must
    fall back to summing live-array shard bytes per device — and track
    a peak watermark across snapshots."""
    import jax.numpy as jnp

    from ray_tpu._internal import accel

    held = jnp.ones((512, 512), jnp.float32)  # 1 MiB on device 0
    held.block_until_ready()
    rows = accel.snapshot_devices(force_jax=True)
    assert len(rows) == 8  # conftest forces an 8-device CPU mesh
    by_index = {r["index"]: r for r in rows}
    dev0 = by_index[held.devices().pop().id]
    assert dev0["source"] == "live_buffers"
    assert dev0["hbm_used_bytes"] >= held.nbytes
    assert dev0["device_kind"] == "cpu"
    assert dev0["peak_flops"] == 1e12  # the shared table's cpu entry
    peak_before = dev0["hbm_peak_bytes"]
    assert peak_before >= dev0["hbm_used_bytes"]
    del held
    rows = accel.snapshot_devices()
    # used drops with the buffer, the watermark does not
    dev0_after = {r["index"]: r for r in rows}[dev0["index"]]
    assert dev0_after["hbm_used_bytes"] < dev0["hbm_used_bytes"]
    assert dev0_after["hbm_peak_bytes"] >= peak_before


def test_compile_capture_around_fresh_jit():
    import jax
    import jax.numpy as jnp

    from ray_tpu._internal import accel

    assert accel.ensure_installed()
    # the summary lists the 50 slowest sites of the process: start from none,
    # or a worker that ran model files first has 50 slower than this test's
    with accel._TRACKER.lock:
        accel._TRACKER.per_function.clear()
    before = accel.compile_summary()

    def my_unique_compile_site(x):
        return x * 7 + 3

    jax.jit(my_unique_compile_site)(jnp.ones((16,)))
    after = accel.compile_summary()
    assert after["compiles"] > before["compiles"]
    assert after["compile_seconds"] > before["compile_seconds"]
    # per-function attribution names THIS test, not a jax internal
    sites = {row["function"]: row for row in after["per_function"]}
    mine = [s for s in sites
            if "test_accel_observability.py" in s]
    assert mine, f"no test-attributed compile in {sorted(sites)}"
    assert sites[mine[0]]["seconds"] > 0
    # cumulative counters moved too
    total = accel.compile_seconds_total()
    jax.jit(lambda x: x - 1)(jnp.ones((16,)))
    assert accel.compile_seconds_total() > total


def test_report_step_mfu_and_goodput_arithmetic():
    from ray_tpu._internal import accel

    # 2e9 FLOPs in 1s on a "cpu" (peak 1e12) => MFU 0.002 exactly
    out = accel.report_step(
        "unit_mfu", 1.0, tokens=500, device_s=0.6, compile_s=0.1,
        flops=2e9, device_kind="cpu")
    assert out["mfu"] == pytest.approx(2e9 / 1e12)
    assert out["tokens_per_s"] == pytest.approx(500.0)
    assert out["compile_s"] == pytest.approx(0.1)
    assert out["device_s"] == pytest.approx(0.6)
    assert out["host_s"] == pytest.approx(0.3)
    metrics = accel.accel_metrics()
    mfu_series = _series(metrics.mfu)
    assert any(tags[1] == "unit_mfu" and
               value == pytest.approx(2e9 / 1e12)
               for tags, value in mfu_series.items())
    goodput = _series(metrics.goodput)
    by_bucket = {tags[1]: value for tags, value in goodput.items()
                 if tags[0] == "unit_mfu"}
    assert by_bucket["compile"] == pytest.approx(0.1)
    assert by_bucket["device"] == pytest.approx(0.6)
    assert by_bucket["host"] == pytest.approx(0.3)
    # the per-kind fold shows up in step_summary
    row = next(r for r in accel.step_summary()
               if r["kind"] == "unit_mfu")
    assert row["steps"] == 1
    assert row["mean_step_s"] == pytest.approx(1.0)
    # device+compile clamp to wall: nonsense inputs can't go negative
    out = accel.report_step("unit_mfu", 0.1, device_s=5.0, compile_s=5.0)
    assert out["compile_s"] == pytest.approx(0.1)
    assert out["device_s"] == 0.0
    assert out["host_s"] == 0.0


def test_step_timer_splits_wall_into_buckets():
    from ray_tpu._internal import accel

    with accel.StepTimer("unit_timer", tokens=10) as t:
        time.sleep(0.02)           # host
        with t.device():
            time.sleep(0.03)       # "device"
    assert t.result is not None
    assert t.result["wall_s"] >= 0.05
    assert t.result["device_s"] >= 0.03
    assert t.result["host_s"] >= 0.015
    # aggregated-interval reporting (steps>1) keeps the fold consistent
    accel.report_step("unit_timer", 1.0, steps=100, tokens=1000)
    row = next(r for r in accel.step_summary()
               if r["kind"] == "unit_timer")
    assert row["steps"] == 101
    assert row["mean_step_s"] < 0.1


def test_pressure_rows_watermark_and_rate_limit():
    from ray_tpu._internal import accel

    rows = [{"index": 991, "device_kind": "fake-tpu",
             "hbm_used_bytes": 95, "hbm_limit_bytes": 100},
            {"index": 992, "device_kind": "fake-tpu",
             "hbm_used_bytes": 10, "hbm_limit_bytes": 100},
            {"index": 993, "device_kind": "cpu",
             "hbm_used_bytes": 10 ** 9, "hbm_limit_bytes": 0}]
    out = accel.check_pressure(rows, watermark=0.9)
    assert [r["device"] for r in out] == [991]
    assert out[0]["used_ratio"] == pytest.approx(0.95)
    # rate limit: the same device does not re-emit within the window
    assert accel.check_pressure(rows, watermark=0.9) == []


def test_kill_switch_installs_zero_listeners():
    """RTPU_NO_ACCEL_METRICS: ensure_installed refuses, jax.monitoring
    listener lists stay untouched, not even the (inert) jax post-import
    meta-path finder is registered, snapshots/steps are no-ops."""
    import sys

    from jax._src import monitoring as jax_monitoring

    from ray_tpu._internal import accel
    from ray_tpu._internal.config import CONFIG

    accel.uninstall()  # clean slate whatever ran before
    CONFIG.apply_system_config({"no_accel_metrics": True})
    try:
        assert accel.install_import_hook() is False
        assert accel._IMPORT_HOOK not in sys.meta_path
        dur_before = list(jax_monitoring._event_duration_secs_listeners)
        ev_before = list(jax_monitoring._event_listeners)
        assert accel.ensure_installed() is False
        assert accel.snapshot_devices(force_jax=True) == []
        assert accel.report_step("killed", 1.0, tokens=10) is None
        with accel.StepTimer("killed", tokens=5) as t:
            with t.device():
                pass
        assert t.result is None
        report = accel.accel_report(force_jax=True)
        assert report["disabled"] is True
        assert report["devices"] == []
        assert jax_monitoring._event_duration_secs_listeners \
            == dur_before
        assert jax_monitoring._event_listeners == ev_before
        assert accel._on_duration_event not in \
            jax_monitoring._event_duration_secs_listeners
    finally:
        CONFIG.apply_system_config({"no_accel_metrics": False})
    assert accel.ensure_installed() is True
    assert accel.accel_report()["disabled"] is False
    # enabled + jax already imported: the boot hook installs directly
    # and registers no lingering meta-path finder
    assert accel.install_import_hook() is True
    assert accel._IMPORT_HOOK not in sys.meta_path


def test_peak_flops_table_shared_with_bench():
    """bench.py and the MFU gauge must divide by the same table."""
    import bench

    from ray_tpu.accelerators import flops

    assert bench.PEAK_FLOPS is flops.PEAK_FLOPS
    assert flops.peak_flops_for_kind("TPU v6e") == 918e12
    assert flops.peak_flops_for_kind("TPU v5e") == 197e12
    assert flops.peak_flops_for_kind("TPU v5 lite") == 197e12
    assert flops.peak_flops_for_kind("TPU v5p") == 459e12
    assert flops.peak_flops_for_kind("cpu") == 1e12
    with pytest.raises(KeyError, match="martian-npu"):
        flops.peak_flops_for_kind("martian-npu")

    class FakeDev:
        device_kind = "TPU v4"
    assert flops.peak_flops(FakeDev()) == 275e12


def test_paged_decode_loop_reports_step_telemetry():
    from ray_tpu._internal import accel
    from ray_tpu.llm import PagedEngineConfig, PagedLLMEngine
    from ray_tpu.models.llama import LlamaConfig

    model = LlamaConfig(vocab_size=64, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=2,
                        num_kv_heads=2, max_seq_len=64, remat=False,
                        use_flash=False, attention_impl="reference")
    engine = PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=2, max_len=32, page_size=8, num_pages=16,
        prefill_buckets=(8,)))
    engine.generate([[1, 2, 3]], max_new_tokens=4)
    engine.stats()  # drained engine: flushes the partial accumulator
    row = next(r for r in accel.step_summary() if r["kind"] == "decode")
    assert row["steps"] >= 3
    assert row["tokens"] >= 3
    assert row["device_s"] > 0
    assert row["tokens_per_s"] > 0
    assert row["mfu"] > 0  # 2*params FLOPs/token against the cpu entry


def test_train_controller_folds_step_reports():
    from ray_tpu._internal import accel
    from ray_tpu.train.controller import TrainController

    def train_row():
        return next((r for r in accel.step_summary()
                     if r["kind"] == "train"), {"steps": 0, "tokens": 0})

    # rows are per process: a train test that ran here before left some
    before = train_row()
    controller = TrainController.__new__(TrainController)
    controller.reports = {}
    controller._fold_step_telemetry(
        {"loss": 1.0, "step_time_s": 0.5, "tokens": 100,
         "step_flops": 1e9, "device_kind": "cpu"})
    row = train_row()
    assert row["steps"] - before["steps"] == 1
    assert row["tokens"] - before["tokens"] == 100
    assert row["mfu"] == pytest.approx((1e9 / 0.5) / 1e12)
    # reports without timing keys are ignored, not crashed on
    controller._fold_step_telemetry({"loss": 2.0})
    controller._fold_step_telemetry({"step_time_s": "garbage-free?"})


# ---------------------------------------------------------------------------
# e2e: worker -> raylet -> state API -> HTTP -> CLI, plus pressure events
# ---------------------------------------------------------------------------

@pytest.fixture
def accel_cluster():
    worker = ray_tpu.init(num_cpus=4,
                          object_store_memory=64 * 1024 * 1024)
    yield worker
    ray_tpu.shutdown()


@pytest.mark.timeout_s(180)
def test_accel_plane_e2e(accel_cluster, capsys):
    import jax
    import jax.numpy as jnp

    from ray_tpu import cli
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.util import state as st

    # driver-side compile + device residency
    jax.jit(lambda x: x * 2)(jnp.ones((32,))).block_until_ready()

    # a worker that touches jax so its report carries devices and the
    # raylet fan-out has something to fold
    @ray_tpu.remote
    def burn():
        import jax as wjax
        import jax.numpy as wjnp
        y = wjax.jit(lambda x: x @ x)(wjnp.ones((64, 64)))
        y.block_until_ready()
        return float(y[0, 0])

    assert ray_tpu.get(burn.remote(), timeout=120) == 64.0

    summary = st.accel_summary()
    assert summary["devices"], summary["errors"]
    assert all("hbm_used_bytes" in d for d in summary["devices"])
    assert summary["compile"]["compiles"] > 0
    assert summary["compile"]["compile_seconds"] > 0
    # the driver's own report is in, with the CPU fallback source
    assert any(d["source"] == "live_buffers"
               for d in summary["devices"])
    node_row = next(n for n in summary["nodes"] if n["num_devices"])
    assert node_row["num_devices"] >= 8
    # worker report rode the raylet fan-out (>= 2 processes with jax:
    # the driver + the task worker)
    jax_procs = {p["pid"] for p in summary["processes"]
                 if p.get("jax_initialized")}
    assert len(jax_procs) >= 2
    # the WORKER's compile was counted too: burn() imported jax inside
    # the first task body, so only the post-import hook could have
    # armed the listeners before that jit compiled
    worker_compiles = [p for p in summary["processes"]
                       if p.get("mode") not in ("driver",)
                       and (p.get("compile") or {}).get("compiles", 0)]
    assert worker_compiles, [
        (p.get("pid"), p.get("mode"), p.get("compile"))
        for p in summary["processes"]]

    # dashboard route
    address = start_dashboard()
    _s, body = _get(f"{address}/api/devices")
    api_summary = json.loads(body)
    assert api_summary["devices"]
    assert api_summary["compile"]["compiles"] > 0

    # cli devices renders the table
    class D:
        address = None
        json = False
    cli.cmd_devices(D())
    out = capsys.readouterr().out
    assert "devices:" in out
    assert "cpu" in out
    assert "live_buffers" in out

    # cli devices --json is loadable
    class DJ:
        address = None
        json = True
    cli.cmd_devices(DJ())
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["devices"]

    # cli status gains the per-node accelerator rows
    class S:
        address = None
    cli.cmd_status(S())
    out = capsys.readouterr().out
    assert "accelerators:" in out
    assert "chips" in out
    assert "compile" in out


def test_device_object_spill_emits_pressure_event(accel_cluster):
    """reserve_bytes exhaustion publishes DEVICE_MEMORY_PRESSURE to the
    GCS event log instead of degrading silently (the spill itself still
    happens — the ref resolves through the host store)."""
    import jax.numpy as jnp

    from ray_tpu._internal.config import CONFIG
    from ray_tpu.experimental import device_objects
    from ray_tpu.util import state as st

    arr = jnp.ones((1024,), jnp.float32)  # 4 KiB > 1 KiB budget
    old = CONFIG.device_object_hbm_budget
    CONFIG.apply_system_config({"device_object_hbm_budget": 1024})
    try:
        ref = device_objects.device_put_ref(arr, timeout_s=0.1)
        # spilled: resolves through the normal object path as numpy
        spilled = ray_tpu.get(ref)
        assert isinstance(spilled, np.ndarray)
        assert spilled.shape == (1024,)
    finally:
        CONFIG.apply_system_config({"device_object_hbm_budget": old})
    deadline = time.monotonic() + 20
    events = []
    while time.monotonic() < deadline:
        events = st.list_events(event_type="DEVICE_MEMORY_PRESSURE")
        if events:
            break
        time.sleep(0.25)
    assert events, "no DEVICE_MEMORY_PRESSURE event reached the GCS"
    assert events[-1]["severity"] == "WARNING"
    assert "budget exhausted" in events[-1]["message"]


def test_pull_counters_on_device_object_path(accel_cluster):
    """The _pull path counts pulls/bytes FIRST (before any transport
    work), so the counters are testable even where this jax build lacks
    jax.experimental.transfer (the transport import then fails — a
    pre-existing limitation the device-object suite shares)."""
    from ray_tpu.experimental import device_objects

    metrics = device_objects._metrics()
    base_pulls = _series(metrics.pulls).get((), 0)
    base_bytes = _series(metrics.pull_bytes).get((), 0)
    desc = device_objects.DeviceObjectDescriptor(
        object_hex="ab" * 20, transfer_addr="127.0.0.1:1",
        producer_rpc_addr=("127.0.0.1", 1), shape=(256,),
        dtype="float32", nbytes=1024)
    with pytest.raises(Exception):
        device_objects._pull(desc)  # no producer at that addr / no
        #                             transfer API in this jax build
    assert _series(metrics.pulls).get((), 0) == base_pulls + 1
    assert _series(metrics.pull_bytes).get((), 0) == base_bytes + 1024
