"""Fleet operations: graceful drain, rolling restarts, elastic
autoscaling, and the chaos schedule (ROADMAP item 5).

Units: chaos time-scheduled scripts, elastic-autoscaler hysteresis (no
flapping on an oscillating queue), drain fence/cancel semantics, the
drain-deadline straggler contract (postmortem-tagged kills, not hangs).
E2e: a full rolling restart of every worker raylet plus a GCS kill -9
mid-rollout under a task flood and a streaming serve client — zero
lost, zero doubled, stream completes."""

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.state import api as state_api


# ---------------------------------------------------------------------------
# chaos schedule units
# ---------------------------------------------------------------------------

def test_chaos_schedule_parse_and_override():
    from ray_tpu._internal import chaos
    from ray_tpu._internal.config import CONFIG

    sched = chaos.parse_schedule(
        "5:hb:delay:1.0:0.2, 15:hb:delay:0, 0:push:dup:0.5")
    # sorted by at_s
    assert [e.at_s for e in sched] == [0.0, 5.0, 15.0]
    assert sched[1].rule.param == 0.2
    with pytest.raises(ValueError):
        chaos.parse_schedule("5:hb:explode:1.0")
    with pytest.raises(ValueError):
        chaos.parse_schedule("hb:delay:1.0")  # missing at_s

    reg = chaos.ChaosRegistry()
    try:
        # one entry active immediately, one far in the future
        reg.arm(seed=7, schedule="0:foo:dup:1.0,9999:bar:drop_req:1.0")
        rules = reg.active_rules()
        assert [(r.pattern, r.action) for r in rules] == [("foo", "dup")]
        assert reg.duplicate_response("a_foo_method")
        assert not reg.drop_request("bar_rpc")  # not yet armed
        rows = reg.schedule_status()
        assert [(r["at_s"], r["active"]) for r in rows] == \
            [(0.0, True), (9999.0, False)]

        # a later entry for the same (pattern, action) REPLACES the
        # earlier one — prob 0 switches the fault off
        reg.arm(seed=7, schedule="0:foo:dup:1.0,0:foo:dup:0.0")
        assert reg.active_rules() == []
        assert not reg.duplicate_response("a_foo_method")

        # static spec + schedule compose; the schedule wins on overlap
        reg.arm(seed=7, spec="foo:dup:1.0",
                schedule="0:foo:dup:0.0,9999:baz:delay:1.0:0.5")
        assert not reg.duplicate_response("a_foo_method")

        # spec-only update (schedule=None) KEEPS the armed schedule —
        # adding a static rule mid-soak must not disarm the script;
        # an explicit "" clears it
        reg.arm(seed=7, spec="qux:delay:1.0:0.1")
        assert len(reg.schedule_status()) == 2
        reg.arm(seed=7, schedule="")
        assert reg.schedule_status() == []
    finally:
        CONFIG.reset()
        chaos.REGISTRY._specs = None


def test_chaos_schedule_seeded_determinism():
    from ray_tpu._internal import chaos
    from ray_tpu._internal.config import CONFIG

    def draws(seed):
        reg = chaos.ChaosRegistry()
        reg.arm(seed=seed, schedule="0:m:drop_req:0.5")
        return [reg.drop_request("method_m") for _ in range(64)]

    try:
        assert draws(4321) == draws(4321)   # bit-identical replay
        assert draws(4321) != draws(99)
    finally:
        CONFIG.reset()
        chaos.REGISTRY._specs = None


# ---------------------------------------------------------------------------
# elastic autoscaler hysteresis units (synthetic state, fake clock)
# ---------------------------------------------------------------------------

class _FakeGcs:
    def __init__(self):
        self.state = {"nodes": {}, "task_demand": [], "pg_demand": []}
        self.drained = []

    def call_sync(self, method, **kw):
        if method == "get_autoscaler_state":
            return self.state
        if method == "drain_node":
            self.drained.append(kw["node_id"])
            return {"drained": True, "node_id": kw["node_id"]}
        raise AssertionError(method)


class _ListProvider:
    def __init__(self):
        self.instances = {}
        self.launches = 0
        self.terminated = []

    def launch(self, node_type, resources, labels):
        iid = f"i-{self.launches}"
        self.launches += 1
        self.instances[iid] = {"node_type": node_type, "node_id": None}
        return iid

    def terminate(self, instance_id):
        self.terminated.append(instance_id)
        return self.instances.pop(instance_id, None) is not None

    def non_terminated_instances(self):
        return dict(self.instances)


def _elastic(gcs, provider, clock, **over):
    from ray_tpu.autoscaler import (ElasticAutoscaler, ElasticConfig,
                                    NodeTypeConfig)
    cfg = dict(node_types=[NodeTypeConfig("w2", {"CPU": 2},
                                          max_workers=4)],
               queue_age_up_s=1.0, up_delay_s=2.0, down_delay_s=5.0,
               drain_timeout_s=5.0)
    cfg.update(over)
    return ElasticAutoscaler(ElasticConfig(**cfg), provider, gcs,
                             clock=clock)


def _node_row(avail, total=None, age=0.0, depth=0, head=False,
              draining=False, labels=None):
    total = total if total is not None else dict(avail)
    return {"node_index": 0, "is_head": head, "labels": labels or {},
            "total": total, "available": avail, "draining": draining,
            "queue_depth": depth, "queue_age_s": age,
            "queue_ages": {"CPU=1": age} if age else {}}


def test_autoscaler_no_flap_on_oscillating_queue():
    """An oscillating scale-up signal (queue appears and clears faster
    than up_delay_s) must never launch; a PERSISTED signal must."""
    gcs, provider = _FakeGcs(), _ListProvider()
    now = [0.0]
    auto = _elastic(gcs, provider, clock=lambda: now[0])

    busy = {"nodes": {"n1": _node_row({"CPU": 0.0}, {"CPU": 2.0},
                                      age=3.0, depth=2)},
            "task_demand": [{"CPU": 1.0}], "pg_demand": []}
    calm = {"nodes": {"n1": _node_row({"CPU": 2.0})},
            "task_demand": [], "pg_demand": []}

    # oscillate at 0.5s period for 10s: signal never persists 2s
    for i in range(20):
        gcs.state = busy if i % 2 == 0 else calm
        auto.reconcile()
        now[0] += 0.5
    assert provider.launches == 0, "flapped on an oscillating queue"

    # sustained pressure: launches exactly after up_delay_s
    gcs.state = busy
    auto.reconcile()          # arms the clock
    assert provider.launches == 0
    now[0] += 1.0
    auto.reconcile()          # 1.0s persisted < 2.0s delay
    assert provider.launches == 0
    now[0] += 1.1
    stats = auto.reconcile()  # 2.1s persisted -> launch
    assert provider.launches == 1 and stats["launched"] == 1
    # the clock re-arms after acting: no second launch next tick
    now[0] += 0.1
    auto.reconcile()
    assert provider.launches == 1


def test_autoscaler_scale_in_via_drain_with_hysteresis():
    """Scale-in only after down_delay_s of FULL idleness, and always
    through the GCS drain path before provider.terminate; oscillating
    idleness never terminates; pending demand holds idle nodes."""
    gcs, provider = _FakeGcs(), _ListProvider()
    now = [0.0]
    auto = _elastic(gcs, provider, clock=lambda: now[0])
    iid = provider.launch("w2", {"CPU": 2}, {})
    provider.instances[iid]["node_id"] = "n2"

    idle = {"nodes": {"head": _node_row({"CPU": 2.0}, head=True),
                      "n2": _node_row({"CPU": 2.0})},
            "task_demand": [], "pg_demand": []}
    busy = {"nodes": {"head": _node_row({"CPU": 2.0}, head=True),
                      "n2": _node_row({"CPU": 0.0}, {"CPU": 2.0})},
            "task_demand": [], "pg_demand": []}

    # oscillating idleness at 2s period never persists 5s
    for i in range(10):
        gcs.state = idle if i % 2 == 0 else busy
        auto.reconcile()
        now[0] += 2.0
    assert gcs.drained == [] and provider.terminated == []

    # sustained idleness: drains (then terminates) after down_delay_s
    gcs.state = idle
    auto.reconcile()
    now[0] += 5.5
    stats = auto.reconcile()
    assert stats["drained"] == 1
    assert gcs.drained == ["n2"], "scale-in must route through drain"
    assert provider.terminated == [iid]

    # unmet demand elsewhere HOLDS idle nodes (no churn under load)
    iid2 = provider.launch("w2", {"CPU": 2}, {})
    provider.instances[iid2]["node_id"] = "n3"
    gcs.state = {
        "nodes": {"head": _node_row({"CPU": 0.0}, {"CPU": 2.0},
                                    age=5.0, depth=1, head=True),
                  "n3": _node_row({"CPU": 2.0})},
        "task_demand": [{"CPU": 8.0}],  # unsatisfiable: no launch either
        "pg_demand": []}
    for _ in range(4):
        auto.reconcile()
        now[0] += 5.0
    assert provider.terminated == [iid]  # n3 was never torn down


def test_autoscaler_ignores_draining_capacity():
    """Free capacity on a DRAINING node must not cancel scale-up demand
    (that capacity is leaving)."""
    gcs, provider = _FakeGcs(), _ListProvider()
    now = [0.0]
    auto = _elastic(gcs, provider, clock=lambda: now[0],
                    up_delay_s=0.0)
    gcs.state = {
        "nodes": {"n1": _node_row({"CPU": 2.0}, draining=True, age=2.0,
                                  depth=1)},
        "task_demand": [{"CPU": 1.0}], "pg_demand": []}
    auto.reconcile()
    assert provider.launches == 1


def test_serve_autoscaling_policy_metric_signals():
    """Queue-depth and TTFT targets drive desired replicas past the
    ongoing-request formula."""
    from ray_tpu.serve.autoscaling_policy import \
        calculate_desired_num_replicas

    base = {"min_replicas": 1, "max_replicas": 10,
            "target_ongoing_requests": 4}
    assert calculate_desired_num_replicas(base, 8.0) == 2
    # queue depth signal wins when it asks for more
    cfg = dict(base, target_queue_depth=2)
    assert calculate_desired_num_replicas(cfg, 8.0, total_queued=10) == 5
    # TTFT over target scales proportionally from the current count
    cfg = dict(base, target_ttft_s=0.5)
    assert calculate_desired_num_replicas(
        cfg, 0.0, p50_ttft_s=2.0, current_num_replicas=2) == 8
    # clamped to max
    cfg = dict(base, target_queue_depth=1)
    assert calculate_desired_num_replicas(cfg, 0.0,
                                          total_queued=100) == 10


# ---------------------------------------------------------------------------
# drain fence semantics (in-process raylet)
# ---------------------------------------------------------------------------

@pytest.mark.timeout_s(180)
def test_drain_fence_cancel_and_return_worker_dispose():
    """The fence stops new grants (callers park, not fail), a returned
    worker is DISPOSED while draining (the drain-leak fix: never
    re-leased to a queued request), and cancel lowers the fence so
    parked work proceeds."""
    from ray_tpu._internal.rpc import EventLoopThread

    cluster = Cluster(head_node_args={"num_cpus": 2})
    cluster.connect()
    try:
        raylet = cluster.head_node.raylet
        loop = EventLoopThread.get()

        @ray_tpu.remote(num_cpus=1)
        def step(i):
            time.sleep(0.1)
            return i

        # Warm leases + workers exist.
        assert ray_tpu.get([step.remote(i) for i in range(6)],
                           timeout=60) == list(range(6))
        assert any(not h.is_actor_worker
                   for h in raylet.workers.values())

        # Fence.
        reply = loop.run_sync(raylet.handle_drain_self(phase="fence"))
        assert reply["draining"] is True

        # Once the owners' idle-lease cleaner returns the warm leases,
        # the fenced raylet must DISPOSE the workers, not re-pool them.
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            live = [h for h in raylet.workers.values()
                    if h.state in ("IDLE", "LEASED")
                    and not h.is_actor_worker]
            if not live and not raylet.leases:
                break
            time.sleep(0.2)
        assert not [h for h in raylet.workers.values()
                    if h.state == "IDLE" and not h.is_actor_worker], \
            "returned workers re-entered the idle pool during drain"

        # New work parks behind the fence (single node: nowhere to
        # spill) instead of failing...
        refs = [step.remote(100 + i) for i in range(4)]
        with pytest.raises(Exception):
            ray_tpu.get(refs[0], timeout=2.0)

        # ...and proceeds when the drain is canceled.
        reply = loop.run_sync(raylet.handle_drain_self(phase="cancel"))
        assert reply["draining"] is False
        assert ray_tpu.get(refs, timeout=60) == [100 + i
                                                 for i in range(4)]
    finally:
        cluster.shutdown()


@pytest.mark.timeout_s(180)
def test_drain_deadline_kills_stragglers_with_postmortem():
    """A task that outlives drain_timeout_s gets a postmortem-tagged
    SIGKILL (DRAIN_TIMEOUT_KILLED), the drain returns (no hang), and
    the caller's exception carries the taxonomy."""
    cluster = Cluster(head_node_args={"num_cpus": 1})
    cluster.connect()
    try:
        node = cluster.add_node(num_cpus=2)
        cluster.wait_for_nodes()

        @ray_tpu.remote(num_cpus=2, max_retries=0)
        def straggler():
            time.sleep(300)

        ref = straggler.remote()
        # wait until it is actually running on the worker node
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            rows = state_api.list_tasks()
            if any(r["state"] == "RUNNING" for r in rows):
                break
            time.sleep(0.2)

        t0 = time.monotonic()
        report = state_api.drain_node(node.node_id, timeout_s=2.0)
        elapsed = time.monotonic() - t0
        assert report.get("drained") is True
        assert report["timed_out"] is True
        assert len(report["stragglers_killed"]) == 1
        assert elapsed < 30, f"drain hung: {elapsed:.1f}s"

        with pytest.raises(Exception) as excinfo:
            ray_tpu.get(ref, timeout=60)
        pm = getattr(getattr(excinfo.value, "cause", None),
                     "postmortem", None)
        assert pm is not None \
            and pm["exit"]["kind"] == "DRAIN_TIMEOUT_KILLED", \
            f"wrong taxonomy: {pm and pm.get('exit')}"

        # drain telemetry: NODE_DRAINING + NODE_DRAINED events landed
        events = {e["type"] for e in state_api.list_events(limit=500)}
        assert "NODE_DRAINING" in events and "NODE_DRAINED" in events
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# the rolling-restart e2e: every raylet restarted one-by-one + one GCS
# kill -9 mid-rollout, under a task flood and a streaming serve client
# ---------------------------------------------------------------------------

@pytest.mark.timeout_s(300)
def test_rolling_restart_e2e(tmp_path):
    from ray_tpu import serve
    from ray_tpu.perf_workloads import _SoakStreamer, _soak_stream_once

    marker = str(tmp_path / "executions.log")
    persist = str(tmp_path / "gcs.db")
    cluster = Cluster(
        head_node_args={"num_cpus": 2},
        external_gcs=True, gcs_persist_path=persist,
        gcs_env={"RTPU_GCS_PERSIST": "wal",
                 # seeded control-plane chaos rides the whole rollout
                 "RTPU_CHAOS_SPEC": "heartbeat:dup:0.05",
                 "RTPU_CHAOS_SEED": "1234"})
    cluster.connect()
    stop = threading.Event()
    try:
        nodes = [cluster.add_node(num_cpus=2) for _ in range(2)]
        cluster.wait_for_nodes()

        @ray_tpu.remote(num_cpus=1)
        def bump(i):
            fd = os.open(marker, os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                         0o644)
            try:
                os.write(fd, f"{i}\n".encode())
            finally:
                os.close(fd)
            time.sleep(0.02)
            return i

        # a named detached actor on a worker node must MIGRATE (not
        # die) through the rollout
        @ray_tpu.remote(num_cpus=1)
        class Survivor:
            def ping(self):
                return "alive"

        survivor = Survivor.options(name="rollout-survivor",
                                    lifetime="detached").remote()
        assert ray_tpu.get(survivor.ping.remote(), timeout=60) == "alive"

        # streaming serve client: stream spans the rollout; the serve
        # plane (controller/proxy/replica, num_cpus=0) lives on the
        # head, so the stream must survive raylet restarts AND the GCS
        # kill (replica calls ride direct actor RPC, no GCS hop)
        chunks = 40
        from ray_tpu.util.scheduling_strategies import \
            NodeAffinitySchedulingStrategy
        head_id = next(n["node_id"] for n in state_api.list_nodes()
                       if n["is_head"])
        streamer = serve.deployment(_SoakStreamer).options(
            ray_actor_options={
                "num_cpus": 0,
                # replicas pinned off the rolled nodes: a drained
                # replica's in-flight streams are killed by contract
                # (see README guarantees table) — the zero-dropped-
                # streams SLO is about the supporting planes (proxy,
                # GCS failover), not about streaming off a node being
                # decommissioned
                "scheduling_strategy": NodeAffinitySchedulingStrategy(
                    head_id, soft=True)})
        serve.run(streamer.bind(chunks, 0.3), name="soak",
                  route_prefix="/soak",
                  http_options=serve.HTTPOptions(port=0))
        addr = serve.api.get_http_address()
        host, port = addr.rsplit("://", 1)[-1].rsplit(":", 1)

        stream_result = {}

        def stream_client():
            try:
                stream_result["tokens"] = _soak_stream_once(
                    host, port, "/soak", chunks, timeout_s=240)
            except Exception as e:  # noqa: BLE001 — asserted below
                stream_result["error"] = repr(e)

        flood_errors = []
        submitted = []

        def flood():
            base = 0
            while not stop.is_set():
                idx = list(range(base, base + 20))
                base += 20
                submitted.extend(idx)
                try:
                    assert ray_tpu.get([bump.remote(i) for i in idx],
                                       timeout=180) == idx
                except Exception as e:  # noqa: BLE001 — asserted below
                    flood_errors.append(repr(e))
                    return

        threads = [threading.Thread(target=stream_client, daemon=True),
                   threading.Thread(target=flood, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(1.0)

        # rolling restart: node 0, then kill -9 the GCS mid-rollout,
        # then node 1 — the full fleet upgrade drill
        rep0 = cluster.restart_node(nodes[0], timeout_s=20)
        assert rep0.drain_report.get("drained") is True

        cluster.kill_gcs()
        time.sleep(0.5)
        cluster.restart_gcs()

        rep1 = cluster.restart_node(nodes[1], timeout_s=20)
        assert rep1.drain_report.get("drained") is True
        cluster.wait_for_nodes()

        # let the load settle, then stop the flood
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=240)

        # SLO: zero lost, zero doubled (exactly-once audit)
        assert not flood_errors, flood_errors
        with open(marker) as f:
            executed = [int(x) for x in f.read().split()]
        assert sorted(executed) == sorted(set(executed)) == \
            sorted(submitted), "tasks lost or doubled across the rollout"

        # SLO: the stream completed with every chunk
        assert stream_result.get("error") is None, stream_result
        assert stream_result.get("tokens") == chunks, stream_result

        # the detached actor migrated and still answers BY NAME
        from ray_tpu.actor import get_actor
        again = get_actor("rollout-survivor")
        assert ray_tpu.get(again.ping.remote(), timeout=60) == "alive"

        # failover observable: incarnation bumped, both nodes drained
        info = state_api.gcs_info()
        assert info["incarnation"] == 2 and info["failovers"] == 1
        drained_events = state_api.list_events(event_type="NODE_DRAINED")
        assert len(drained_events) >= 2
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
    finally:
        stop.set()
        cluster.shutdown()
