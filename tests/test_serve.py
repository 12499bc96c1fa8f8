"""Serve tests: deploy → HTTP request → routed replica → response;
handle calls, composition, batching, replica-death recovery, autoscaling,
redeploy (reference coverage: serve/tests/test_standalone.py,
test_deployment_state.py, test_autoscaling_policy.py, test_batching.py)."""

import json
import threading
import time
import urllib.request

import pytest

from ray_tpu import serve


def _http_get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _http_post(url, payload, timeout=10):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


# ---------------------------------------------------------------------------
# basic deploy + HTTP
# ---------------------------------------------------------------------------

@serve.deployment
class Doubler:
    def __init__(self, bias: int = 0):
        self.bias = bias

    def __call__(self, request):
        x = request.json()["x"]
        return {"y": 2 * x + self.bias}


def test_http_deploy_and_request(serve_cluster):
    serve.run(Doubler.bind(3), name="app1", route_prefix="/double")
    addr = serve.api.get_http_address()
    status, body = _http_post(f"{addr}/double", {"x": 5})
    assert status == 200
    assert json.loads(body) == {"y": 13}
    # Unknown route -> 404.
    with pytest.raises(urllib.error.HTTPError) as err:
        _http_get(f"{addr}/nope")
    assert err.value.code == 404
    # Health endpoint.
    status, body = _http_get(f"{addr}/-/healthz")
    assert body == b"ok"


def test_handle_call_and_methods(serve_cluster):
    @serve.deployment
    class Calc:
        def add(self, a, b):
            return a + b

        async def mul(self, a, b):
            return a * b

        def __call__(self, request):
            return "root"

    handle = serve.run(Calc.bind(), name="calc", route_prefix="/calc")
    assert handle.add.remote(2, 3).result() == 5
    assert handle.mul.remote(4, 5).result() == 20


def test_function_deployment(serve_cluster):
    @serve.deployment
    def echo(request):
        return request.json()

    serve.run(echo.bind(), name="echo", route_prefix="/echo")
    addr = serve.api.get_http_address()
    status, body = _http_post(f"{addr}/echo", {"hello": "world"})
    assert json.loads(body) == {"hello": "world"}


# ---------------------------------------------------------------------------
# composition: ingress holds a handle to an inner deployment
# ---------------------------------------------------------------------------

def test_model_composition(serve_cluster):
    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Pipeline:
        def __init__(self, pre):
            self.pre = pre

        async def __call__(self, request):
            x = request.json()["x"]
            pre = await self.pre.remote(x)
            return {"out": pre * 10}

    app = Pipeline.bind(Preprocess.bind())
    serve.run(app, name="pipe", route_prefix="/pipe")
    addr = serve.api.get_http_address()
    _status, body = _http_post(f"{addr}/pipe", {"x": 4})
    assert json.loads(body) == {"out": 50}


# ---------------------------------------------------------------------------
# batching
# ---------------------------------------------------------------------------

def test_serve_batch_coalesces(serve_cluster):
    @serve.deployment(max_ongoing_requests=64)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        async def handle_batch(self, xs):
            self.batch_sizes.append(len(xs))
            return [x * 2 for x in xs]

        async def __call__(self, x):
            return await self.handle_batch(x)

        def get_batch_sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind(), name="batched", route_prefix=None)
    responses = [handle.remote(i) for i in range(16)]
    results = [r.result(timeout_s=30) for r in responses]
    assert results == [i * 2 for i in range(16)]
    sizes = handle.get_batch_sizes.remote().result(timeout_s=30)
    assert max(sizes) > 1  # at least one real batch formed
    assert sum(sizes) == 16


# ---------------------------------------------------------------------------
# multiple replicas + pow-2 routing spread
# ---------------------------------------------------------------------------

def test_multiple_replicas_share_load(serve_cluster):
    import os

    @serve.deployment(num_replicas=3)
    class WhoAmI:
        def __call__(self, request=None):
            return os.getpid()

    handle = serve.run(WhoAmI.bind(), name="who", route_prefix=None)
    pids = {handle.remote().result(timeout_s=30) for _ in range(40)}
    assert len(pids) >= 2  # traffic reached more than one replica


# ---------------------------------------------------------------------------
# replica death recovery
# ---------------------------------------------------------------------------

def test_replica_death_recovery(serve_cluster):
    import os

    @serve.deployment(num_replicas=2, health_check_period_s=0.2)
    class Fragile:
        def __call__(self, request=None):
            return os.getpid()

        def die(self):
            os._exit(1)

    handle = serve.run(Fragile.bind(), name="fragile", route_prefix=None)
    pid_before = handle.remote().result(timeout_s=30)
    # Kill one replica out from under the controller.
    try:
        handle.die.remote().result(timeout_s=10)
    except Exception:
        pass  # the dying call may surface an error
    # The deployment must return to 2 healthy replicas and keep serving.
    deadline = time.monotonic() + 30
    healthy = False
    while time.monotonic() < deadline:
        snap = serve.status()
        dep = snap["apps"]["fragile"]["deployments"]["Fragile"]
        if dep["status"] == "HEALTHY" and dep["running"] == 2:
            healthy = True
            break
        time.sleep(0.2)
    assert healthy, f"deployment never recovered: {serve.status()}"
    for _ in range(5):
        assert isinstance(handle.remote().result(timeout_s=30), int)


# ---------------------------------------------------------------------------
# autoscaling
# ---------------------------------------------------------------------------

def test_autoscaling_up_and_down(serve_cluster):
    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1, "max_replicas": 4,
            "target_ongoing_requests": 1.0,
            "upscale_delay_s": 0.2, "downscale_delay_s": 0.5,
        },
        max_ongoing_requests=32)
    class Slow:
        async def __call__(self, request=None):
            import asyncio
            await asyncio.sleep(0.4)
            return "done"

    handle = serve.run(Slow.bind(), name="auto", route_prefix=None)

    def running_count():
        dep = serve.status()["apps"]["auto"]["deployments"]["Slow"]
        return dep["running"]

    assert running_count() == 1
    # Sustained concurrent load -> scale up.
    stop = threading.Event()
    errors = []

    def pound():
        while not stop.is_set():
            try:
                handle.remote().result(timeout_s=30)
            except Exception as e:
                errors.append(e)
                return

    threads = [threading.Thread(target=pound, daemon=True)
               for _ in range(8)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30
    scaled_up = False
    while time.monotonic() < deadline:
        if running_count() >= 2:
            scaled_up = True
            break
        time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join(timeout=35)
    assert scaled_up, "never scaled up under load"
    assert not errors
    # Load gone -> scale back down to min.
    deadline = time.monotonic() + 30
    scaled_down = False
    while time.monotonic() < deadline:
        if running_count() == 1:
            scaled_down = True
            break
        time.sleep(0.2)
    assert scaled_down, "never scaled back down"


# ---------------------------------------------------------------------------
# redeploy (rolling update) + delete
# ---------------------------------------------------------------------------

def test_redeploy_new_version_and_delete(serve_cluster):
    @serve.deployment(version="v1")
    class Versioned:
        def __init__(self, value):
            self.value = value

        def __call__(self, request=None):
            return self.value

    handle = serve.run(Versioned.bind("one"), name="ver", route_prefix=None)
    assert handle.remote().result(timeout_s=30) == "one"
    handle = serve.run(
        Versioned.options(version="v2").bind("two"), name="ver",
        route_prefix=None)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if handle.remote().result(timeout_s=30) == "two":
            break
        time.sleep(0.2)
    assert handle.remote().result(timeout_s=30) == "two"
    serve.delete("ver")
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if "ver" not in serve.status()["apps"]:
            break
        time.sleep(0.2)
    assert "ver" not in serve.status()["apps"]
