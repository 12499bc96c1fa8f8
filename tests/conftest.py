import os
import sys

# Tests run on a virtual multi-device CPU mesh: 8 XLA CPU devices per
# process (the pattern the driver's dryrun_multichip uses as well). A chip
# host's ambient environment names the TPU platform — hold the tests to the
# CPU at the config level too, before any backend init.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import faulthandler  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# Lock-order sanitizer: armed session-wide under RTPU_SANITIZE=1 (fails
# the run on acquisition-order cycles), and per-test for the
# concurrency-heavy modules otherwise (report-only). See
# ray_tpu/_internal/lint/sanitizer.py.
pytest_plugins = ["ray_tpu._internal.lint.pytest_plugin"]

TEST_TIMEOUT_S = 120  # reference pytest.ini uses 180s per test


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout_s(seconds): override the per-test watchdog timeout")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` gate (heavy "
        "A/B arms, soaks)")


@pytest.fixture(autouse=True)
def _test_watchdog(request):
    """Dump all stacks and abort if a test wedges (poor man's pytest-timeout)."""
    marker = request.node.get_closest_marker("timeout_s")
    timeout = marker.args[0] if marker else TEST_TIMEOUT_S
    faulthandler.dump_traceback_later(timeout, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture
def ray_start_regular():
    """Local one-node cluster (reference: tests/conftest.py ray_start_regular)."""
    import ray_tpu
    worker = ray_tpu.init(num_cpus=4, object_store_memory=200 * 1024 * 1024)
    yield worker
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node cluster factory (reference: conftest.py ray_start_cluster)."""
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster()
    yield cluster
    cluster.shutdown()


def _serve_cluster(num_cpus, object_store_mb):
    """A one-node cluster with serve started on a port the kernel picks:
    xdist runs several serve test files side by side, and the product's
    default (127.0.0.1:8000) is one port for all of them. Tests ask
    `serve.get_http_address()` for what the proxy bound. Serve is shut
    down before the node."""
    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init(num_cpus=num_cpus,
                 object_store_memory=object_store_mb * 1024 * 1024)
    serve.start(serve.HTTPOptions(port=0))
    yield
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


@pytest.fixture
def serve_cluster():
    yield from _serve_cluster(8, 200)


@pytest.fixture
def llm_cluster():
    """Cluster for LLM serving tests."""
    yield from _serve_cluster(4, 300)


def raw_http(host, port, method, path, body):
    """One HTTP/1.1 request over a raw socket; returns (head, raw_body).
    Raw so chunked-streaming framing stays visible to assertions."""
    import json as _json
    import socket as _socket
    payload = _json.dumps(body).encode()
    s = _socket.create_connection((host, int(port)), timeout=240)
    s.sendall((f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
               f"Content-Length: {len(payload)}\r\n"
               "Connection: close\r\n\r\n").encode() + payload)
    data = b""
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        data += chunk
    s.close()
    head, _, rest = data.partition(b"\r\n\r\n")
    return head.decode("latin1"), rest
