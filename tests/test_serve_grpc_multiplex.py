"""Serve gRPC ingress + model multiplexing
(reference: serve/_private/proxy.py:530 gRPCProxy, serve/multiplex.py)."""

import asyncio

import pytest

from ray_tpu import serve


def test_multiplex_wrapper_lru_no_cluster():
    """LRU model cache semantics (reference: _ModelMultiplexWrapper)."""
    from ray_tpu.serve.multiplex import _ModelMultiplexWrapper

    loads = []

    async def loader(model_id):
        loads.append(model_id)
        return f"model-{model_id}"

    async def scenario():
        mux = _ModelMultiplexWrapper(loader, None, max_models=2)
        assert await mux.load_model("a") == "model-a"
        assert await mux.load_model("b") == "model-b"
        assert await mux.load_model("a") == "model-a"  # cached
        assert loads == ["a", "b"]
        await mux.load_model("c")                      # evicts LRU ("b")
        assert set(mux.model_ids()) == {"a", "c"}
        await mux.load_model("b")                      # reload after evict
        assert loads == ["a", "b", "c", "b"]
        return True

    assert asyncio.run(scenario())


@pytest.mark.timeout_s(300)
def test_grpc_proxy_end_to_end(serve_cluster):
    """A gRPC client calls a deployment through the gRPC proxy."""
    import grpc

    @serve.deployment
    class Echo:
        def predict(self, payload: bytes) -> bytes:
            return b"echo:" + payload

        def __call__(self, payload: bytes) -> bytes:
            return b"call:" + payload

    serve.run(Echo.bind(), name="gapp", route_prefix="/gapp")
    addr = serve.get_grpc_address()
    channel = grpc.insecure_channel(addr)
    stub = channel.unary_unary(
        "/rtpu.Serve/predict",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b)
    out = stub(b"hello", metadata=(("application", "gapp"),), timeout=120)
    assert out == b"echo:hello"
    # method defaults to the final path segment; __call__ route too
    stub2 = channel.unary_unary(
        "/rtpu.Serve/__call__",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b)
    out2 = stub2(b"x", metadata=(("application", "gapp"),), timeout=120)
    assert out2 == b"call:x"
    channel.close()


@pytest.mark.timeout_s(300)
def test_multiplexed_deployment_via_handle(serve_cluster):
    """Two models multiplex on one replica with LRU swap; same-model
    calls hit the cache (reference: serve/multiplex.py +
    get_multiplexed_model_id)."""

    @serve.deployment
    class MuxServer:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=1)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"id": model_id}

        async def __call__(self, _request):
            model = await self.get_model()
            return {"model": model["id"],
                    "ctx": serve.get_multiplexed_model_id(),
                    "loads": list(self.loads)}

        async def query(self):
            model = await self.get_model()
            return {"model": model["id"], "loads": list(self.loads)}

    serve.run(MuxServer.bind(), name="mux", route_prefix=None)
    handle = serve.get_app_handle("mux")
    r1 = handle.options(method_name="query",
                        multiplexed_model_id="m1").remote().result(
                            timeout_s=120)
    assert r1["model"] == "m1" and r1["loads"] == ["m1"]
    # same model again: served from cache, no reload
    r2 = handle.options(method_name="query",
                        multiplexed_model_id="m1").remote().result(
                            timeout_s=120)
    assert r2["loads"] == ["m1"]
    # second model with max=1: LRU swap (m1 evicted, m2 loaded)
    r3 = handle.options(method_name="query",
                        multiplexed_model_id="m2").remote().result(
                            timeout_s=120)
    assert r3["model"] == "m2" and r3["loads"] == ["m1", "m2"]
    # m1 again: reloaded after eviction
    r4 = handle.options(method_name="query",
                        multiplexed_model_id="m1").remote().result(
                            timeout_s=120)
    assert r4["loads"] == ["m1", "m2", "m1"]
