"""The continuous tick by phase (accel `tick` row, StepTimer.phase) and the
replica's token hand-off (reqtrace STREAMED): CPU, toy engines."""

import asyncio

import pytest

from ray_tpu._internal import accel
from ray_tpu._internal.config import CONFIG
from ray_tpu.llm import PagedEngineConfig, PagedLLMEngine, reqtrace
from ray_tpu.llm.engine import GenerationRequest
from ray_tpu.models.llama import LlamaConfig

# ISSUE 24's table: these tile a tick; `between` lies outside its wall
IN_TICK = ("reap", "admit", "prefill", "grow", "stage", "dispatch", "wait",
           "emit", "gauges")
PHASES = ("between",) + IN_TICK


def toy_config(hidden=32, layers=2, batch=2):
    model = LlamaConfig(vocab_size=256, hidden_size=hidden,
                        intermediate_size=2 * hidden, num_layers=layers,
                        num_heads=4, num_kv_heads=2, max_seq_len=128,
                        remat=False, use_flash=False,
                        attention_impl="reference")
    return PagedEngineConfig(
        model=model, max_batch=batch, max_len=96, page_size=8,
        num_pages=16 * batch, prefill_buckets=(8,))


def toy_engine(**sizes):
    return PagedLLMEngine(toy_config(**sizes))


def tick_row(engine):
    engine.stats()  # flushes the partial accumulator window
    for row in accel.step_summary():
        if row["kind"] == "tick":
            return row
    return {"steps": 0, "wall_s": 0.0, "cpu_s": 0.0, "phases": {}}


def delta(before, after):
    return {"steps": after["steps"] - before["steps"],
            "wall_s": after["wall_s"] - before["wall_s"],
            "cpu_s": after["cpu_s"] - before["cpu_s"],
            "phases": {name: seconds - before["phases"].get(name, 0.0)
                       for name, seconds in after["phases"].items()}}


def submit(engine, n, max_new_tokens, tag):
    for i in range(n):
        engine.submit(GenerationRequest(
            prompt_tokens=[1 + i, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11],
            max_new_tokens=max_new_tokens, request_id=f"{tag}-{i}"))


@pytest.fixture(scope="module")
def forty_ticks():
    """40 back-to-back ticks of a toy engine whose tick (~20 ms on the
    CPU) dwarfs the timers' own bookkeeping between two phases (~0.1
    ms): the window's share of the `tick` row, then 3 idle steps'."""
    engine = toy_engine(hidden=512, layers=4, batch=8)
    engine.generate([[5, 6, 7, 8, 9, 10, 11, 12, 13]], max_new_tokens=3)
    submit(engine, 8, 64, "tile")
    engine.step()  # the first tick after idle has no `between`
    before = tick_row(engine)
    for _ in range(40):
        engine.step()
    busy = delta(before, tick_row(engine))
    while engine.has_work():
        engine.step()
    before = tick_row(engine)
    for _ in range(3):
        engine.step()
    idle = delta(before, tick_row(engine))
    return {"busy": busy, "idle": idle}


@pytest.mark.parametrize("window", ["busy"])
def test_phases_tile_the_tick(forty_ticks, window):
    ticks = forty_ticks[window]
    assert ticks["steps"] == 40
    whole = ticks["wall_s"] + ticks["phases"]["between"]
    assert sum(ticks["phases"].values()) == pytest.approx(whole, rel=0.02)
    # the stepping thread's CPU seconds cover no more than its wall
    # (with room for a thread clock that ticks in coarse steps)
    assert 0.0 < ticks["cpu_s"] <= ticks["wall_s"] * 1.1


@pytest.mark.parametrize("name", PHASES)
def test_every_phase_is_reported(forty_ticks, name):
    assert forty_ticks["busy"]["phases"][name] > 0.0


@pytest.mark.parametrize("window,positive", [("idle", False),
                                             ("busy", True)])
def test_between_counts_only_waiting_work(forty_ticks, window, positive):
    """An idle engine's gaps are nobody's wait; back-to-back steps' are."""
    ticks = forty_ticks[window]
    assert ticks["steps"] >= 3
    between = ticks["phases"].get("between", 0.0)
    assert (between > 0.0) if positive else (between == 0.0)


@pytest.mark.parametrize("what", ["row", "spans"])
def test_kill_switch_leaves_no_row_and_builds_no_span(monkeypatch, what):
    import jax
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kwargs):
            built.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(accel, "_step_stats", {})
    CONFIG.apply_system_config({"no_accel_metrics": True})
    try:
        engine = toy_engine()
        engine.generate([[1, 2, 3]], max_new_tokens=4)
        engine.stats()
        if what == "row":
            assert accel.step_summary() == []
        else:
            assert built == []
    finally:
        CONFIG.apply_system_config({"no_accel_metrics": False})
    # and with the plane back on the same calls do both
    engine = toy_engine()
    engine.generate([[1, 2, 3]], max_new_tokens=4)
    engine.stats()
    if what == "row":
        assert {"tick", "decode"} <= {
            row["kind"] for row in accel.step_summary()}
    else:
        assert {"tick", "decode", "decode/device"} \
            | {"tick/" + name for name in IN_TICK} <= set(built)


def stream(request_id, max_new_tokens, cancel_after=None):
    """One streamed request through LLMServer as the proxy drives it:
    generate_stream_start, then stream_next until done (or cancel_stream
    after `cancel_after` answers that carried tokens). Returns the tokens
    the polls delivered and how many polls carried any."""
    from ray_tpu.llm.serving import LLMServer

    async def drive():
        server = LLMServer(toy_config())
        stream_id = await server.generate_stream_start(
            [1, 2, 3, 4, 5], max_new_tokens=max_new_tokens,
            request_id=request_id)
        tokens, polls = [], 0
        try:
            while True:
                out = await server.stream_next(stream_id, timeout_s=30.0)
                tokens += out["tokens"]
                polls += bool(out["tokens"])
                if out["done"]:
                    break
                if polls == cancel_after:
                    assert await server.cancel_stream(stream_id)
                    break
        finally:
            server._loop_task.cancel()
        return tokens, polls

    return asyncio.run(drive())


def events_of(request_id):
    return [[rid, event, ts, args]
            for rid, event, ts, args in reqtrace.events()
            if rid == request_id]


@pytest.mark.parametrize("max_new_tokens,cancel_after",
                         [(2, None), (12, None), (40, 2)])
def test_stream_leaves_one_streamed_event(max_new_tokens, cancel_after):
    rid = f"streamed-{max_new_tokens}-{cancel_after}"
    tokens, polls = stream(rid, max_new_tokens, cancel_after)
    if cancel_after is None:
        assert len(tokens) == max_new_tokens
    streamed = [args for _rid, event, _ts, args in events_of(rid)
                if event == reqtrace.STREAMED]
    assert len(streamed) == 1
    assert streamed[0]["tokens"] == len(tokens)
    assert streamed[0]["polls"] == polls
    assert 0.0 < streamed[0]["hold_max_s"] <= streamed[0]["hold_sum_s"]
    assert streamed[0]["hold_sum_s"] < 30.0


@pytest.mark.parametrize("buckets,total", [("e2e_buckets", "e2e_s"),
                                           ("ttft_buckets", "ttft_s")])
def test_why_slow_still_sums_with_streamed_present(buckets, total):
    rid = f"why-slow-{total}"
    stream(rid, 8)
    events = events_of(rid)
    stamps = {event: ts for _rid, event, ts, _args in events}
    # stamped when the stream ended, on the replica's loop: around the
    # engine thread's FINISHED, on either side of it
    assert {reqtrace.STREAMED, reqtrace.FINISHED} <= set(stamps)
    report = reqtrace.why_slow(rid, [{"pid": 1, "events": events}])
    assert report["outcome"] == reqtrace.FINISHED
    assert sum(report[buckets].values()) == pytest.approx(
        report[total], abs=1e-4)
    assert report["end_ts"] == pytest.approx(stamps[reqtrace.FINISHED])
    # the raw event stays visible to the reader of why_slow
    assert reqtrace.STREAMED in [e["event"] for e in report["events"]]
    # and the fold holds with the event a second late, or alone in a
    # ring that has dropped the rest
    late = [[r, e, ts + (e == reqtrace.STREAMED), a]
            for r, e, ts, a in events]
    report = reqtrace.why_slow(rid, [{"pid": 1, "events": late}])
    assert report["end_ts"] == pytest.approx(stamps[reqtrace.FINISHED])
    assert sum(report[buckets].values()) == pytest.approx(
        report[total], abs=1e-4)
    only = [e for e in events if e[1] == reqtrace.STREAMED]
    assert reqtrace.why_slow(
        rid, [{"pid": 1, "events": only}])["e2e_s"] == 0.0
