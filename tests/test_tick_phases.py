"""The continuous tick by phase (accel `tick` row, StepTimer.phase) and the
replica's token hand-off (reqtrace STREAMED): CPU, toy engines."""

import asyncio
import time

import pytest

from ray_tpu._internal import accel
from ray_tpu._internal.config import CONFIG
from ray_tpu.llm import PagedEngineConfig, PagedLLMEngine, reqtrace
from ray_tpu.llm import GenerationRequest
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
                       for name, seconds in after["phases"].items()},
            "extents": [b - a for a, b in zip(
                before["extent_hist"]["counts"],
                after["extent_hist"]["counts"])]}


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


@pytest.fixture
def built(monkeypatch):
    """The names of the spans built during the test, on a step table of
    its own."""
    import jax
    names = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kwargs):
            names.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(accel, "_step_stats", {})
    return names


@pytest.mark.parametrize("what", ["row", "spans"])
def test_kill_switch_leaves_no_row_and_builds_no_span(built, what):
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


# -- the distribution of visits, the slow ones, what paused them (PR 39; built by PR 38) ----


def row_of(kind):
    for row in accel.step_summary():
        if row["kind"] == kind:
            return row
    return {"steps": 0, "slow_total": 0, "slow": [],
            "extent_hist": {"counts": [0] * (len(accel._EXTENT_EDGES) + 1)}}


def visit(acc, kind, sleeps=None, between=0.0, inside=None):
    """One timed step of `kind` with the tick's phases; `sleeps`: seconds
    slept by phase; `inside()` runs first in the phase `emit`."""
    sleeps = dict({"wait": 0.002}, **(sleeps or {}))
    timer = accel.StepTimer(kind, sink=acc)
    if between:
        timer.outside("between", between)
    with timer:
        for name in ("stage", "dispatch", "wait", "emit"):
            with timer.phase(name):
                if name == "emit" and inside is not None:
                    inside()
                if name in sleeps:
                    time.sleep(sleeps[name])
        timer.count("lookahead_ticks", 1)


def usual_then(acc, kind, **slow):
    """24 usual steps (~2 ms), then one made slow; returns that step as
    the row's `slow` has it."""
    for _ in range(24):
        visit(acc, kind)
    acc.flush()   # under load a usual step may have been slow too
    before = row_of(kind)["slow_total"]
    visit(acc, kind, **slow)
    acc.flush()
    row = row_of(kind)
    assert row["slow_total"] == before + 1
    return row["slow"][-1]


@pytest.mark.parametrize("steps", [1, 16, 37])
def test_extent_hist_counts_every_step_once(steps):
    kind = f"hist-{steps}"
    acc = accel.StepAccumulator(kind, timeline=True)
    visit(acc, kind)
    acc.flush()
    before = row_of(kind)
    for _ in range(steps):
        visit(acc, kind, between=0.004)
    acc.flush()
    after = row_of(kind)
    assert after["steps"] - before["steps"] == steps
    gained = [b - a for a, b in zip(before["extent_hist"]["counts"],
                                    after["extent_hist"]["counts"])]
    assert sum(gained) == steps and min(gained) >= 0
    edges = after["extent_hist"]["edges_s"]
    assert len(gained) == len(edges) + 1
    # an extent is `between` + the step: 4 + 2 ms and the timer's own
    median = accel.extent_quantile(
        {"edges_s": edges, "counts": gained}, 0.5)
    assert 0.006 <= median < 0.1


def test_engine_visits_are_counted_with_their_between(forty_ticks):
    assert sum(forty_ticks["busy"]["extents"]) == 40


def test_a_slow_thread_clock_is_inside_the_phase_it_measures(monkeypatch):
    """On a crowded host `time.thread_time()` (a real syscall) takes
    hundreds of microseconds: read after a phase's end it would lie in no
    phase, and the phases would stop tiling the step."""
    real = time.thread_time

    def slow_clock():
        time.sleep(0.002)
        return real()

    monkeypatch.setattr(time, "thread_time", slow_clock)
    timer = accel.StepTimer("slow-clock")
    with timer:
        for name in ("stage", "dispatch", "wait", "emit", "prefill"):
            with timer.phase(name):
                pass
    # five reads of 2 ms at the phases' ends, each inside its phase
    assert sum(timer.phases.values()) >= 0.010
    assert sum(timer.phases.values()) >= 0.9 * timer.result["wall_s"]
    assert set(timer.phases_cpu) == set(timer.phases)


@pytest.mark.parametrize("phase", ["stage", "wait", "emit"])
def test_slow_visit_is_kept_whole(phase):
    kind = f"slow-{phase}"
    t0 = time.monotonic()
    step = usual_then(accel.StepAccumulator(kind, timeline=True), kind,
                      sleeps={phase: 0.12}, between=0.003)
    assert max(step["phases"], key=step["phases"].get) == phase
    assert step["phases"][phase] >= 0.12
    # a sleeping thread burns no CPU: wall − CPU of the phase says so
    assert step["phases_cpu"][phase] < 0.5 * step["phases"][phase]
    assert step["extent_s"] == pytest.approx(step["wall_s"] + 0.003)
    assert step["extent_s"] > 4 * step["typical_s"] > 0.004
    assert step["cpu_s"] < step["wall_s"]
    assert step["counters"] == {"lookahead_ticks": 1}
    assert t0 < step["end"] <= time.monotonic()
    # and the cumulative row has the CPU seconds by phase beside the wall's
    row = row_of(kind)
    assert set(row["phases_cpu"]) == {"stage", "dispatch", "wait", "emit"}
    assert row["phases_cpu"][phase] < row["phases"][phase]
    assert row["slow_seconds"] >= step["extent_s"]


@pytest.mark.parametrize("pause,overlap", [
    ((0.010, 0.030), 0.020),      # inside the visit
    ((-0.500, 0.025), 0.025),     # began before it
    ((0.100, 5.000), "to its end"),
    ((-1.000, -0.900), None),     # before it
    ((0.0101, 0.0105), None),     # under 1 ms: not kept
])
def test_pause_that_overlaps_a_slow_visit_is_listed(pause, overlap):
    kind = f"pause-{pause[0]}"
    what = f"test-{pause[0]}"
    acc = accel.StepAccumulator(kind, timeline=True)
    began = []

    def stamp():
        # the visit began ~2 ms ago (its `wait`); it sleeps 118 ms more
        began.append(time.monotonic() - 0.002)
        accel.note_pause(what, began[0] + pause[0], began[0] + pause[1])

    step = usual_then(acc, kind, sleeps={"emit": 0.118}, inside=stamp)
    mine = [p for p in step["pauses"] if p["what"] == what]
    if overlap is None:
        assert mine == []
        return
    assert len(mine) == 1
    if overlap == "to its end":
        assert mine[0]["t1"] == step["end"]
        assert mine[0]["seconds"] == pytest.approx(
            step["end"] - began[0] - pause[0])
        assert mine[0]["seconds"] >= 0.015
    elif pause[0] < 0:
        # the visit began 2 ms before the stamp, or more on a busy host
        assert overlap - 0.004 < mine[0]["seconds"] < overlap + 0.05
    else:
        assert mine[0]["seconds"] == pytest.approx(overlap, abs=1e-6)
    assert mine[0]["t1"] - mine[0]["t0"] == mine[0]["seconds"]
    assert mine[0]["pause_s"] == pytest.approx(pause[1] - pause[0])
    start = step["end"] - step["extent_s"]
    assert start <= mine[0]["t0"] < mine[0]["t1"] <= step["end"]


@pytest.mark.parametrize("generation", [2])
def test_forced_collection_during_a_visit_is_stamped(generation):
    import gc
    kind = f"gc-{generation}"
    ballast = [[i] for i in range(300_000)]   # a heap worth over 1 ms
    assert accel.watch_gc() and accel.watch_gc()   # idempotent
    try:
        assert gc.callbacks.count(accel._on_gc) == 1
        step = usual_then(accel.StepAccumulator(kind, timeline=True), kind,
                          sleeps={"emit": 0.1},
                          inside=lambda: gc.collect(generation))
    finally:
        gc.callbacks.remove(accel._on_gc)
        del ballast
    stamped = [p for p in step["pauses"] if p["what"] == f"gc{generation}"]
    assert stamped and stamped[-1]["seconds"] >= 0.001
    assert stamped[-1]["seconds"] <= step["phases"]["emit"]


class Clock:
    """`time`, as `accel` sees it, at a scripted moment."""

    def __init__(self, at=1000.0):
        self.at = at

    def monotonic(self):
        return self.at

    perf_counter = monotonic

    def thread_time(self):
        return 0.0


def test_slow_is_kept_by_time_and_slow_total_keeps_counting(monkeypatch):
    """`slow` is a view of the kind's `timeline`, which keeps a flush for
    ten minutes whatever their number (the list kept 64 before PR 54: a
    reader's two marks 100 s apart lost the window's visits)."""
    clock = Clock()
    monkeypatch.setattr(accel, "time", clock)
    kind = "slow-many"
    acc = accel.StepAccumulator(kind, timeline=True)
    for _ in range(300):          # 300 slow steps in 200 s
        for _ in range(10):
            acc.add(0.010)
        acc.add(0.5, extent_s=1.0)
        clock.at += 200.0 / 300
    acc.flush()
    row = row_of(kind)
    assert row["steps"] == 3300 and sum(row["extent_hist"]["counts"]) == 3300
    assert row["slow_total"] == 300 and len(row["slow"]) == 300
    assert row["slow_seconds"] == pytest.approx(300.0)
    assert all(step["extent_s"] == 1.0 and step["wall_s"] == 0.5
               for step in row["slow"])
    ends = [step["end"] for step in row["slow"]]
    assert ends == sorted(ends) and 199.0 < ends[-1] - ends[0] < 200.0
    timeline = row["timeline"]
    assert len(timeline) == -(-3300 // 16)
    assert sum(flush["steps"] for flush in timeline) == 3300
    assert [step for flush in timeline for step in flush["slow"]] \
        == row["slow"]
    # the usual extent followed the many, not the few
    assert row["slow"][-1]["typical_s"] == pytest.approx(0.010, rel=0.35)
    assert accel.extent_quantile(row["extent_hist"], 0.5) == pytest.approx(
        0.010, rel=0.2)
    assert accel.extent_quantile(row["extent_hist"], 0.99) == pytest.approx(
        1.0, rel=0.2)
    # 599 s after the oldest flush every row is still there; 202 s later
    # only what ended inside ten minutes
    clock.at += 399.0
    for _ in range(16):
        acc.add(0.010)
    assert len(row_of(kind)["timeline"]) == len(timeline) + 1
    clock.at += 202.0
    for _ in range(15):
        acc.add(0.010)
    acc.add(0.5, extent_s=1.0)
    row = row_of(kind)
    assert [flush["end"] for flush in row["timeline"]] \
        == [clock.at - 202.0, clock.at]
    assert len(row["slow"]) == 1 and row["slow_total"] == 301
    assert row["steps"] == 3332 and sum(row["extent_hist"]["counts"]) == 3332


@pytest.mark.parametrize("what", ["fields", "stamp", "span", "gc"])
def test_kill_switch_leaves_no_field_stamp_span_or_hook(monkeypatch, built,
                                                        what):
    import gc

    def exercise():
        engine = toy_engine()
        engine.generate([[1, 2, 3]], max_new_tokens=4)
        engine.stats()
        now = time.monotonic()
        accel.note_pause("switch", now - 0.5, now)
        with accel.pause("switch-span"):
            time.sleep(0.002)
        return accel.watch_gc()

    monkeypatch.setattr(accel, "_tracing", lambda: True)
    monkeypatch.setattr(accel, "_pauses", [None] * accel._PAUSE_KEEP)
    while accel._on_gc in gc.callbacks:
        gc.callbacks.remove(accel._on_gc)
    CONFIG.apply_system_config({"no_accel_metrics": True})
    try:
        watching = exercise()
        if what == "fields":
            assert accel.step_summary() == []
        elif what == "stamp":
            assert not any(accel._pauses)
        elif what == "span":
            assert built == []
        else:
            assert not watching and accel._on_gc not in gc.callbacks
    finally:
        CONFIG.apply_system_config({"no_accel_metrics": False})
    # and with the plane back on the same calls leave all four
    try:
        watching = exercise()
        if what == "fields":
            tick = row_of("tick")
            assert {"extent_hist", "slow", "slow_total", "slow_seconds",
                    "phases_cpu"} <= set(tick)
            assert sum(tick["extent_hist"]["counts"]) == tick["steps"]
            assert tick["counters"]["decode_rows"] >= 3
            assert tick["counters"]["prefill_chunks"] == 1
        elif what == "stamp":
            assert {"switch", "switch-span"} <= {
                stamp[0] for stamp in accel._pauses if stamp}
        elif what == "span":
            assert "pause/switch-span" in built
        else:
            assert watching and accel._on_gc in gc.callbacks
    finally:
        while accel._on_gc in gc.callbacks:
            gc.callbacks.remove(accel._on_gc)


# -- what a prompt's finish costs, inside `prefill` (PR 39) ------------------


@pytest.fixture(scope="module")
def five_prompts():
    """Five prompts of two chunks each through a two-row engine: the
    `tick` row's share of it, with the visits' own phases."""
    engine = toy_engine()
    engine.generate([[1, 2, 3]], max_new_tokens=2)   # compiles
    before = tick_row(engine)
    submit(engine, 5, 3, "finish")
    while engine.has_work():
        engine.step()
    after = tick_row(engine)
    return {"stats": engine.stats(),
            "phases": delta(before, after)["phases"],
            "counters": {name: value - before["counters"].get(name, 0.0)
                         for name, value in after["counters"].items()}}


def test_prefill_parts_lie_inside_the_phase_and_are_no_phase(five_prompts):
    counters, phases = five_prompts["counters"], five_prompts["phases"]
    assert counters["prefill_chunk_s"] > 0.0
    assert counters["prefill_finish_s"] > 0.0
    assert counters["prefill_chunk_s"] + counters["prefill_finish_s"] \
        <= phases["prefill"]
    # the phases still tile the visit: a part is a counter, not a phase
    # (the row is the process's: a phase that only another file's engine
    # ran, a windowed model's `compress`, stands in it with no seconds)
    assert {name for name, seconds in phases.items() if seconds} \
        <= set(PHASES) | {"state"}


@pytest.mark.parametrize("name,count", [("prompts_finished", 5),
                                        ("prefill_chunks", 10),
                                        ("prefill_heads", 5)])
def test_each_finished_prompt_is_counted_once(five_prompts, name, count):
    """`prefill_heads` (PR 40): the chunks that ran the head, one a
    finished prompt: half of these ten ran none."""
    assert five_prompts["counters"][name] == count
    assert five_prompts["stats"][name] >= count


@pytest.mark.parametrize("tracing", [False, True])
def test_prefill_parts_are_spans_only_while_a_trace_runs(monkeypatch, built,
                                                         tracing):
    monkeypatch.setattr(accel, "_tracing", lambda: tracing)
    engine = toy_engine()
    engine.generate([[1, 2, 3]], max_new_tokens=2)
    parts = {"tick/prefill/chunk", "tick/prefill/finish"}
    assert parts & set(built) == (parts if tracing else set())
    assert "tick/prefill" in built


def test_part_is_a_counter_of_its_timer_and_nothing_under_the_switch():
    timer = accel.StepTimer("parted")
    with timer, timer.phase("prefill"):
        with timer.part("prefill", "finish"):
            time.sleep(0.002)
        with timer.part("prefill", "finish"):
            time.sleep(0.002)
    assert 0.004 <= timer.counters["prefill_finish_s"] \
        <= timer.phases["prefill"]
    assert set(timer.phases) == {"prefill"}
    CONFIG.apply_system_config({"no_accel_metrics": True})
    try:
        timer = accel.StepTimer("parted")
        with timer, timer.part("prefill", "finish"):
            pass
        assert timer.counters == {}
    finally:
        CONFIG.apply_system_config({"no_accel_metrics": False})


def test_cli_prints_extents_and_the_parts_of_a_slow_steps_phase(capsys):
    from ray_tpu import cli
    kind = "printed"
    usual_then(accel.StepAccumulator(kind, timeline=True), kind,
               sleeps={"emit": 0.1})
    row = dict(row_of(kind), now=time.monotonic())
    row["slow"][-1]["counters"].update(emit_handoff_s=0.0625, emits=3)
    cli._print_extents(row)
    assert "dry " not in capsys.readouterr().out    # no account, no line
    row.update(dry_by_phase={"prefill/finish": 0.75, "stage": 0.25},
               dry_gap_max_s=0.125, wall_s=19.0,
               phases=dict(row["phases"], between=1.0))
    row["counters"].update(dispatches=400, dry_dispatches=12)
    cli._print_extents(row)
    out = capsys.readouterr().out
    assert "dry 1.00s (5.0%) in 12 of 400 dispatches, longest 125ms " \
        "· prefill/finish 0.75, stage 0.25" in out
    assert "extent p50=" in out and f"slow {row['slow_total']} (" in out
    assert "emit=1" in out and "(handoff 62.5)" in out
    assert "pauses: none stamped" in out


# -- a process without a StepAccumulator: the train worker, the controller --

_NO_ACCUMULATOR_SCRIPT = r"""
import json, sys, tempfile, threading
import jax
from ray_tpu._internal import accel, serialization
from ray_tpu.train.controller import TrainController
from ray_tpu.util import metrics

traced = sys.argv[1] == "traced"
accel.ensure_installed()


class Gcs:
    puts = 0

    def put(self, namespace, key, value):
        Gcs.puts += 1


if traced:
    directory = tempfile.mkdtemp()
    jax.profiler.start_trace(directory)
try:
    # every process's flusher: on a thread of its own, inside accel.pause
    flusher = threading.Thread(
        target=lambda: metrics.flush_now(gcs=Gcs(), key="worker"))
    flusher.start()
    flusher.join(60)
    assert not flusher.is_alive()
    assert metrics.flush_now(gcs=Gcs(), key="worker") is True
    # a compile (the listener stamps its end), then a real one
    accel._on_duration_event(accel._BACKEND_COMPILE_EVENT, 0.25)
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8)).block_until_ready()
    with accel.pause("anything"):
        pass
finally:
    if traced:
        jax.profiler.stop_trace()
# the controller's fold of a rank-0 report, with and without step timing
fold = TrainController._fold_step_telemetry
fold(None, {"step": 3, "loss": 2.5})
fold(None, {"step_time_s": 0.2, "tokens": 1024, "device_time_s": 0.15})
with accel.StepTimer("bench", tokens=8) as timer:
    with timer.device():
        pass
report = accel.accel_report()
# the encoding a reply to get_accel_report travels in, and the CLI's
back = serialization.loads(serialization.dumps(report))
json.dumps(back)
rows = {row["kind"]: row for row in back["steps"]}
off = accel.accel_disabled()
assert Gcs.puts >= (0 if off else 2), Gcs.puts
if off:
    assert rows == {} and not any(accel._pauses)
else:
    assert set(rows) == {"train", "bench"}, rows
    assert rows["train"]["steps"] == 1 and rows["train"]["tokens"] == 1024
    for row in rows.values():
        assert not {"slow", "slow_total", "slow_seconds",
                    "extent_hist"} & set(row), row
    assert "compile" in {stamp[0] for stamp in accel._pauses if stamp}
    assert back["compile"]["compiles"] >= 2
assert isinstance(back["now"], float)
print("NO_ACCUMULATOR_OK")
"""


@pytest.mark.parametrize("how", ["untraced", "traced", "switched-off"])
def test_process_without_an_accumulator_runs_what_every_process_runs(how):
    """What stopped PR 38 unseen was a run of a train cell: whatever this
    plane puts into a process that times no step of its own (a flush
    inside `accel.pause`, the compile stamp, the controller's fold,
    `accel_report()`'s `now`) runs there without raising, in and outside
    a profiler trace, and its report has no field of the slow-visit log."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RTPU_NO_ACCEL_METRICS", None)
    if how == "switched-off":
        env["RTPU_NO_ACCEL_METRICS"] = "1"
    proc = subprocess.run(
        [sys.executable, "-c", _NO_ACCUMULATOR_SCRIPT, how],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "NO_ACCUMULATOR_OK" in proc.stdout


def test_gauges_follow_a_drain_and_serving_watches_the_collector():
    import gc
    import os

    from ray_tpu.llm._metrics import llm_metrics
    # the gauges are set where the tick accumulator flushes, no longer in
    # every visit: a drained engine still reads drained
    engine = toy_engine()
    tags = {"engine": "paged", "pid": str(os.getpid())}
    metrics = llm_metrics()

    def gauge(metric):
        snap = metric.snapshot()
        key = [tags.get(k, "") for k in snap["tag_keys"]]
        return next(value for tag_values, value in snap["series"]
                    if tag_values == key)

    submit(engine, 3, 4, "gauged")
    engine.step()
    assert engine.stats()["pending"] == 1   # two rows, three requests
    assert gauge(metrics.waiting) == gauge(metrics.queue_depth) == 1
    assert gauge(metrics.running) == 2
    while engine.has_work():
        engine.step()
    for metric in (metrics.waiting, metrics.queue_depth, metrics.running):
        assert gauge(metric) == 0
    del engine
    # last, as the file ended before: a replica that begins to serve
    # collects what the tests above left, freezes the heap, and from then
    # on stamps the collector's passes
    while accel._on_gc in gc.callbacks:
        gc.callbacks.remove(accel._on_gc)
    try:
        stream("watched", 4)
        assert accel._on_gc in gc.callbacks
    finally:
        while accel._on_gc in gc.callbacks:
            gc.callbacks.remove(accel._on_gc)
