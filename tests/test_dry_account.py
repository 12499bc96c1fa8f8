"""The engine's own account of a dry device (accel `DryWatch`, the `tick`
row's `dry_*` and its `timeline`): CPU, scripted clocks and handles, a toy
engine."""

import pytest

from ray_tpu._internal import accel
from ray_tpu._internal.config import CONFIG
from ray_tpu.llm import GenerationRequest

from test_tick_phases import Clock, row_of, toy_engine

FAR = 1e9   # a handle that is never ready: the device always has work


class Handle:
    """An output of a dispatched program that is ready from `ready_at` on
    the scripted clock; counts its polls."""

    def __init__(self, clock, ready_at):
        self.clock = clock
        self.ready_at = ready_at
        self.polls = 0

    def is_ready(self):
        self.polls += 1
        return self.clock.at >= self.ready_at


@pytest.fixture
def clock(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(accel, "time", clock)
    monkeypatch.setattr(accel, "_step_stats", {})
    return clock


def usual_visit(clock, acc, watch, kind, ready_at=FAR, between=0.001):
    """A visit of ~3 ms that dispatches one program; returns its handle."""
    timer = accel.StepTimer(kind, sink=acc, watch=watch)
    if between:
        clock.at += between
        timer.outside("between", between)
    handle = Handle(clock, ready_at)
    with timer:
        with timer.phase("stage"):
            clock.at += 0.001
        with timer.phase("dispatch"):
            watch.dispatching()
            clock.at += 0.0005
            watch.dispatched(handle)
        with timer.phase("wait"):
            clock.at += 0.0015
    return handle


def dry_visits(clock, acc, watch, kind):
    """A long visit in whose `prefill/finish` the device runs out of work
    (at 0.0305 s into it, between two polls 1 ms apart), and the visit
    whose dispatch ends the gap. Returns (true dry seconds, the first
    visit's share by phase, the second's)."""
    t0 = clock.at
    timer = accel.StepTimer(kind, sink=acc, watch=watch)
    with timer:
        with timer.phase("dispatch"):
            watch.dispatching()
            clock.at += 0.001
            watch.dispatched(Handle(clock, t0 + 0.0305))
        with timer.phase("wait"):
            clock.at += 0.010
        with timer.phase("emit"):
            clock.at += 0.010
        with timer.phase("prefill"):
            clock.at += 0.002
            with timer.part("prefill", "finish"):
                for _ in range(40):
                    clock.at += 0.001
                    watch.poll()
            clock.at += 0.001
    clock.at += 0.004
    timer = accel.StepTimer(kind, sink=acc, watch=watch)
    timer.outside("between", 0.004)
    with timer:
        with timer.phase("grow"):
            clock.at += 0.001
        with timer.phase("stage"):
            clock.at += 0.003
        with timer.phase("dispatch"):
            clock.at += 0.0005
            watch.dispatching()
            clock.at += 0.0005
            watch.dispatched(Handle(clock, FAR))
            ended = clock.at
    return (ended - (t0 + 0.0305),
            {"prefill/finish": 0.0005 + 0.032, "prefill": 0.001},
            {"between": 0.004, "grow": 0.001, "stage": 0.003,
             "dispatch": 0.001})


def test_a_dispatch_on_a_busy_device_adds_nothing(clock):
    kind = "dry-busy"
    acc = accel.StepAccumulator(kind, timeline=True)
    watch = accel.DryWatch()
    handles = [usual_visit(clock, acc, watch, kind) for _ in range(24)]
    acc.flush()
    row = row_of(kind)
    assert row["counters"]["dispatches"] == 24
    assert "dry_dispatches" not in row["counters"]
    assert "dry_s_upper" not in row["counters"]
    assert row["dry_by_phase"] == {} and sum(
        row["dry_gap_hist"]["counts"]) == 0 and row["dry_gap_max_s"] == 0.0
    # one poll where each phase and the time between two visits ended
    # (dispatch, wait, between, stage) and one at the next dispatch
    assert all(h.polls == 5 for h in handles[:-1])
    assert watch.totals["dispatches"] == 24 and watch.totals["dry_s"] == 0.0


def test_the_gap_lies_between_its_bounds_and_its_phases_sum_to_it(clock):
    kind = "dry-gap"
    acc = accel.StepAccumulator(kind, timeline=True)
    watch = accel.DryWatch()
    for _ in range(24):
        usual_visit(clock, acc, watch, kind)
    acc.flush()
    before = row_of(kind)
    true_s, first, second = dry_visits(clock, acc, watch, kind)
    acc.flush()
    row = row_of(kind)
    counters = {name: value - before["counters"].get(name, 0)
                for name, value in row["counters"].items()}
    assert counters["dispatches"] == 2 and counters["dry_dispatches"] == 1
    lower, upper = counters["dry_s_lower"], counters["dry_s_upper"]
    assert lower < true_s < upper
    assert upper - lower == pytest.approx(0.001)      # one poll's interval
    dry_s = 0.5 * (lower + upper)
    assert dry_s == pytest.approx(true_s)
    assert sum(row["dry_by_phase"].values()) == pytest.approx(dry_s)
    assert row["dry_by_phase"] == pytest.approx({**first, **second})
    # one gap, in the bucket of its length, and the longest
    edges, counts = (row["dry_gap_hist"][k] for k in ("edges_s", "counts"))
    assert sum(counts) == 1 and len(counts) == len(edges) + 1
    bucket = counts.index(1)
    assert edges[bucket - 1] < dry_s <= edges[bucket]
    assert row["dry_gap_max_s"] == pytest.approx(dry_s)
    assert edges[14:] == row["extent_hist"]["edges_s"] and edges[0] < 1e-4
    # the long visit was slow, and keeps its own share of the gap
    slow = row["slow"][-1]
    assert slow["extent_s"] == pytest.approx(0.064)
    assert slow["dry_by_phase"] == pytest.approx(first)
    assert slow["dry_s"] == pytest.approx(sum(first.values()))
    assert slow["counters"]["dry_s_upper"] \
        - slow["counters"]["dry_s_lower"] == pytest.approx(0.001)
    # the engine-side totals say the same
    assert watch.totals["dry_s"] == pytest.approx(dry_s)
    assert watch.totals["by_phase"] == pytest.approx(row["dry_by_phase"])
    assert watch.totals["dry_dispatches"] == 1
    assert watch.totals["gap_max_s"] == pytest.approx(dry_s)


def test_boundary_polls_alone_leave_a_phase_in_doubt(clock):
    """Without a poll inside it a long phase bounds the moment only to
    itself: upper − lower is the phase, and the mean takes half."""
    kind = "dry-coarse"
    acc = accel.StepAccumulator(kind, timeline=True)
    watch = accel.DryWatch()
    t0 = clock.at
    timer = accel.StepTimer(kind, sink=acc, watch=watch)
    with timer:
        with timer.phase("dispatch"):
            watch.dispatching()
            watch.dispatched(Handle(clock, t0 + 0.010))
        with timer.phase("prefill"):
            clock.at += 0.040
        with timer.phase("dispatch"):
            watch.dispatching()
            watch.dispatched(Handle(clock, FAR))
    acc.flush()
    row = row_of(kind)
    assert "dry_s_lower" not in row["counters"]
    assert row["counters"]["dry_s_upper"] == pytest.approx(0.040)
    assert row["dry_by_phase"] == pytest.approx({"prefill": 0.020,
                                                 "dispatch": 0.0})


def test_a_wait_for_the_handle_itself_dates_the_gap_exactly(clock):
    """A read with nothing dispatched behind it returns when the device
    runs dry: `waited` leaves no doubt. An owner gone idle leaves no gap:
    what follows is nobody's wait (the rest of the visit that found no
    work stays counted)."""
    kind = "dry-waited"
    acc = accel.StepAccumulator(kind, timeline=True)
    watch = accel.DryWatch()
    t0 = clock.at
    with accel.StepTimer(kind, sink=acc, watch=watch) as timer:
        with timer.phase("dispatch"):
            watch.dispatching()
            handle = Handle(clock, t0 + 0.015)
            watch.dispatched(handle)
        with timer.phase("wait"):
            clock.at += 0.015
            watch.waited(handle)
        with timer.phase("admit"):
            clock.at += 0.002
        with timer.phase("prefill"):
            watch.dispatching()
            watch.dispatched(Handle(clock, t0 + 0.025))
            clock.at += 0.001
    with accel.StepTimer(kind, sink=acc, watch=watch) as timer:
        with timer.phase("dispatch"):
            watch.dispatching()
            handle = Handle(clock, t0 + 0.030)
            watch.dispatched(handle)
        with timer.phase("wait"):
            clock.at = t0 + 0.030
            watch.waited(handle)
        with timer.phase("emit"):
            clock.at += 0.001
    watch.idle()
    clock.at += 0.050
    with accel.StepTimer(kind, sink=acc, watch=watch) as timer:
        with timer.phase("admit"):
            clock.at += 0.003
        with timer.phase("prefill"):
            watch.dispatching()
            watch.dispatched(Handle(clock, FAR))
    acc.flush()
    row = row_of(kind)
    assert row["counters"]["dispatches"] == 4
    assert row["counters"]["dry_dispatches"] == 1
    assert row["counters"]["dry_s_lower"] == pytest.approx(0.003)
    assert row["counters"]["dry_s_upper"] == pytest.approx(0.003)
    assert row["dry_by_phase"] == pytest.approx(
        {"wait": 0.0, "admit": 0.002, "prefill": 0.0, "emit": 0.001})
    assert sum(row["dry_gap_hist"]["counts"]) == 1
    assert row["dry_gap_max_s"] == pytest.approx(0.002)


@pytest.mark.parametrize("tracing", [False, True])
def test_a_gap_is_a_span_only_while_a_trace_runs(clock, monkeypatch,
                                                 tracing):
    import jax
    names = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kwargs):
            names.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(accel, "_tracing", lambda: tracing)
    kind = "dry-span"
    dry_visits(clock, accel.StepAccumulator(kind, timeline=True),
               accel.DryWatch(), kind)
    dry = [name for name in names if name.startswith("dry/")]
    assert dry == (["dry/prefill/finish"] if tracing else [])


def test_kill_switch_builds_no_ring_and_polls_nothing(clock):
    CONFIG.apply_system_config({"no_accel_metrics": True})
    try:
        kind = "dry-off"
        acc = accel.StepAccumulator(kind, timeline=True)
        watch = accel.DryWatch()
        timer = accel.StepTimer(kind, sink=acc, watch=watch)
        assert timer.watch is None
        handle = Handle(clock, FAR)
        watch.dispatched(handle)
        with timer:
            with timer.phase("stage"):
                clock.at += 0.001
            with timer.part("prefill", "finish"):
                clock.at += 0.001
        timer = accel.StepTimer(kind, sink=acc, watch=watch)
        timer.outside("between", 0.001)
        acc.add(0.002, extent_s=0.003)
        acc.flush()
        assert handle.polls == 0 and accel.step_summary() == []
        engine = toy_engine()
        engine.generate([[1, 2, 3]], max_new_tokens=4)
        assert engine._dry is None and engine.radix.poll is None
        assert engine.stats()["dry"] == {}
        assert accel.step_summary() == []
    finally:
        CONFIG.apply_system_config({"no_accel_metrics": False})


def window_of(rows, begin, end):
    """What `benchmarks/harness/ticktimeline.py` reads: the sums of the
    timeline's rows that ended in [begin, end)."""
    out = {"steps": 0, "wall_s": 0.0, "extent_s": 0.0, "phases": {},
           "counters": {}, "dry_by_phase": {}, "extent_hist": {},
           "dry_gap_hist": {}, "slow": 0}
    for row in rows:
        if begin <= row["end"] < end:
            for key in ("steps", "wall_s", "extent_s"):
                out[key] += row[key]
            for key in ("phases", "counters", "dry_by_phase",
                        "extent_hist", "dry_gap_hist"):
                accel._sum_phases(out[key], row[key])
            out["slow"] += len(row["slow"])
    return out


def test_a_window_of_the_ring_is_closed_less_opened(clock):
    kind = "dry-window"
    acc = accel.StepAccumulator(kind, timeline=True)
    watch = accel.DryWatch()

    def run(visits):
        for i in range(visits):
            usual_visit(clock, acc, watch, kind)
            if i % 20 == 19:
                dry_visits(clock, acc, watch, kind)
        acc.flush()
        return clock.at, row_of(kind)

    run(100)
    clock.at += 1.0
    opened_t, opened = run(100)
    clock.at += 1.0
    closed_t, closed = run(300)
    clock.at += 1.0
    _, final = run(50)
    # read from the LATER summary: the rows that ended between the marks
    window = window_of(final["timeline"], opened_t + 0.5, closed_t + 0.5)
    assert window["steps"] == closed["steps"] - opened["steps"] == 330
    assert window["wall_s"] == pytest.approx(
        closed["wall_s"] - opened["wall_s"])
    assert window["extent_s"] == pytest.approx(
        window["wall_s"] + window["phases"]["between"])
    assert window["slow"] == closed["slow_total"] - opened["slow_total"] > 0
    for key in ("phases", "counters", "dry_by_phase"):
        assert window[key] == pytest.approx(
            {name: value - opened[key].get(name, 0.0)
             for name, value in closed[key].items()}), key
    for key in ("extent_hist", "dry_gap_hist"):
        counts = [b - a for a, b in zip(opened[key]["counts"],
                                        closed[key]["counts"])]
        assert {i: n for i, n in enumerate(counts) if n} == window[key]
    assert sum(window["dry_gap_hist"].values()) \
        == window["counters"]["dry_dispatches"] == 15
    assert sum(window["dry_by_phase"].values()) == pytest.approx(
        0.5 * (window["counters"]["dry_s_lower"]
               + window["counters"]["dry_s_upper"]))


def test_a_kind_that_asks_for_no_timeline_keeps_none():
    engine = toy_engine()
    engine.generate([[1, 2, 3, 4]], max_new_tokens=6)
    engine.stats()
    rows = {row["kind"]: row for row in accel.step_summary()}
    assert rows["decode"]["steps"] > 0
    assert not {"timeline", "slow", "slow_total", "extent_hist",
                "dry_gap_hist", "dry_by_phase"} & set(rows["decode"])
    assert {"timeline", "slow", "slow_total", "extent_hist",
            "dry_gap_hist", "dry_gap_max_s", "dry_by_phase"} \
        <= set(rows["tick"])
    assert not hasattr(accel, "_SLOW_KEEP")
    assert sum(f["steps"] for f in rows["tick"]["timeline"]) \
        <= rows["tick"]["steps"]


def test_engine_counts_every_program_it_dispatches():
    """200 visits of a toy engine on the CPU: `dispatches` is the decode
    steps, the prefill chunks and the installs (a dense staging cache a
    prompt, a gather where its prefix was shared, a page write and a first
    token a finished prompt), each counted where it is called."""
    engine = toy_engine(batch=4)
    calls = {}

    def counted(name, fn):
        def call(*args, **kwargs):
            calls[name] = calls.get(name, 0) + 1
            return fn(*args, **kwargs)
        return call

    for name in ("_decode", "_chunk_prefill", "_dense_zero_caches",
                 "_gather_pages", "_write_pages"):
        setattr(engine, name, counted(name, getattr(engine, name)))
    before = dict(row_of("tick").get("counters", {}))
    shared = list(range(1, 25))
    for i in range(40):
        engine.submit(GenerationRequest(
            prompt_tokens=shared + [30 + i, 31, 32],
            max_new_tokens=12, request_id=f"dry-{i}"))
    visits = 0
    while engine.has_work() and visits < 200:
        engine.step()
        visits += 1
    assert visits == 200 or not engine.has_work()
    stats = engine.stats()
    first_tokens = stats["prompts_finished"]
    assert calls["_decode"] == sum(stats["sampler"].values()) > 50
    assert calls["_chunk_prefill"] == stats["prefill_chunks"] > 20
    assert calls["_gather_pages"] > 0
    installs = calls["_dense_zero_caches"] + calls["_write_pages"] \
        + first_tokens
    dry = stats["dry"]
    # a staging cache's zeros and its gather are one hand-over
    assert dry["dispatches"] == calls["_decode"] + calls["_chunk_prefill"] \
        + installs
    assert 0 <= dry["dry_dispatches"] <= dry["dispatches"]
    assert dry["dry_s_lower"] <= dry["dry_s"] <= dry["dry_s_upper"]
    assert sum(dry["by_phase"].values()) == pytest.approx(dry["dry_s"])
    counters = row_of("tick")["counters"]
    assert counters["dispatches"] - before.get("dispatches", 0) \
        == dry["dispatches"]
    assert counters.get("dry_s_upper", 0.0) - before.get("dry_s_upper", 0.0) \
        == pytest.approx(dry["dry_s_upper"])
    assert stats["leaked_pages"] == 0
