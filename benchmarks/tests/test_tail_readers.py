"""The six readers of the tick's tail (PR 39) over a hand-made record,
found by name as run.py finds them: with and without a trace in the record;
a program without the fields (the parent of the PR that added them) reads
None."""

import copy
import os

import pytest

from benchmarks.harness import spec, tickstalls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["serve-chat-closed64", "serve-docqa-open",
         "serve-falconh1-chat-closed128",
         "serve-nemotron3-reason-closed192"]

# four buckets a doubling from 1 ms, as the program has them
EDGES = [0.001 * 2.0 ** (i / 4.0) for i in range(45)]


def bucket(seconds):
    return next((i for i, edge in enumerate(EDGES) if seconds <= edge),
                len(EDGES))


def hist(extents):
    counts = [0] * (len(EDGES) + 1)
    for seconds, n in extents.items():
        counts[bucket(seconds)] += n
    return {"edges_s": EDGES, "counts": counts}


def slow(end, extent, pauses=(), stage=0.004, stage_cpu=0.003,
         lookahead=1, finished=0, finish_s=0.0):
    counters = {"lookahead_ticks": lookahead, "decode_rows": 32,
                "prompts_finished": finished}
    if finished:    # timed only in the visits that finish a prompt
        counters["prefill_finish_s"] = finish_s
    return {"end": end, "extent_s": extent, "typical_s": 0.02,
            "wall_s": extent - 0.001, "cpu_s": 0.01,
            "phases": {"between": 0.001, "stage": stage,
                       "dispatch": 0.002, "wait": extent - stage - 0.003},
            "phases_cpu": {"stage": stage_cpu, "dispatch": 0.002,
                           "wait": 0.0},
            "counters": counters,
            "pauses": [{"what": what, "t0": t0, "t1": t1,
                        "seconds": t1 - t0, "pause_s": t1 - t0}
                       for what, t0, t1 in pauses]}


# A 10 s window, marks at its edges. Before it: 100 visits of 20 ms and one
# slow one. In it: 388 visits of 20 ms, 8 of 38 ms, and four slow ones:
#   at 102 s, 120 ms: a gen-2 collection of 90 ms inside it
#   at 104 s,  70 ms: nothing stamped
#   at 106 s, 220 ms: a flush 105.85–105.95 that holds its encode
#                     105.90–105.93, and a drain 105.99–106.00
#   at 107 s, 2.02 s: the profiler's stop of a traced run, in a visit
#                     that also finished four prompts in 0.4 s
# In the window 100 prompts finish, in 2.4 s of `_finish_prefill`.
BEFORE = slow(95.0, 0.100)
SLOW = [slow(102.0, 0.120, [("gc2", 101.90, 101.99)]),
        slow(104.0, 0.070),
        slow(106.0, 0.220, [("metrics_flush", 105.85, 105.95),
                            ("reqtrace_encode", 105.90, 105.93),
                            ("drain/idle", 105.99, 106.00)],
             stage=0.104, stage_cpu=0.003, lookahead=0),
        slow(107.0, 2.020, stage=1.004, stage_cpu=0.004, finished=4,
             finish_s=0.4)]
OPENED = {"t": 100.0, "steps": [
    {"kind": "tick", "steps": 101, "wall_s": 2.1, "cpu_s": 0.5,
     "phases": {"between": 0.1, "stage": 0.4, "dispatch": 0.2,
                "wait": 1.4},
     "phases_cpu": {"stage": 0.3, "dispatch": 0.2, "wait": 0.0},
     "counters": {"lookahead_ticks": 99, "decode_rows": 3200,
                  "prompts_finished": 25, "prefill_finish_s": 0.5},
     "extent_hist": hist({0.020: 100, 0.100: 1}),
     "slow": [BEFORE], "slow_total": 1, "slow_seconds": 0.1}]}
CLOSED = {"t": 110.0, "steps": [
    {"kind": "tick", "steps": 501, "wall_s": 12.6, "cpu_s": 2.5,
     # in the window: stage 2.8 s of which the thread ran 1.7, dispatch
     # 0.8 of which 0.6
     "phases": {"between": 0.5, "stage": 3.2, "dispatch": 1.0,
                "wait": 8.0},
     "phases_cpu": {"stage": 2.0, "dispatch": 0.8, "wait": 0.0},
     "counters": {"lookahead_ticks": 489, "decode_rows": 16000,
                  "prompts_finished": 125, "prefill_finish_s": 2.9},
     "extent_hist": hist({0.020: 488, 0.038: 8, 0.100: 1, 0.120: 1,
                          0.070: 1, 0.220: 1, 2.020: 1}),
     "slow": [BEFORE] + SLOW, "slow_total": 5, "slow_seconds": 2.53}]}
RECORD = {"t0": 100.0, "t1": 110.0, "opened": OPENED, "closed": CLOSED,
          "report": {"events": []}}
TRACE = {"host_began": 106.5, "host_ended": 109.0, "window_s": 4.0}

# the window's visits: 388 + 8 + 4 = 400; the 396th (99 %) is the last
# of the eight at 38 ms, whose bucket ends at 38.05 ms; the median lies
# in the 20 ms bucket (19.03-22.63 ms] at rank 200 of its 388
def median(rank):
    return EDGES[17] * (EDGES[18] / EDGES[17]) ** (rank / 388)


def expected(traced):
    """Traced: the visit that ended after the trace began (106.5 s) is
    taken out of its bucket, of the sums and of the counters: 399 visits
    in 6.5 s. Explained: 90 ms under the collection; none; 100 ms under the
    flush (its encode lies inside it) + 10 ms under the drain."""
    mid = median(199.5 if traced else 200)
    stalled = sum(extent - mid for extent in (
        (0.120, 0.070, 0.220) if traced else (0.120, 0.070, 0.220, 2.020)))
    late = 1.006 if traced else 0.0     # its stage + dispatch
    late_cpu = 0.006 if traced else 0.0
    return {
        "tick_p99_ms": 1e3 * (
            EDGES[20] * (EDGES[21] / EDGES[20]) ** (7.01 / 8) if traced
            else EDGES[21]),
        "tick_stall_pct": 100.0 * stalled / (6.5 if traced else 10.0),
        "tick_stall_unexplained_pct": 100.0 * (1.0 - 0.200 / stalled),
        "tick_stage_offcpu_pct":
            100.0 * (3.6 - late - (2.3 - late_cpu)) / (3.6 - late),
        "lookahead_pct": 100.0 * (389 / 399 if traced else 390 / 400),
        "prefill_finish_ms": 1e3 * (2.0 / 96 if traced else 2.4 / 100),
    }


EXPECTED = {traced: expected(traced) for traced in (False, True)}


def record(traced):
    out = copy.deepcopy(RECORD)
    if traced:
        out["trace"] = dict(TRACE)
    return out


def without_the_fields(rec):
    # the `tick` row of the parent: sums and counters only
    for mark in (rec["opened"], rec["closed"]):
        for row in mark["steps"]:
            for key in ("extent_hist", "slow", "slow_total",
                        "slow_seconds", "phases_cpu"):
                row.pop(key, None)


def without_tick_row(rec):
    rec["closed"]["steps"] = []


def no_visit_in_window(rec):
    rec["closed"] = copy.deepcopy(rec["opened"])


def reader(name, cell="serve-chat-closed64"):
    return spec.Cell(ROOT, cell).reader(name)


def test_median_of_the_hand_made_window():
    assert tickstalls.visits(record(False))["median"] == pytest.approx(
        median(200))
    assert 0.0190 < median(200) < 0.0227


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", sorted(EXPECTED[False]))
def test_reader_over_a_hand_made_record(name, traced):
    assert reader(name)(record(traced)) == pytest.approx(
        EXPECTED[traced][name])


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", sorted(EXPECTED[False]))
@pytest.mark.parametrize("strip", [without_the_fields, without_tick_row,
                                   no_visit_in_window])
def test_reader_returns_none_when_the_program_has_nothing(name, strip,
                                                          traced):
    rec = record(traced)
    strip(rec)
    assert reader(name)(rec) is None


@pytest.mark.parametrize("traced", [False, True])
def test_only_prefill_finish_ms_needs_what_pr39_times(traced):
    """A program with the tail's fields that does not time a prompt's
    finish: that reader alone reads None; so it does where no prompt
    finished in the span."""
    rec = record(traced)
    for mark in (rec["opened"], rec["closed"]):
        mark["steps"][0]["counters"].pop("prefill_finish_s")
    for step in rec["closed"]["steps"][0]["slow"]:
        step["counters"].pop("prefill_finish_s", None)
    assert reader("prefill_finish_ms")(rec) is None
    assert reader("lookahead_pct")(rec) == pytest.approx(
        EXPECTED[traced]["lookahead_pct"])
    rec = record(traced)
    rec["closed"]["steps"][0]["counters"]["prompts_finished"] = \
        29 if traced else 25
    assert reader("prefill_finish_ms")(rec) is None


def test_no_slow_visit_reads_no_stall_and_nothing_unexplained():
    rec = record(False)
    row = rec["closed"]["steps"][0]
    row["slow"], row["slow_total"], row["slow_seconds"] = [BEFORE], 1, 0.1
    assert reader("tick_stall_pct")(rec) == 0.0
    assert reader("tick_stall_unexplained_pct")(rec) == 0.0
    assert reader("tick_p99_ms")(rec) is not None


@pytest.mark.parametrize("traced", [False, True])
def test_a_slow_list_that_dropped_the_windows_oldest(traced):
    """The stalled time is read from the sums, which count every slow visit;
    the unexplained share from the visits the list still holds."""
    rec = record(traced)
    row = rec["closed"]["steps"][0]
    row["slow"] = row["slow"][2:]     # the visits at 95 and 102 s are gone
    assert reader("tick_stall_pct")(rec) == pytest.approx(
        EXPECTED[traced]["tick_stall_pct"])
    mid = median(199.5 if traced else 200)
    held = sum(extent - mid for extent in (
        (0.070, 0.220) if traced else (0.070, 0.220, 2.020)))
    assert reader("tick_stall_unexplained_pct")(rec) == pytest.approx(
        100.0 * (1.0 - 0.110 / held))
    assert reader("lookahead_pct")(rec) == pytest.approx(
        EXPECTED[traced]["lookahead_pct"])
    # a list that may have dropped late visits too: nothing can be read
    row["slow"] = []
    if traced:
        assert reader("tick_p99_ms")(rec) is None
        assert reader("tick_stall_pct")(rec) is None
    else:
        assert reader("tick_p99_ms")(rec) == pytest.approx(
            EXPECTED[False]["tick_p99_ms"])


@pytest.mark.parametrize("intervals,seconds", [
    ([], 0.0), ([(1.0, 2.0)], 1.0), ([(1.0, 2.0), (1.5, 1.8)], 1.0),
    ([(3.0, 4.0), (1.0, 2.0), (1.5, 2.5)], 2.5)])
def test_union_of_pauses(intervals, seconds):
    assert tickstalls.union_s(intervals) == pytest.approx(seconds)


def test_every_reader_is_declared_for_all_four_serve_cells():
    benchmark = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in benchmark["per_layer"]
                if m["name"] in EXPECTED[False]}
    assert sorted(declared) == sorted(EXPECTED[False])
    assert [m["name"] for m in benchmark["per_layer"][-6:]] == [
        "tick_p99_ms", "tick_stall_pct", "tick_stall_unexplained_pct",
        "tick_stage_offcpu_pct", "lookahead_pct", "prefill_finish_ms"]
    tick_ms = next(m for m in benchmark["per_layer"]
                   if m["name"] == "tick_ms")
    for metric in declared.values():
        assert metric["workloads"] == CELLS
        assert metric["moves"] == "tpot_p90_ms"
        assert metric["layer"] == tick_ms["layer"]
        for cell in CELLS:
            assert callable(reader(metric["name"], cell))
