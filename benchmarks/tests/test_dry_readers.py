"""The readers of the engine's dry-device account (PR 54) and of two
counters that had none, found by name as run.py finds them, over a record
made by hand (benchmarks/fixtures/dry-account.record.json): untraced, with
a trace, with a closed mark taken long after the window and 400 slow visits
between (PR 52's refusal), and on a program without the timeline."""

import copy
import json
import os

import pytest

from benchmarks.harness import spec, ticktimeline, tickstalls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GAP_EDGES = [0.001 * 2.0 ** (i / 4.0) for i in range(-14, 45)]
TRACE = {"host_began": 125.0, "host_ended": 131.0, "window_s": 4.0,
         "busy_s": 3.7}
DRY = ("device_dry_pct", "dry_dispatch_pct", "dry_gap_p99_ms",
       "dry_prefill_pct", "dry_stage_pct", "dry_between_pct")


def top_gap(rank_in_bucket):
    # the longest gap, 200 ms, lies in (181.0, 215.3] ms, alone
    return 1e3 * GAP_EDGES[44] * (GAP_EDGES[45] / GAP_EDGES[44]) \
        ** rank_in_bucket


# The fixture's rows, 0.5 s of visits each, by their `end`:
#    99.5  before the window
#   100.5  24 dispatches, nothing dry
#   110.0  26, one gap of 40 ms: 30 under prefill/finish, 4 between,
#          4 stage, 2 grow
#   120.0  24, two gaps of 5 ms: 4 stage, 2 dispatch, 4 wait
#   124.9  30, one of 200 ms: 150 prefill/finish, 20 prefill/chunk, 10
#          prefill, 10 between, 2 grow, 6 stage, 2 dispatch
#   125.0  24, one of 20 ms under emit  (a trace begins here: left out)
#   140.0  24, nothing dry
#   144.9  28, four gaps of 1 ms, all between
EXPECTED = {
    # seven rows, 3.5 s: 274 ms dry in 9 gaps of 180 dispatches; the 99th
    # percentile is at rank 8.91 of 9, 0.91 into the longest gap's bucket
    False: {"device_dry_pct": 100.0 * 0.274 / 3.5,
            "dry_dispatch_pct": 100.0 * 9 / 180,
            "dry_gap_p99_ms": top_gap(0.91),
            "dry_prefill_pct": 100.0 * 0.210 / 0.274,
            "dry_stage_pct": 100.0 * 0.022 / 0.274,
            "dry_between_pct": 100.0 * 0.018 / 0.274},
    # the four rows that ended before 125.0, 2.0 s: 250 ms in 4 of 104
    True: {"device_dry_pct": 100.0 * 0.250 / 2.0,
           "dry_dispatch_pct": 100.0 * 4 / 104,
           "dry_gap_p99_ms": top_gap(0.96),
           "dry_prefill_pct": 100.0 * 0.210 / 0.250,
           "dry_stage_pct": 100.0 * 0.022 / 0.250,
           "dry_between_pct": 100.0 * 0.014 / 0.250},
}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "benchmarks", "fixtures",
                           "dry-account.record.json")) as f:
        return json.load(f)


def record_of(recorded, traced):
    out = copy.deepcopy(recorded)
    if traced:
        out["trace"] = dict(TRACE)
    return out


def reader(name, cell="serve-xing-longin-closed64"):
    return spec.Cell(ROOT, cell).reader(name)


def test_the_gap_edges_are_the_programs():
    from ray_tpu._internal import accel
    assert GAP_EDGES == accel._GAP_EDGES
    assert GAP_EDGES[44] < 0.200 <= GAP_EDGES[45]


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", DRY)
def test_reader_over_the_recorded_record(recorded, name, traced):
    assert reader(name)(record_of(recorded, traced)) == pytest.approx(
        EXPECTED[traced][name])


def test_every_serve_cell_lists_the_six_and_finds_their_files():
    cells = [w["name"] for w in spec.Cell(
        ROOT, "serve-chat-closed64").benchmark["workloads"]
        if w["name"].startswith("serve-")]
    assert len(cells) == 8
    for name in cells:
        cell = spec.Cell(ROOT, name)
        listed = {m["name"]: m for m in cell.metrics(True)}
        for metric in DRY:
            assert listed[metric]["moves"] == "tpot_p90_ms"
            assert callable(cell.reader(metric))


def without_timeline(rec):
    # the `tick` row of PR 54's parent
    for mark in (rec["opened"], rec["closed"]):
        for row in mark["steps"]:
            for key in ("timeline", "dry_gap_hist", "dry_by_phase",
                        "dry_gap_max_s"):
                row.pop(key, None)


def without_tick_row(rec):
    rec["closed"]["steps"] = [row for row in rec["closed"]["steps"]
                              if row["kind"] != "tick"]


def no_row_in_the_span(rec):
    rec["opened"]["t"] = rec["t0"] = 144.95


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", DRY)
@pytest.mark.parametrize("strip", [without_timeline, without_tick_row,
                                   no_row_in_the_span])
def test_reader_returns_none_when_the_program_has_nothing(
        recorded, name, strip, traced):
    rec = record_of(recorded, traced)
    strip(rec)
    assert reader(name)(rec) is None


@pytest.mark.parametrize("name", DRY)
def test_a_late_closed_mark_and_400_slow_visits_read_the_same(
        recorded, name):
    """PR 52's refusal: the closed mark of a traced run waits for the
    profiler's stop and the parses, and a cell with regular slow work
    filled the list of 64 meanwhile, so the tail's readers gave None. The
    rows carry their own clock: what comes after the trace began, however
    much and however late, is not read."""
    rec = record_of(recorded, True)
    row = rec["closed"]["steps"][-1]
    template = row["timeline"][4]
    late = []
    for i in range(400):
        flush = copy.deepcopy(template)
        flush["end"] = 125.2 + i * 0.37
        flush["slow"] = [{"end": flush["end"] - 0.01, "extent_s": 0.3,
                          "typical_s": 0.03, "wall_s": 0.29, "cpu_s": 0.2,
                          "phases": {"prefill": 0.25}, "phases_cpu": {},
                          "counters": {"prompts_finished": 1},
                          "dry_s": 0.2, "dry_by_phase": {"prefill": 0.2},
                          "pauses": []}]
        late.append(flush)
    row["timeline"] = row["timeline"][:6] + late
    row["slow"] = [step for flush in late for step in flush["slow"]]
    row["slow_total"], row["slow_seconds"] = 400, 120.0
    rec["closed"]["t"] = 275.0
    assert rec["closed"]["t"] - rec["opened"]["t"] > 150
    assert reader(name)(rec) == pytest.approx(EXPECTED[True][name])


def test_nothing_dry_reads_zero_and_not_none(recorded):
    """A listed cell's traced line must carry the metric."""
    rec = record_of(recorded, False)
    rec["opened"]["t"], rec["closed"]["t"] = 130.0, 144.0   # one row
    assert reader("device_dry_pct")(rec) == 0.0
    assert reader("dry_dispatch_pct")(rec) == 0.0
    assert reader("dry_gap_p99_ms")(rec) == 0.0
    for name in ("dry_prefill_pct", "dry_stage_pct", "dry_between_pct"):
        assert reader(name)(rec) == 0.0


def test_the_account_of_a_span_and_what_stderr_says(recorded, capsys):
    rec = record_of(recorded, True)
    assert ticktimeline.span(rec) == (100.0, 125.0)
    assert [row["end"] for row in ticktimeline.rows(rec)] \
        == [100.5, 110.0, 120.0, 124.9]
    account = ticktimeline.dry(rec)
    assert sum(account["by_phase"].values()) == pytest.approx(
        account["dry_s"]) == pytest.approx(0.250)
    assert account["dry_s_upper"] - account["dry_s_lower"] \
        == pytest.approx(0.026)
    assert account["gap_max_s"] == 0.200 and sum(account["counts"]) == 4
    assert tickstalls.quantile(account["edges"], account["counts"], 0.5) \
        == pytest.approx(0.005, rel=0.2)     # its bucket's upper edge
    # the trace's own seconds [125.0, 129.0): the one row that ended there
    traced = ticktimeline.dry(rec, 125.0, 129.0)
    assert traced["dry_s"] == pytest.approx(0.020) and traced["seconds"] == 0.5
    reader("device_dry_pct")(rec)
    said = capsys.readouterr().err
    assert "dry account, window: dry 0.2500 s of 2.000 (12.500 %)" in said
    assert "prefill/finish 0.1800" in said
    assert "the trace's own idle 0.3000 s (7.500 %)" in said
    assert "rows that ended in it: dry 0.0200 s of 0.500" in said


def test_dry_seconds_of_a_stretch_finer_than_a_row(recorded):
    """A row's dry seconds lie evenly over its stretch (0.5 s back from its
    end, or from the row before it), but for its slow visits, which keep
    their own time."""
    rec = record_of(recorded, True)
    # the row that ended at 125.0 began at 124.9, where the one before it
    # ended: its 20 ms lie in (124.9, 125.0]
    assert ticktimeline.dry_seconds_in(rec, 124.95, 129.0) \
        == pytest.approx(0.010)
    # the row at 124.9 (200 ms dry) began at 124.4: a fifth of it
    assert ticktimeline.dry_seconds_in(rec, 124.8, 124.9) \
        == pytest.approx(0.040)
    row = rec["closed"]["steps"][-1]["timeline"][4]
    row["slow"] = [{"end": 124.85, "extent_s": 0.25, "dry_s": 0.180,
                    "dry_by_phase": {"prefill/finish": 0.180}}]
    # now 180 ms of it lie in (124.6, 124.85] and 20 ms over the row
    assert ticktimeline.dry_seconds_in(rec, 124.8, 124.9) \
        == pytest.approx(0.180 * 0.05 / 0.25 + 0.020 * 0.1 / 0.5)
    assert ticktimeline.dry_seconds_in(rec, 0.0, 1e9) == pytest.approx(
        sum(sum(r["dry_by_phase"].values())
            for r in rec["closed"]["steps"][-1]["timeline"]))
    without_timeline(rec)
    assert ticktimeline.dry_seconds_in(rec, 0.0, 1e9) is None


@pytest.mark.parametrize("name, cell, value", [
    ("sorted_chunk_pct", "serve-xing-longin-closed64", 100.0 * 182 / 200),
    ("index_share_factor", "serve-keye-longdoc-closed96", 6300 / 2000)])
def test_the_two_counter_readers(recorded, name, cell, value):
    rec = record_of(recorded, False)
    listed = {m["name"] for m in spec.Cell(ROOT, cell).metrics(True)}
    assert name in listed
    assert reader(name, cell)(rec) == pytest.approx(value)
    # a program whose traffic never counted: off the line
    for mark in (rec["opened"], rec["closed"]):
        mark["steps"][-1]["counters"] = {"dispatches": 10}
    assert reader(name, cell)(rec) is None
    without_tick_row(rec)
    assert reader(name, cell)(rec) is None
