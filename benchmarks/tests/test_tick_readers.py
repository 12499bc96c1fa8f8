"""The ten tick-phase and relay readers over a hand-made record, found by
name as run.py finds them; a program without the `tick` row or without
STREAMED events (the parent of the PR that added them) reads None."""

import copy
import os

import pytest

from benchmarks.harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a 10 s window: 100 ticks of 50 ms wall each, 4 ms apart
OPENED = {"steps": [
    {"kind": "decode", "steps": 10, "wall_s": 0.4, "tokens": 300},
    {"kind": "tick", "steps": 10, "wall_s": 0.5, "cpu_s": 0.1,
     "phases": {"between": 0.04, "reap": 0.001, "admit": 0.01,
                "prefill": 0.05, "grow": 0.002, "stage": 0.01,
                "dispatch": 0.02, "wait": 0.38, "emit": 0.02,
                "gauges": 0.007}}]}
CLOSED = {"steps": [
    {"kind": "tick", "steps": 110, "wall_s": 5.5, "cpu_s": 1.0,
     "phases": {"between": 0.44, "reap": 0.011, "admit": 0.11,
                "prefill": 0.55, "grow": 0.022, "stage": 0.11,
                "dispatch": 0.22, "wait": 4.18, "emit": 0.22,
                "gauges": 0.077,
                # a phase the first mark has not seen yet
                "new": 0.3}},
    {"kind": "decode", "steps": 110, "wall_s": 4.4, "tokens": 3300}]}
RECORD = {
    "t0": 100.0, "t1": 110.0, "opened": OPENED, "closed": CLOSED,
    "report": {"events": [
        ["a", "STREAMED", 99.0, {"polls": 50, "tokens": 50,
                                 "hold_sum_s": 9.0, "hold_max_s": 1.0}],
        ["b", "FINISHED", 101.0, {"tokens": 8}],
        ["b", "STREAMED", 101.0, {"polls": 8, "tokens": 9,
                                  "hold_sum_s": 0.016,
                                  "hold_max_s": 0.004}],
        ["c", "STREAMED", 105.0, {"polls": 2, "tokens": 3,
                                  "hold_sum_s": 0.004,
                                  "hold_max_s": 0.003}],
        ["d", "STREAMED", 110.0, {"polls": 70, "tokens": 70,
                                  "hold_sum_s": 9.0, "hold_max_s": 1.0}],
    ]}}
EXPECTED = {
    "tick_ms": 50.0,
    "tick_between_ms": 4.0,
    "tick_admit_ms": 0.1 + 1.0 + 0.2,
    "tick_prefill_ms": 5.0,
    "tick_stage_ms": 1.0 + 2.0,
    "tick_wait_ms": 38.0,
    "tick_emit_ms": 2.0 + 0.7,
    # host wall = 5.0 - 3.8 = 1.2 s, of which the thread ran 0.9 s
    "tick_offcpu_pct": 25.0,
    "relay_hold_ms": 2.0,
    "relay_tokens_per_poll": 1.2,
}


def without_tick_row(record):
    record["closed"]["steps"] = [
        row for row in record["closed"]["steps"] if row["kind"] != "tick"]


def tick_row_without_phases(record):
    # StepTimer rows of a program older than the phases
    for mark in (record["opened"], record["closed"]):
        for row in mark["steps"]:
            row.pop("phases", None)
            row.pop("cpu_s", None)


def no_tick_in_window(record):
    record["closed"] = copy.deepcopy(record["opened"])


def no_streamed_event(record):
    record["report"]["events"] = [
        e for e in record["report"]["events"] if e[1] != "STREAMED"]


def reader(name):
    return spec.Cell(ROOT, "serve-chat-closed64").reader(name)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_over_a_hand_made_record(name):
    assert reader(name)(copy.deepcopy(RECORD)) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("strip", [
    without_tick_row, tick_row_without_phases, no_tick_in_window,
    no_streamed_event])
def test_reader_returns_none_when_the_program_has_nothing(name, strip):
    record = copy.deepcopy(RECORD)
    strip(record)
    relay = name.startswith("relay_")
    touched = (strip is no_streamed_event) == relay
    value = reader(name)(record)
    if touched:
        assert value is None
    else:
        assert value == pytest.approx(EXPECTED[name])


def test_every_reader_is_declared_for_both_serve_cells():
    benchmark = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    layers = {m["layer"] for m in benchmark["per_layer"]
              if m["name"] not in EXPECTED}
    declared = {m["name"]: m for m in benchmark["per_layer"]
                if m["name"] in EXPECTED}
    assert sorted(declared) == sorted(EXPECTED)
    for metric in declared.values():
        assert metric["workloads"] == ["serve-chat-closed64",
                                       "serve-docqa-open"]
        assert metric["moves"] == "tpot_p90_ms"
        assert metric["layer"] in layers   # a layer PR 23 already named
