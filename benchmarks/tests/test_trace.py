"""The reduction from a profiler trace to numbers: on a hand-made trace
whose answers are known, and on a cut of a trace recorded on the v5e."""

import glob
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(os.path.dirname(HERE), "fixtures")
MS = 1_000_000


def hand_made():
    ops = [["fusion.1", 0 * MS, 10 * MS], ["paged_attention.3", 10 * MS, 5 * MS],
           ["all-reduce.7", 15 * MS, 4 * MS], ["copy.2", 17 * MS, 6 * MS],
           # 23..40 idle; next tick
           ["fusion.1", 40 * MS, 10 * MS]]
    modules = [["jit_decode_step(123)", 0, 23 * MS],
               ["jit_chunk_prefill(9)", 40 * MS, 10 * MS]]
    host = [["engine_step", 0, 30 * MS], ["dispatch:decode", 24 * MS, 2 * MS],
            ["engine_step", 35 * MS, 15 * MS], ["unrelated", 0, 50 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "thread", "events": host}]}]}


def test_reduction_of_a_hand_made_trace():
    got = trace.reduce(hand_made())
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(0.050)
    assert got["busy_s"] == pytest.approx(0.033)          # 0-23 and 40-50
    # the all-reduce runs 15-19; compute (copy) covers 17-19
    assert got["collective_exposed_s"] == pytest.approx(0.002)
    assert got["programs"]["decode_step"]["calls"] == 1
    assert got["programs"]["decode_step"]["total_s"] == pytest.approx(0.023)
    assert got["ops"]["fusion"] == {"calls": 2,
                                    "total_s": pytest.approx(0.020)}
    assert got["inside"]["decode_step/paged_attention"] \
        == pytest.approx(0.005)
    assert got["inside"]["chunk_prefill/fusion"] == pytest.approx(0.010)
    idle = got["idle"]
    # 23-24 host, 24-26 dispatch, 26-30 host, 30-35 no span, 35-40 host
    assert idle["engine_step/dispatch:decode"] == pytest.approx(0.002)
    assert idle["engine_step/host"] == pytest.approx(0.010)
    assert idle["outside_any_span"] == pytest.approx(0.005)
    assert sum(idle.values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    rows = trace.breakdown(got)
    assert rows["device_ops"][0] == ["decode_step", pytest.approx(0.023)]
    assert len(rows["device_ops"]) <= 10 and len(rows["idle_gaps"]) <= 10


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.clean("jit__threefry_split(77)") == "threefry_split"
    assert trace.clean("%fusion.12 = bf16[2]") == "fusion"
    assert trace.is_collective("all-gather-start")


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIXTURES, "*.json.gz"))) or [None])
def test_reduction_of_a_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace under fixtures/")
    recorded = trace.load(path)
    got = trace.reduce(recorded)
    expected = trace.load(path.replace(".json.gz", ".expected.gz"))
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(expected["busy_s"])
    assert got["window_s"] == pytest.approx(expected["window_s"])
    for name, row in expected["programs"].items():
        assert got["programs"][name]["total_s"] \
            == pytest.approx(row["total_s"])
    assert sum(got["idle"].values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    for kernel in expected["kernels"]:
        assert any(kernel in op for op in got["ops"]), kernel
