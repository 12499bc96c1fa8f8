"""The files the Falcon-H1 cell brings: the byte counts behind its
roofline share, its readers on a synthetic record, the driver shim's
refusal of a program that lacks the model, and the cell end to end on the
CPU (--rehearse: toy widths, the same control flow, parity against the
float32 reference included)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import costs_hybrid, serve_cell_by_config, spec
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-falconh1-chat-closed128"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b-serve.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_published_layer(config):
    p = costs_hybrid.matmul_params(config)
    # ISSUE 32: attention 31.5 M, mixer 68.3 M, MLP 330.3 M, head 1.337 B
    assert p["attention"] == 31_457_280
    assert p["mixer"] == 5120 * 9248 + 4096 * 5120 == 68_321_280
    assert p["mlp"] == 330_301_440
    assert p["lm_head"] == 261_120 * 5120
    # one row, one layer: 32 x 128 x 256 float32 + a 3 x 5120 bf16 window
    assert costs_hybrid.state_bytes_per_row(config) == 4_194_304 + 30_720


def test_decode_tick_bytes_add_up(config):
    rows, context = 48, 48 * 704
    moved = costs_hybrid.decode_tick_bytes(config, rows, context)
    layers = config["num_hidden_layers"]
    assert moved["weights"] == 2 * (layers * 430_080_000 + 1_336_934_400)
    assert moved["state"] == 2 * rows * layers * 4_225_024
    assert moved["kv"] == context * layers * 2 * 4 * 128 * 2
    assert moved["total"] == sum(moved[k] for k in ("weights", "state",
                                                    "kv"))
    # the state's bytes grow with rows and not with context
    more = costs_hybrid.decode_tick_bytes(config, rows, 2 * context)
    assert more["state"] == moved["state"] and more["kv"] == 2 * moved["kv"]
    bf16 = costs_hybrid.decode_tick_bytes(
        dict(config, state_dtype="bfloat16"), rows, context)
    assert bf16["state"] < 0.51 * moved["state"]


def _record(config):
    ticks = [(10.0 + i, 10.5 + i, 100, 40 + i % 2, 1, (40 + i % 2) * 640)
             for i in range(8)]
    stats = {"state_installs": 3}
    phases = {"state": 0.5, "admit": 1.0}
    step = lambda n, scale: [{"kind": "tick", "steps": n,  # noqa: E731
                              "wall_s": 9.0 * scale, "cpu_s": 1.0 * scale,
                              "phases": {k: v * scale
                                         for k, v in phases.items()}}]
    return {
        "config": config, "t0": 10.0, "t1": 18.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"ticks": ticks, "max_batch": 48, "page_size": 16},
        "opened": {"stats": dict(stats), "steps": step(10, 1.0)},
        "closed": {"stats": dict(stats, state_installs=9),
                   "steps": step(110, 3.0)},
        "trace": {"window_s": 4.0, "busy_s": 3.0, "host_began": 12.0,
                  "host_ended": 16.0, "ops": {},
                  "programs": {"jit_decode_step": {"calls": 4,
                                                   "total_s": 0.1}}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    moved = costs_hybrid.decode_tick_bytes(config, 40.5, 40.5 * 640)
    roofline = cell.reader("decode_hbm_roofline_pct")(record)
    assert roofline == pytest.approx(
        100.0 * (moved["total"] / 819e9) / 0.025)
    assert 0 < roofline <= 100
    assert cell.reader("state_bytes_pct")(record) == pytest.approx(
        100.0 * moved["state"] / moved["total"])
    assert cell.reader("tick_state_ms")(record) == pytest.approx(
        1.0 / 100 * 1e3)


def test_readers_find_nothing_on_a_program_without_the_state(config):
    """The parent of the PR that added them, a dense cell's record: every
    new reader returns None and raises nothing."""
    cell = spec.Cell(ROOT, CELL)
    record = _record({k: v for k, v in config.items()
                      if not k.startswith("mamba")})
    for edge in ("opened", "closed"):
        record[edge]["stats"] = {}
    record["trace"]["programs"] = {}
    for name in ("decode_hbm_roofline_pct", "state_bytes_pct",
                 "tick_state_ms"):
        assert cell.reader(name)(record) is None


def test_the_cell_lists_every_metric_it_reports():
    cell = spec.Cell(ROOT, CELL)
    end_to_end = {m["name"] for m in cell.metrics(False)}
    assert end_to_end == {"serve_out_tok_s", "tpot_p90_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.metrics(True)}
    assert {"decode_hbm_roofline_pct", "state_bytes_pct", "tick_state_ms",
            "paged_attn_roofline_pct", "hbm_peak_gib.serve",
            "decode_step_ms", "prefill_tick_pct",
            "pool_in_use_pct"} <= per_layer
    for metric in cell.metrics(True):
        cell.reader(metric["name"])    # each has its file
    assert cell.driver() is serve_cell_by_config.run


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    assert serve_cell_by_config.missing_modules(cell.config) \
        == ["ray_tpu.models.no_such_model"]
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_by_config.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_rehearsal_runs_the_hybrid_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "4", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 12)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""
    line = json.loads([ln for ln in got.stderr.splitlines()
                       if ln.startswith("bench: rehearsal")][-1]
                      .split(": ", 2)[2])
    assert line["correct"] is True and line["failed"] == 0
    assert {"state_bytes_pct", "tick_state_ms", "decode_step_ms",
            "prefill_tick_pct", "pool_in_use_pct"} <= set(line["metrics"])
