"""The files the Nemotron-H cell brings: the byte counts behind its table
and its roofline shares, its readers on a synthetic record, the driver's
refusal of a program that lacks the model, the scope map of a compiled
program's text, and the cell end to end on the CPU (--rehearse: toy
widths, the same control flow, parity against the float32 reference
included)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import costs_nemotron_h as costs
from benchmarks.harness import serve_cell_nemotron_h, spec
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-nemotron3-reason-closed192"
NEW_READERS = ("moe_decode_hbm_roofline_pct", "expert_matmul_roofline_pct",
               "expert_time_pct", "expert_pairs_per_step",
               "expert_load_max_over_mean")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-super-120b-serve.json")) as f:
        return json.load(f)


def test_the_file_keeps_every_published_number(config):
    """Every number of the catalog row's `config` is in the file under the
    same key, but the three numbers in `reduced`, whose published values
    stand under `published`; the pattern is cut with the depth and is
    listed there too."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    differs = {k for k, v in row["config"].items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)
               and config.get(k) != v}
    assert differs | {"hybrid_override_pattern"} == set(config["reduced"])
    assert config["hybrid_override_pattern"] \
        != config["published"]["hybrid_override_pattern"] \
        == row["config"]["hybrid_override_pattern"]
    for key in config["reduced"]:
        assert config["published"][key] == row["config"][key]
    assert config["published"]["hybrid_override_pattern"] \
        == row["config"]["hybrid_override_pattern"]
    period = config["hybrid_override_pattern"]
    assert len(period) == config["num_hidden_layers"] == 11
    # one whole period in the published 5 : 5 : 1
    assert (period.count("M"), period.count("E"), period.count("*")) \
        == (5, 5, 1)
    whole = config["published"]["hybrid_override_pattern"]
    assert (whole.count("M"), whole.count("E"), whole.count("*")) \
        == (40, 40, 8)


def test_parameter_counts_are_the_published_layers(config):
    p = costs.layer_params(config)
    # ISSUE 35, section 2
    assert p["mamba"] == 4096 * 18560 + 8192 * 4096 == 109_576_192
    assert p["attention"] == 35_651_584
    assert p["moe_outside_experts"] \
        == 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 == 54_525_952
    assert p["expert"] == 2 * 1024 * 2688 == 5_505_024
    assert p["lm_head"] == p["embedding"] == 32768 * 4096
    assert costs.kinds(config) == {"mamba": 5, "attention": 1, "moe": 5}
    # one row, one M layer: 128 x 64 x 128 float32 + a 3 x 10240 bf16 window
    assert costs.state_bytes_per_row(config) == 4_194_304 + 61_440
    assert costs.kv_bytes_per_token(config) == 1024


def test_the_table_of_the_cut(config):
    t = costs.table(config)
    giga = lambda key: round(t[key] / 1e9, 3)  # noqa: E731
    assert giga("mamba_layer_bytes") == 0.219
    assert giga("attention_layer_bytes") == 0.071
    assert giga("moe_outside_experts_bytes") == 0.109
    assert giga("held_experts_bytes_per_layer") == 1.409
    assert giga("vocabulary_bytes") == 0.537
    assert round(t["weights_bytes"] / 1e9, 2) == 9.30
    assert round(t["moe_layers_share"], 2) == 0.82
    assert round(t["state_bytes_per_row"] / 1e6, 1) == 21.3
    assert round(t["state_pool_bytes"] / 1e9, 2) == 2.04
    assert round(t["page_pool_bytes"] / 1e9, 2) == 0.15
    assert round(t["staging_bytes_per_row"] / 1e6, 1) == 23.1
    resident = t["weights_bytes"] + t["state_pool_bytes"] \
        + t["page_pool_bytes"]
    assert resident == config["memory_analysis"]["decode_step_batch96"][
        "argument_bytes"] - _small_arguments(config)


def _small_arguments(config):
    """What the decode step takes besides weights and pools: norm scales,
    conv kernels and biases, per-head scalars, the router's bias, the
    counters and the tick's vectors: under 4 MB."""
    resident = sum(costs.table(config)[k] for k in (
        "weights_bytes", "state_pool_bytes", "page_pool_bytes"))
    small = config["memory_analysis"]["decode_step_batch96"][
        "argument_bytes"] - resident
    assert 0 < small < 4e6
    return small


def test_decode_step_bytes_add_up(config):
    rows, context = 96, 96 * 560
    moved = costs.decode_step_bytes(config, rows, context)
    # ISSUE 35, section 5: 9.03 GB of weights that multiply (7.05 the held
    # experts'), 4.09 GB of state, ~0.05 of K/V: 13.2 GB
    assert round((moved["experts"] + moved["dense_weights"]) / 1e9, 2) \
        == 9.03
    assert round(moved["experts"] / 1e9, 2) == 7.05
    assert round(moved["state"] / 1e9, 2) == 4.09
    assert moved["kv"] == context * 1024
    assert moved["total"] == sum(moved[k] for k in (
        "experts", "dense_weights", "state", "kv"))
    assert round(moved["total"] / 1e9, 1) == 13.2
    half = costs.decode_step_bytes(config, rows, context, hit_experts=64)
    assert half["experts"] == moved["experts"] / 2
    assert half["dense_weights"] == moved["dense_weights"]
    one = costs.expert_matmul_bytes(config, 128, 528)
    assert one == 1_409_286_144 + 528 * 4096


def _record(config):
    import numpy as np
    ticks = [(10.0 + i, 10.5 + i, 100, 90 + i % 2, 1, (90 + i % 2) * 640)
             for i in range(8)]
    pairs = lambda scale: (np.arange(5 * 128).reshape(5, 128) % 7  # noqa
                           * scale).tolist()
    steps = lambda n: [[n] * 127 + [n // 2]] * 5  # noqa: E731
    mark = lambda n, scale: {  # noqa: E731
        "stats": {"expert_pairs": pairs(scale), "expert_steps": steps(n),
                  "state_installs": n},
        "steps": [{"kind": "decode", "steps": n, "wall_s": 1.0,
                   "tokens": 1}]}
    by_instruction = {"fusion.1": [20, 0.020], "custom-call.7": [40, 0.040],
                      "fusion.9": [20, 0.010], "copy.3": [20, 0.005]}
    return {
        "config": config, "t0": 10.0, "t1": 18.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"ticks": ticks, "max_batch": 96, "page_size": 16},
        "opened": mark(100, 1), "closed": mark(300, 3),
        "parity": {"moe_instructions": {
            "fusion.1": "jit(decode_step)/M/layer_1/moe/routed/moe/route/dot",
            "custom-call.7":
                "jit(decode_step)/M/layer_1/moe/routed/moe/experts/dot_general",
            "fusion.9": "jit(decode_step)/M/layer_1/moe/moe/shared/dot"}},
        "trace": {"window_s": 4.0, "busy_s": 3.9, "host_began": 12.0,
                  "host_ended": 16.0, "ops": {},
                  "programs": {"jit_decode_step": {"calls": 4,
                                                   "total_s": 0.1}},
                  "decode_step_instructions": {
                      "runs": 4, "total_s": 0.1,
                      "by_instruction": by_instruction}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    read = lambda name: cell.reader(name)(record)  # noqa: E731
    # pairs: (i % 7) * 2 over 640 experts-of-layers, 200 decode steps
    total = sum(i % 7 for i in range(640)) * 2
    assert read("expert_pairs_per_step") == pytest.approx(total / (5 * 200))
    assert read("expert_load_max_over_mean") == pytest.approx(
        6 / (sum(i % 7 for i in range(128)) / 128))
    assert read("expert_time_pct") == pytest.approx(
        100.0 * (0.020 + 0.040 + 0.010) / 0.1)
    hit = (127 * 200 + 100) / 200.0
    moved = costs.expert_matmul_bytes(config, hit, total / (5 * 200))
    assert read("expert_matmul_roofline_pct") == pytest.approx(
        100.0 * (moved / 819e9) / (0.040 / (4 * 5)))
    step = costs.decode_step_bytes(config, 90.5, 90.5 * 640, hit)
    roofline = read("moe_decode_hbm_roofline_pct")
    assert roofline == pytest.approx(
        100.0 * (step["total"] / 819e9) / 0.025)
    assert 0 < roofline <= 100


def test_readers_find_nothing_on_a_program_without_the_counters(config):
    """The parent of the PR that added them, an untraced run: every new
    reader returns None and raises nothing."""
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    for edge in ("opened", "closed"):
        record[edge]["stats"] = {}
    for name in NEW_READERS:
        if name != "expert_time_pct":       # reads the trace alone
            assert cell.reader(name)(record) is None
    record = _record(config)
    record["trace"] = {}
    record["parity"] = {}
    for name in ("moe_decode_hbm_roofline_pct", "expert_time_pct",
                 "expert_matmul_roofline_pct"):
        assert cell.reader(name)(record) is None


def test_scopes_of_a_compiled_programs_text():
    text = '''
  %fusion.12 = bf16[96,4096]{1,0} fusion(%p.1), kind=kLoop, calls=%fc.3, metadata={op_name="jit(decode_step)/jit(main)/M/layer_1/moe/routed/moe/route/dot_general" source_file="x.py" source_line=3}
  ROOT %custom-call.4 = f32[2112,2688]{1,0} custom-call(%a, %b), metadata={op_name="jit(decode_step)/M/layer_1/moe/routed/moe/experts/dot_general"}
  %copy.9 = s32[4]{0} copy(%q)
'''
    scopes = serve_cell_nemotron_h.instruction_scopes(text)
    assert scopes["fusion.12"].endswith("moe/route/dot_general")
    assert "moe/experts" in scopes["custom-call.4"]
    assert "copy.9" not in scopes
    kept = {"by_instruction": {"fusion.12": [2, 0.5],
                               "custom-call.4": [2, 1.5],
                               "copy.9": [2, 9.0]}}
    under = serve_cell_nemotron_h.seconds_under
    assert under(kept, scopes, "moe/") == 2.0
    assert under(kept, scopes, "moe/experts") == 1.5
    assert under(kept, {}, "moe/") is None


def test_instructions_of_a_program_leave_loops_out():
    xplane = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_decode_step(1)", 100, 100], ["jit_chunk_prefill(2)", 300,
                                               50]]},
        {"name": "XLA Ops", "events": [
            ["%while.2 = (s32[]) while(...)", 100, 60],
            ["%fusion.5 = f32[4] fusion(...)", 110, 20],
            ["%fusion.5 = f32[4] fusion(...)", 140, 20],
            ["%copy.1 = f32[4] copy(...)", 310, 10]]}]}]}
    got = serve_cell_nemotron_h.program_instructions(xplane, "decode_step")
    assert got["runs"] == 1 and got["total_s"] == pytest.approx(1e-7)
    assert got["by_instruction"] == {"fusion.5": [2, pytest.approx(4e-8)]}


def test_the_cell_lists_every_metric_it_reports():
    cell = spec.Cell(ROOT, CELL)
    end_to_end = {m["name"] for m in cell.metrics(False)}
    assert end_to_end == {"serve_out_tok_s", "tpot_p90_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.metrics(True)}
    assert set(NEW_READERS) | {
        "paged_attn_roofline_pct", "hbm_peak_gib.serve", "decode_step_ms",
        "prefill_tick_pct", "pool_in_use_pct", "tick_state_ms",
        "device_idle_pct.serve"} <= per_layer
    assert not {"decode_hbm_roofline_pct", "state_bytes_pct"} & per_layer
    for metric in cell.metrics(True):
        cell.reader(metric["name"])    # each has its file
    assert cell.driver() is serve_cell_nemotron_h.run
    assert cell.chips == 1 and cell.traffic["clients"] == 192
    assert cell.config["engine"]["max_batch"] * 2 == cell.traffic["clients"]


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    assert cell.config["requires"] == ["ray_tpu.models.nemotron_h"]
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_nemotron_h.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "4", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 35)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""
    line = json.loads([ln for ln in got.stderr.splitlines()
                       if ln.startswith("bench: rehearsal")][-1]
                      .split(": ", 2)[2])
    assert line["correct"] is True and line["failed"] == 0
    # the CPU has no device trace: the three trace readers return None
    assert {"expert_pairs_per_step", "expert_load_max_over_mean",
            "tick_state_ms", "decode_step_ms", "prefill_tick_pct",
            "pool_in_use_pct"} <= set(line["metrics"])
    assert line["metrics"]["expert_pairs_per_step"]["value"] > 0
