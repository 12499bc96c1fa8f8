"""The files the LFM2 cell brings: the counts behind its roofline shares
(ISSUE 56's table from the file's keys), its request stream, its readers on
a synthetic record and on a record that has nothing for them (the
parent's), the published numbers the configuration file must keep, the
driver's refusal of a program that lacks the model, and the cell end to end
on the CPU (--rehearse: toy widths, short and long prompts prefilled in the
window straight into pages, the same control flow, parity against the
float32 reference and its three controls included). Written as "contains":
a later cell may join any list this cell is on."""

import ast
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import costs_lfm2, serve_cell_lfm2, spec
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-lfm2-mixlen-closed128"
NEW_METRICS = ("lfm2_decode_hbm_roofline_pct", "lfm2_chunk_roofline_pct",
               "lfm2_attn_roofline_pct", "conv_time_pct")
LISTED = ("serve_out_tok_s", "tpot_p90_ms", "gap_p99_ms",
          "batch_occupancy_pct", "prefill_tick_pct", "ttft_p50_ms.closed",
          "pool_in_use_pct", "decode_step_ms", "decode_step_device_ms",
          "prefill_chunk_device_ms", "compiles_in_window.serve",
          "device_idle_pct.serve", "hbm_peak_gib.serve", "tick_state_ms",
          "expert_time_pct", "expert_pairs_per_step",
          "expert_load_max_over_mean", "sorted_chunk_pct", "device_dry_pct",
          "dry_prefill_pct", "dry_stage_pct", "dry_between_pct",
          "dry_dispatch_pct", "dry_gap_p99_ms") + NEW_METRICS


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-24b-a2b-serve.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published():
    """The source's config.json, as the catalog of public architectures has
    it (skipped where the catalog is not installed)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog of public architectures here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "LFM2-24B-A2B"][0]


def test_the_file_keeps_every_published_number(config, published):
    changed = {k for k, v in published["config"].items()
               if config.get(k) != v}
    assert changed == {"num_experts", "num_hidden_layers", "layer_types"} \
        == set(config["reduced"])
    assert config["source"] == published["source_url"]
    assert config["published"] == {
        k: published["config"][k] for k in config["reduced"]}
    assert config["held_experts"] == [0, 8] and config["num_experts"] == 8
    # two leading layers and five whole periods of the published pattern
    assert config["layer_types"] == published["config"]["layer_types"][:22]
    assert config["num_hidden_layers"] == 22
    assert config["layer_types"][2:] == ["full_attention", "conv", "conv",
                                         "conv"] * 5
    for key in ("source", "deployment", "assumed", "engine",
                "memory_analysis", "builder", "parity", "requires"):
        assert config[key], key
    for key in ("tied_head", "split_order", "filter", "window", "head_norms",
                "rotary_pairs", "router", "expert_bias", "weights",
                "num_pages", "depth"):
        assert key in config["assumed"], key
    engine = config["engine"]
    assert (engine["max_batch"], engine["page_size"]) == (96, 64)
    assert engine["max_len"] == 8192 + 1024 + 512
    assert engine["num_pages"] % 256 == 0


def test_parameter_counts_are_the_issues(config):
    p = costs_lfm2.layer_params(config)
    assert p["conv"] == 12582912 + 4194304 + 3 * 2048           # 16.78 M
    assert p["attention"] == 4194304 * 2 + 1048576 * 2          # 10.49 M
    assert p["dense_mlp"] == 3 * 2048 * 11776 == 72351744
    assert p["expert"] == 9437184 and p["router"] == 2048 * 64
    assert costs_lfm2.kinds(config) == {"conv": 17, "attention": 5,
                                        "dense": 2, "moe": 20}
    table = costs_lfm2.table(config)
    assert table["weights_params"] == 2129332096                # 2.13 B
    assert table["weights_bytes"] == pytest.approx(4.259e9, rel=1e-4)
    assert table["kv_bytes_per_token"] == 10240
    assert table["pool_bytes"] == pytest.approx(10.905e9, rel=1e-3)
    assert table["window_bytes"] == 96 * 17 * 2 * 2048 * 2
    # the whole depth, as the issue counted it
    whole = dict(config, **dict(config["published"], num_experts=8))
    assert costs_lfm2.kinds(whole) == {"conv": 30, "attention": 10,
                                       "dense": 2, "moe": 38}
    assert costs_lfm2.table(whole)["weights_params"] == 3761333888
    assert {k: int(v) for k, v in table.items()} \
        == config["memory_analysis"]["table"]


def test_a_step_and_a_chunk_move_what_the_issue_counted(config):
    """96 rows holding 213k cached tokens, at the whole depth: the weights
    (7.52 GB, the tied head read as a matrix), 4.36 GB of 64-wide K/V
    pages, 47 MB of windows: 11.9 GB, 14.6 ms at 819 GB/s; a 512-token
    chunk at 2k rows ~1.03 TFLOP, about half of it the conv mixers'. At
    the 22 layers the cell runs: 4.26 + 2.18 GB a step, 0.63 TFLOP a
    chunk."""
    whole = dict(config, **dict(config["published"], num_experts=8))
    moved = costs_lfm2.decode_step_bytes(whole, 213_000, 96)
    assert moved["weights"] == pytest.approx(7.52e9, rel=2e-3)
    assert moved["cache"] == 213_000 * 20480
    assert moved["windows"] == 96 * 30 * 2 * 8192
    assert moved["total"] / 819e9 == pytest.approx(14.6e-3, rel=0.02)
    fewer = costs_lfm2.decode_step_bytes(whole, 213_000, 96,
                                         hit_experts=7.0)
    assert moved["weights"] - fewer["weights"] \
        == pytest.approx(38 * 9437184 * 2)
    chunk = costs_lfm2.chunk(whole, 512, 2000)
    assert chunk["flops"] == pytest.approx(1.03e12, rel=0.02)
    conv = 2 * 512 * 30 * costs_lfm2.layer_params(whole)["conv"]
    assert 0.45 < conv / chunk["flops"] < 0.55
    # bound by its bytes: the weights' read, 8.9 ms against 5.2 of FLOPs
    assert chunk["bytes"] / 819e9 > chunk["flops"] / 197e12
    cut = costs_lfm2.decode_step_bytes(config, 213_000, 96)
    assert cut["weights"] == pytest.approx(4.258e9, rel=2e-3)
    assert cut["cache"] == 213_000 * 10240
    assert costs_lfm2.paged_attention_bytes(config, 213_000) \
        == cut["cache"] / 5
    assert costs_lfm2.chunk(config, 512, 2000)["flops"] \
        == pytest.approx(0.63e12, rel=0.02)


def test_the_request_stream(config):
    from benchmarks.harness import traffic
    cell = spec.Cell(ROOT, CELL)
    t = cell.traffic
    assert t["kind"] == "closed" and not t["sharing"]
    assert traffic.longest(t) <= config["engine"]["max_len"] - 2
    assert (t["clients"], t["cycle"]) == (128, 128)
    assert t["clients"] > config["engine"]["max_batch"]

    def cycle(seed):
        stream = traffic.requests(t, seed, config["vocab_size"])
        return [next(stream) for _ in range(128)]

    one, other = cycle(1), cycle(2 ** 31 + 7)
    sizes = [(len(r.prompt), r.max_new) for r in one]
    # one order of the sizes for every seed; the seed draws the ids
    assert sizes == [(len(r.prompt), r.max_new) for r in other]
    assert one[0].prompt != other[0].prompt
    prompts = sorted(n for n, _ in sizes)
    assert 128 <= prompts[0] and prompts[-1] <= 8192
    assert prompts[64] == pytest.approx(1024, rel=0.05)     # half under 1k
    assert 2800 < prompts[96] < 3000                        # a quarter over
    assert all(256 <= m <= 1024 for _, m in sizes)
    # the schedule's worst case fits the pool without a preemption
    pages = sorted((-(-(n + m) // 64) for n, m in sizes), reverse=True)
    assert sum(pages[:96]) < config["engine"]["num_pages"] - 1


def _record(config):
    """A traced window of 100 decode steps: 96 rows a step holding 213k
    cached tokens; the window's 300 chunks attended 2,000 rows each; 7.9
    of 8 held experts hit a layer a step."""
    ticks = [(10.0 + 0.02 * i, 10.02 + 0.02 * i, 900, 96, 3, 213_000)
             for i in range(100)]
    stats = lambda scale: {  # noqa: E731
        "prefill_chunks": 300 * scale, "prefill_ctx_rows": 600_000 * scale,
        "expert_pairs": [[600 * scale] * 8] * 20,
        "expert_steps": [[99 * scale] * 7 + [97 * scale]] * 20,
        "expert_layers": 20,
        "layer_kinds": ["s", "s"] + ["pc", "sc", "sc", "sc"] * 5}
    steps = lambda n: [{"kind": "decode", "steps": n}]  # noqa: E731
    return {
        "config": config, "t0": 10.0, "t1": 12.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"page_size": 64, "ticks": ticks, "num_pages": 16640,
                   "memory": [{"bytes_limit": 16.9e9}],
                   # the same steps at the rows' own lengths
                   "length_ticks": [(t[0], 96, 210_000) for t in ticks]},
        "opened": {"stats": stats(1), "steps": steps(100)},
        "closed": {"stats": stats(2), "steps": steps(200)},
        "parity": {"decode_instructions": {
            "fusion.1": "x/layer_0/conv/conv/in/dot/",
            "fusion.2": "x/layer_0/conv/conv/filter/mul/",
            "paged_attention.3": "x/layer_2/attn/attn/attend/",
            "fusion.9": "x/layer_2/moe/moe/experts/dot/"},
                   "moe_instructions": {"fusion.9": "x/moe/experts/dot"},
                   "chunk_instructions": {
                       "fusion.7": "x/layer_1/conv/conv/out/dot/",
                       "fusion.8": "x/moe/moe/experts/dot/"}},
        "trace": {"window_s": 2.0, "busy_s": 1.9, "host_began": 10.0,
                  "host_ended": 12.0,
                  "programs": {"jit_decode_step": {
                      "calls": 100, "total_s": 2.0, "median_ms": 20.0},
                      "jit_chunk_prefill": {
                      "calls": 75, "total_s": 1.5, "median_ms": 20.0}},
                  "ops": {"paged_attention.3": {"calls": 1000,
                                                "total_s": 0.8}},
                  "decode_step_instructions": {
                      "runs": 100, "total_s": 2.0, "by_instruction": {
                          "fusion.1": [3000, 0.2], "fusion.2": [3000, 0.1],
                          "paged_attention.3": [1000, 0.8],
                          "fusion.9": [3800, 0.7]}},
                  "chunk_prefill_instructions": {
                      "runs": 75, "total_s": 1.5, "by_instruction": {
                          "fusion.7": [2250, 0.4], "fusion.8": [2850, 0.5]}}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    read = lambda name: cell.reader(name)(record)  # noqa: E731
    hit = (7 * 99 + 97) / (8 * 100)
    bytes_ = costs_lfm2.decode_step_bytes(config, 210_000, 96, 8 * hit)
    assert read("lfm2_decode_hbm_roofline_pct") == pytest.approx(
        100 * bytes_["total"] / 819e9 / 0.020)
    # one call reads a layer's share of the rows' tokens: 0.53 ms of 0.8
    assert read("lfm2_attn_roofline_pct") == pytest.approx(
        100 * 210_000 * 2048 / 819e9 / 0.0008)
    use = serve_cell_lfm2.filled(record)
    assert use["filled_mean_pct"] < use["reserved_pct"] < 100
    need = costs_lfm2.chunk(config, 512, 2000)
    assert read("lfm2_chunk_roofline_pct") == pytest.approx(
        100 * (need["bytes"] / 819e9) / 0.020)
    assert read("conv_time_pct") == pytest.approx(
        100 * (0.2 + 0.1 + 0.4) / (2.0 + 1.5))
    assert read("expert_time_pct") == pytest.approx(100 * 0.7 / 2.0)
    for name in NEW_METRICS:
        assert read(name) < 100


def test_readers_find_nothing_on_a_program_without_the_model(config):
    """The parent's record: no trace of these programs, no counters, no
    scopes. Every new reader returns None and raises nothing."""
    cell = spec.Cell(ROOT, CELL)
    bare = {"config": config, "t0": 0.0, "t1": 1.0,
            "device": {"kind": "TPU v5 lite"},
            "report": {"page_size": 64, "ticks": []},
            "opened": {"stats": {}, "steps": []},
            "closed": {"stats": {}, "steps": []}, "parity": {}}
    traced = dict(bare, trace={"window_s": 1.0, "busy_s": 0.5,
                               "host_began": 0.0, "host_ended": 1.0,
                               "programs": {}, "ops": {}})
    for record in (bare, traced):
        for name in NEW_METRICS:
            assert cell.reader(name)(record) is None, name


def test_the_cell_lists_every_metric_it_reports():
    """Contains, not equals: each list this cell is on names it, and the
    cell's traced line is made of exactly the metrics that list it."""
    cell = spec.Cell(ROOT, CELL)
    by_name = {m["name"]: m for m in cell.benchmark["end_to_end"]
               + cell.benchmark["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW_METRICS:
        assert by_name[name]["moves"] == "serve_out_tok_s"
        assert by_name[name]["unit"] == "%"
    assert {m["name"] for m in cell.metrics(True)} \
        == set(LISTED) - {"serve_out_tok_s", "tpot_p90_ms"}
    assert {"serve_out_tok_s", "tpot_p90_ms", "setup_s"} \
        <= {m["name"] for m in cell.metrics(False)}
    # the six tail readers wait for a benchmark PR's repair (PERF.md 7)
    assert CELL not in by_name["tick_p99_ms"]["workloads"]
    assert cell.entry["chips"] == 1


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_lfm2.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "8", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 56)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""
    said = got.stderr.splitlines()
    line = json.loads([ln for ln in said
                       if ln.startswith("bench: rehearsal")][-1]
                      .split(": ", 2)[2])
    assert line["correct"] is True and line["failed"] == 0
    # every metric the cell lists that a CPU run can produce (the rest
    # read the device's trace or its peaks table)
    for name in ("prefill_tick_pct", "batch_occupancy_pct", "tick_state_ms",
                 "expert_pairs_per_step", "expert_load_max_over_mean",
                 "sorted_chunk_pct", "device_dry_pct", "pool_in_use_pct",
                 "compiles_in_window.serve"):
        assert name in line["metrics"], name
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
    window = [ln for ln in said if "written in place" in ln][-1]
    assert "preemptions 0" in window and "windows installed" in window
    controls = [ln for ln in said if ln.startswith("bench: parity controls ")]
    said_of = lambda name, behind: ast.literal_eval(  # noqa: E731
        controls[-1].split(name + " ", 1)[1].split(behind)[0])
    verdict = said_of("bench: parity controls", "; local ")
    assert verdict["sound_steps"]["ok"] is True
    for control in ("kv_pages_8bit", "windows_8bit", "two_tap_filter",
                    "head_8bit"):
        assert verdict[control]["ok"] is False, control
    # the timed programs ran, several rows live, and are tied to the check's
    timed = said_of("; timed", "; failed ")
    assert timed["decode_agree"] >= 0.9 > timed["mismatched_decode_agree"]
    assert timed["chunk_median"] <= 0.05 < timed["mismatched_chunk_median"]
    counters = said_of("; counters", "; timed ")
    assert counters["pairs_off"] == 0 and counters["steps_off"] == 0
    assert said_of("; failed", "\n") == []
