"""The files the SDAR cell brings: the counts behind its roofline shares (the
issue's table from the file's keys), its request stream with a request's own
denoising steps, its readers on a synthetic record and on a record that has
nothing for them (the parent's), the published numbers the configuration
file must keep, the driver's refusal of a program that lacks the model, and
the cell end to end on the CPU (--rehearse: toy widths, prompts prefilled in
the window straight into pages under the block mask, the same control flow,
parity against the float32 reference and its controls included). Written as
"contains": a later cell may join any list this cell is on."""

import ast
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import costs_sdar, serve_cell_sdar, spec
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-sdar-fixedgen-closed160"
NEW_METRICS = ("tokens_per_forward", "commit_forward_pct", "unmask_time_pct",
               "sdar_attn_roofline_pct", "sdar_decode_hbm_roofline_pct",
               "sdar_chunk_roofline_pct", "sdar_expert_roofline_pct")
# the tick's tail, the stalls' and the lookahead's readers: what names the
# phase of a long visit (tick_ms and its three phases are pinned to two
# cells by equality in test_tick_readers.py: the cell says them on stderr)
TICK_READERS = ("tick_p99_ms", "tick_stall_pct",
                "tick_stall_unexplained_pct", "tick_stage_offcpu_pct",
                "lookahead_pct", "prefill_finish_ms", "queue_wait_p90_ms")
LISTED = ("serve_out_tok_s", "tpot_p90_ms", "gap_p99_ms",
          "batch_occupancy_pct", "prefill_tick_pct", "ttft_p50_ms.closed",
          "pool_in_use_pct", "decode_step_ms", "decode_step_device_ms",
          "prefill_chunk_device_ms", "compiles_in_window.serve",
          "device_idle_pct.serve", "hbm_peak_gib.serve", "expert_time_pct",
          "expert_pairs_per_step", "expert_load_max_over_mean",
          "sorted_chunk_pct", "device_dry_pct", "dry_prefill_pct",
          "dry_stage_pct", "dry_between_pct", "dry_dispatch_pct",
          "dry_gap_p99_ms") + TICK_READERS + NEW_METRICS


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-chat-serve.json")) as f:
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
    return [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"][0]


def test_the_file_keeps_every_published_number(config, published):
    changed = {k for k, v in published["config"].items()
               if config.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(config["reduced"])
    assert config["source"] == published["source_url"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6 and 48 % 6 == 0
    # every expert held, the whole vocabulary
    assert config["held_experts"] == [0, 128] and config["num_experts"] == 128
    assert config["vocab_size"] == 151936
    assert (config["block_length"], config["mask_token_id"]) == (4, 151669)
    for key in ("source", "deployment", "assumed", "engine",
                "memory_analysis", "builder", "parity", "requires"):
        assert config[key], key
    for key in ("block_length", "mask_token_id", "no_shift", "head_norms",
                "block_causal_prefill", "static_rule", "dynamic_rule",
                "commit_forward", "candidates", "last_block", "weights",
                "num_pages", "depth"):
        assert key in config["assumed"], key
    engine = config["engine"]
    assert (engine["max_batch"], engine["page_size"]) == (128, 64)
    assert engine["max_len"] == 2048 + 512 + 512
    assert engine["num_pages"] % 256 == 0
    # 128 rows x 4 positions are the routed experts' sorted form
    from ray_tpu.models import moe
    assert engine["max_batch"] * config["block_length"] \
        == moe.SORTED_FROM_TOKENS
    assert moe.sorted_form(512, 2048, 768) and not moe.sorted_form(
        384, 2048, 768)


def test_parameter_counts_are_the_issues(config):
    p = costs_sdar.layer_params(config)
    assert p["attention"] == 2 * 2048 * 4096 + 2 * 2048 * 512   # 18.87 M
    assert p["router"] == 2048 * 128
    assert p["expert"] == 3 * 2048 * 768
    table = costs_sdar.table(config)
    assert table["held_experts_params_per_layer"] == 603979776  # 603.98 M
    assert table["layer_params"] == pytest.approx(623.1e6, rel=1e-3)
    assert table["embedding_params"] + table["head_params"] \
        == 2 * 151936 * 2048                                    # 622.3 M
    assert table["weights_params"] == 4361055744
    assert table["weights_bytes"] == pytest.approx(8.72e9, rel=1e-3)
    assert table["kv_bytes_per_token"] == 12288                 # 12 KB
    assert table["page_bytes"] == 64 * 12288
    assert {k: int(v) for k, v in table.items()} \
        == config["memory_analysis"]["table"]
    # the whole depth: 30.5 B parameters, 61 GB in bf16
    whole = costs_sdar.table(dict(config, num_hidden_layers=48))
    assert whole["weights_params"] == pytest.approx(30.5e9, rel=5e-3)
    assert whole["weights_bytes"] == pytest.approx(61e9, rel=5e-3)


def test_a_step_and_a_chunk_move_what_the_issue_counted(config):
    """128 rows holding 140k committed tokens: the weights of six layers
    and the head (8.1 GB: no embedding), 1.7 GB of K/V, 0.16 GB of logits:
    about 10 GB, 12 ms at 819 GB/s; a 512-token chunk at 1,000 rows reads
    the weights without the head (7.5 GB, 9 ms) against 0.39 TFLOP (2.0
    ms)."""
    moved = costs_sdar.block_step_bytes(config, 140_000, 128)
    assert moved["weights"] == pytest.approx(8.10e9, rel=2e-3)
    assert moved["cache"] == 6 * (140_000 + 2 * 512) * 2048
    assert moved["logits"] == 512 * 151936 * 2
    assert 11e-3 < moved["total"] / 819e9 < 13e-3
    fewer = costs_sdar.block_step_bytes(config, 140_000, 128,
                                        hit_experts=127.0)
    assert moved["weights"] - fewer["weights"] \
        == pytest.approx(6 * 3 * 2048 * 768 * 2)
    assert costs_sdar.paged_attention_bytes(config, 140_000, 128) \
        == (140_000 + 512) * 2048
    chunk = costs_sdar.chunk(config, 512, 1000)
    assert chunk["weights"] == pytest.approx(7.48e9, rel=2e-3)
    assert chunk["flops"] == pytest.approx(0.387e12, rel=0.02)
    assert chunk["bytes"] / 819e9 > chunk["flops"] / 197e12


def test_the_request_stream(config):
    from benchmarks.harness import traffic
    cell = spec.Cell(ROOT, CELL)
    t = cell.traffic
    assert t["kind"] == "closed" and not t["sharing"]
    assert traffic.longest(t) <= config["engine"]["max_len"] - 2
    assert (t["clients"], t["cycle"]) == (160, 160)
    assert t["clients"] > config["engine"]["max_batch"]
    mask_id = config["mask_token_id"]

    def cycle(seed):
        stream = serve_cell_sdar.with_steps(
            traffic.requests(t, seed, config["vocab_size"]), t, mask_id)
        return [next(stream) for _ in range(320)]

    one, other = cycle(1), cycle(2 ** 31 + 7)
    sizes = [(len(r.prompt), r.max_new, r.extra["denoising_steps"])
             for r in one]
    # one order of the (prompt, steps) draws for every seed; the seed
    # draws the ids
    assert sizes == [(len(r.prompt), r.max_new, r.extra["denoising_steps"])
                     for r in other]
    assert one[0].prompt != other[0].prompt
    assert all(mask_id not in r.prompt for r in one + other)
    prompts = sorted(n for n, _, _ in sizes[:160])
    assert 256 <= prompts[0] and prompts[-1] <= 2048
    assert prompts[80] == pytest.approx(724, rel=0.05)      # half under 724
    assert all(m == 512 for _, m, _ in sizes)
    steps = [s for _, _, s in sizes[:160]]
    assert sorted(set(steps)) == [1, 2, 4]
    assert all(52 <= steps.count(s) <= 54 for s in (1, 2, 4))
    assert [s for _, _, s in sizes[160:]] != steps          # a new deal
    assert all(r.extra["remasking"] == "static" for r in one)
    # the worst case fits the pool without a preemption
    assert 128 * -(-(2048 + 512) // 64) < config["engine"]["num_pages"] - 1
    # 4 tokens a block over 2, 3 or 5 forwards: 1.2 tokens a row-forward
    forwards = sum(128 * (s + 1) for s in steps)
    assert 160 * 512 / forwards == pytest.approx(1.2, rel=0.02)


def test_never_the_mask():
    assert serve_cell_sdar.never_the_mask([1, 9, 9, 3], 9) == [1, 8, 8, 3]


def _record(config):
    """A traced window of 100 block steps: 128 rows a step holding 140k
    committed tokens; the window's 50 chunks attended 1,000 rows each; 127.5
    of 128 experts hit a layer a step; 12,800 row-forwards of which 3,840
    were commits handed out 15,360 tokens; 40 chunks of the largest bucket
    hit 96 of 128 experts a layer each."""
    ticks = [(10.0 + 0.02 * i, 10.02 + 0.02 * i, 900, 128, 3, 144_000)
             for i in range(100)]
    stats = lambda scale: {  # noqa: E731
        "prefill_chunks": 50 * scale, "prefill_ctx_rows": 50_000 * scale,
        "block_forwards": 12_800 * scale, "commit_forwards": 3_840 * scale,
        "block_tokens_out": 15_360 * scale, "blocks_early": 0,
        "prefill_chunks_largest": 40 * scale,
        "chunk_expert_pairs": [[1280 * scale] * 128] * 6,
        "chunk_expert_steps": [[40 * scale] * 96 + [0] * 32] * 6,
        "expert_pairs": [[3200 * scale] * 128] * 6,
        "expert_steps": [[100 * scale] * 64 + [99 * scale] * 64] * 6,
        "layer_kinds": ["pc"] * 6}
    steps = lambda n: [{"kind": "decode", "steps": n}]  # noqa: E731
    return {
        "config": config, "t0": 10.0, "t1": 12.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"page_size": 64, "ticks": ticks, "num_pages": 6656,
                   "memory": [{"bytes_limit": 16.9e9}],
                   "length_ticks": [(t[0], 128, 140_000) for t in ticks]},
        "opened": {"stats": stats(1), "steps": steps(100)},
        "closed": {"stats": stats(2), "steps": steps(200)},
        "parity": {"decode_instructions": {
            "paged_attention.3": "x/layer_2/attn/sdar/attend/",
            "fusion.4": "x/sdar/confidence/reduce_max/",
            "fusion.5": "x/sdar/unmask/select_n/",
            "fusion.9": "x/layer_2/moe/moe/experts/dot/"},
                   "moe_instructions": {"fusion.9": "x/moe/experts/dot"}},
        "trace": {"window_s": 2.0, "busy_s": 1.9, "host_began": 10.0,
                  "host_ended": 12.0,
                  "programs": {"jit_decode_step": {
                      "calls": 100, "total_s": 1.5, "median_ms": 15.0},
                      "jit_chunk_prefill": {
                      "calls": 25, "total_s": 0.3, "median_ms": 12.0}},
                  "ops": {"paged_attention.3": {"calls": 600,
                                                "total_s": 0.3}},
                  "decode_step_instructions": {
                      "runs": 100, "total_s": 1.5, "by_instruction": {
                          "paged_attention.3": [600, 0.3],
                          "fusion.4": [100, 0.10], "fusion.5": [100, 0.02],
                          "fusion.9": [600, 1.0]}},
                  "chunk_prefill_instructions": {
                      "runs": 25, "total_s": 0.3, "by_instruction": {}}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    read = lambda name: cell.reader(name)(record)  # noqa: E731
    assert read("tokens_per_forward") == pytest.approx(1.2)
    assert read("commit_forward_pct") == pytest.approx(30.0)
    assert read("unmask_time_pct") == pytest.approx(100 * 0.12 / 1.5)
    assert read("expert_time_pct") == pytest.approx(100 * 1.0 / 1.5)
    hit = (64 * 100 + 64 * 99) / (128 * 100)
    bytes_ = costs_sdar.block_step_bytes(config, 140_000, 128, 128 * hit)
    assert read("sdar_decode_hbm_roofline_pct") == pytest.approx(
        100 * bytes_["total"] / 819e9 / 0.015)
    # one call reads a layer's share of the rows' tokens and blocks
    assert read("sdar_attn_roofline_pct") == pytest.approx(
        100 * (140_000 + 512) * 2048 / 819e9 / 0.0005)
    # the chunk by ITS OWN counters' hit experts, not the block steps'
    assert serve_cell_sdar.chunk_hit_experts(record) == pytest.approx(96.0)
    need = costs_sdar.chunk(config, 512, 1000, 96.0)
    assert read("sdar_chunk_roofline_pct") == pytest.approx(
        100 * (need["bytes"] / 819e9) / 0.012)
    # a layer's grouped products: 1.0 s under moe/experts over 100 steps of
    # six layers
    moved = costs_sdar.expert_layer_bytes(config, 128 * hit, 128 * 32.0)
    assert moved == 2 * (128 * hit * 3 * 2048 * 768 + 2 * 4096 * 2048)
    assert read("sdar_expert_roofline_pct") == pytest.approx(
        100 * (moved / 819e9) / (1.0 / 600))
    use = serve_cell_sdar.filled_by_table(record)
    assert use["filled_mean_pct"] < use["reserved_pct"] < 100
    for name in NEW_METRICS[2:]:
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
        assert by_name[name]["workloads"] == [CELL]
    assert {m["name"] for m in cell.metrics(True)} \
        == set(LISTED) - {"serve_out_tok_s", "tpot_p90_ms"}
    assert {"serve_out_tok_s", "tpot_p90_ms", "setup_s"} \
        <= {m["name"] for m in cell.metrics(False)}
    # a row of this model carries no recurrent state
    assert CELL not in by_name["tick_state_ms"]["workloads"]
    assert cell.entry["chips"] == 1
    assert len(cell.benchmark["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in cell.benchmark["workloads"]) == 1


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_sdar.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "8", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 58)],
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
    for name in ("prefill_tick_pct", "batch_occupancy_pct",
                 "expert_pairs_per_step", "expert_load_max_over_mean",
                 "sorted_chunk_pct", "device_dry_pct", "pool_in_use_pct",
                 "compiles_in_window.serve", "tokens_per_forward",
                 "commit_forward_pct"):
        assert name in line["metrics"], name
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
    # 12 tokens a request: three blocks, or four behind a prompt's tail
    assert 0.9 < line["metrics"]["tokens_per_forward"]["value"] < 1.4
    window = [ln for ln in said if "the block steps" in ln][-1]
    assert "preemptions 0" in window and "discarded 0" in window
    verdict = [ln for ln in said if ln.startswith("bench: parity logits ")][-1]
    said_of = lambda name, behind: ast.literal_eval(  # noqa: E731
        verdict.split(name + " ", 1)[1].split(behind)[0])
    assert said_of("bench: parity logits", "; rule ")["ok"] is True
    assert said_of("; rule", "; router ")["off"] == []
    assert said_of("; router", "; timed ")["agree"] >= 0.999
    assert said_of("; timed", "; controls ")["agree"] >= 0.9
    controls = said_of("; controls", "; controls that passed ")
    for control in ("bf16_reference", "reference_8bit", "causal_inside",
                    "commit_left_out", "timed_count_less_one"):
        assert controls[control]["ok"] is False, control
        assert controls[control]["forwards"] > 0
    assert controls["bf16_reference"]["judged"] is False
    timed = said_of("; timed", "; controls ")
    assert timed["commits"] > 0 and timed["ok"] is True
    assert all(w["places"] > 0 and w["median"] < 1e-3 < 0.5 < w["mismatched"]
               for w in timed["wrote"].values())
    assert said_of("; controls that passed", ";") == []
