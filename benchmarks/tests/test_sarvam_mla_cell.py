"""The files the Sarvam-105B cell brings: the counts behind its roofline
shares (ISSUE 45's arithmetic from the file's keys), the sessions' request
stream, its readers on a synthetic record and on a record that has nothing
for them (the parent's), the published numbers the configuration file must
keep, the driver's refusal of a program that lacks the model, and the cell
end to end on the CPU (--rehearse: toy widths, first asks in set-up, later
turns in the window, the same control flow, parity against the float32
reference included)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import costs_sarvam_mla, serve_cell_sarvam_mla, spec
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-sarvam-docturns-closed96"
NEW_METRICS = ("mla_attn_roofline_pct", "mla_decode_hbm_roofline_pct",
               "latent_cache_bytes_pct", "latent_share_factor",
               "mla_time_pct", "prefill_ctx_device_ms")
# the source's config.json, as the catalog of public architectures has it
PUBLISHED = {
    "attn_implementation": None, "default_theta": 10000,
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
    "vocab_size": 262144}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sarvam-105b-serve.json")) as f:
        return json.load(f)


def test_the_file_keeps_every_published_number(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(config["reduced"])
    assert config["published"] == {"num_hidden_layers": 32,
                                   "num_experts": 128, "vocab_size": 262144}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 32768)
    assert config["held_experts"] == [0, 16]
    for key in ("source", "deployment", "assumed", "engine",
                "program_settings", "memory_analysis", "builder", "parity",
                "requires"):
        assert config[key], key
    for key in ("use_qk_norm", "scoring", "router_bias", "weights",
                "cached_row_lanes", "num_pages", "prefix_cache_entries"):
        assert key in config["assumed"], key
    # the floors of a cut: a period + 4, 8 experts, an eighth of the rows
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_parameter_counts_are_the_issues(config):
    p = costs_sarvam_mla.layer_params(config)
    # W_q 50.33 M + W_kva 2.36 M + W_kvb 8.39 M + W_o 33.55 M
    assert p["attention"] == 50331648 + 2359296 + 8388608 + 33554432
    assert p["expert"] == 25165824 and p["dense_mlp"] == 201326592
    assert p["moe_outside_experts"] == 524288 + 25165824
    table = costs_sarvam_mla.table(config)
    # 296 + 5 x (120.3 + 402.7) + 268.4 = 3,179 M parameters
    assert table["weights_bytes"] == pytest.approx(6.36e9, rel=2e-3)
    assert table["latent_bytes_per_token"] == 6 * 1152
    assert table["resident_bytes_per_token"] == 6 * 1280
    assert table["pool_bytes"] \
        == config["memory_analysis"]["table"]["pool_bytes"]
    assert config["engine"]["num_pages"] \
        <= config["program_settings"]["prefix_cache_entries"]


def test_a_step_moves_what_the_issue_counted(config):
    """48 rows at a mean context of 17.9k: ~5.9 GB of weights with 15.3
    of 16 experts hit, ~5.9 GB of latent pages counted a row; MLA's
    products 717 GFLOP a step."""
    pages = 48 * 17900 / 64
    moved = costs_sarvam_mla.decode_step_bytes(config, pages, 64, 15.3)
    assert moved["weights"] == pytest.approx(5.9e9, rel=0.03)
    assert moved["cache"] == pytest.approx(5.9e9, rel=0.02)
    call = costs_sarvam_mla.attention_call(config, pages / 1.9,
                                           48 * 17900, 64)
    assert 6 * call["flops"] == pytest.approx(717e9, rel=0.01)
    assert call["bytes"] == pytest.approx(pages / 1.9 * 64 * 1152)
    # 121 FLOP a byte where no page is shared: under the chip's ridge 240
    alone = costs_sarvam_mla.attention_call(config, pages, 48 * 17900, 64)
    assert alone["flops"] / alone["bytes"] == pytest.approx(120.9, rel=0.01)


def test_the_sessions_stream(config):
    from benchmarks.harness import traffic
    cell = spec.Cell(ROOT, CELL)
    t = cell.traffic
    assert traffic.longest(t) <= config["engine"]["max_len"] - 2
    assert (t["clients"], t["cycle"]) == (96, 96)
    assert 2 * config["engine"]["max_batch"] == t["clients"]
    docs = serve_cell_sarvam_mla.documents(t, 5, 32768)
    sizes = [len(d) for d in docs]
    assert len(docs) == 32 and sizes == sorted(sizes)
    assert 8192 <= sizes[0] and sizes[-1] <= 32768
    assert sum(sizes) == pytest.approx(567e3, rel=0.01)
    assert sizes == [len(d) for d in
                     serve_cell_sarvam_mla.documents(t, 2 ** 31 + 9, 32768)]
    assert max(max(d) for d in docs) < 32768
    first = serve_cell_sarvam_mla.first_asks(t, 5, 32768, docs)
    assert [r.index for r in first] == [1_000_000 + s for s in range(32)]
    assert all(r.prompt[:len(d)] == d and r.shared_tokens == 0
               for r, d in zip(first, docs))

    def cycle(seed):
        docs_ = serve_cell_sarvam_mla.documents(t, seed, 32768)
        stream = serve_cell_sarvam_mla.later_turns(t, seed, 32768, docs_)
        return [(r.document_tokens, len(r.prompt) - r.document_tokens,
                 r.max_new) for r, _ in zip(stream, range(96))]

    one = cycle(1)
    # ONE order for every seed; three turns of each session a cycle
    assert one == cycle(2 ** 31 + 7)
    assert sorted(d for d, _, _ in one) == sorted(sizes * 3)
    assert all(64 <= q <= 256 and 64 <= a <= 256 for _, q, a in one)
    a = next(serve_cell_sarvam_mla.later_turns(t, 1, 32768, docs))
    b = next(serve_cell_sarvam_mla.later_turns(t, 2, 32768, docs))
    assert a.prompt[:a.document_tokens] == b.prompt[:b.document_tokens]
    assert a.prompt[a.document_tokens:] != b.prompt[b.document_tokens:]
    assert a.shared_tokens == a.document_tokens


def _record(config):
    """A traced window of 100 decode steps: 48 rows a step holding 13,400
    pages counted a row and 7,000 counted once, 860k cached tokens
    attended; 40 chunks that attended 720k cached rows; 15 of 16 experts
    hit a layer a step."""
    ticks = [(10.0 + 0.02 * i, 860_000, 13_400, 7_000, 48,
              18_000 if i % 5 == 0 else 0, 1 if i % 5 == 0 else 0)
             for i in range(100)]
    stats = lambda scale: {  # noqa: E731
        "latent_pages_rowwise": 13_400 * 100 * scale,
        "latent_pages_distinct": 7_000 * 100 * scale,
        "expert_pairs": [[300 * scale] * 16] * 5,
        "expert_steps": [[94 * scale] * 15 + [90 * scale]] * 5,
        "layer_kinds": ["p"] + ["pc"] * 5}
    steps = lambda n: [{"kind": "decode", "steps": n}]  # noqa: E731
    return {
        "config": config, "t0": 10.0, "t1": 12.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"page_size": 64, "latent_ticks": ticks},
        "opened": {"stats": stats(1), "steps": steps(100)},
        "closed": {"stats": stats(2), "steps": steps(200)},
        "parity": {"mla_instructions": {"fusion.1": "x/mla/q/dot",
                                        "latent_attention.3": "x/mla/attend",
                                        "fusion.9": "x/moe/experts/dot"},
                   "moe_instructions": {"fusion.9": "x/moe/experts/dot"}},
        "trace": {"window_s": 2.0, "busy_s": 1.9, "host_began": 10.0,
                  "host_ended": 12.0,
                  "programs": {"jit_decode_step": {
                      "calls": 100, "total_s": 2.0, "median_ms": 20.0},
                      "jit_chunk_prefill": {
                      "calls": 20, "total_s": 0.6, "median_ms": 30.0}},
                  "ops": {"latent_attention.3": {"calls": 600,
                                                 "total_s": 0.9}},
                  "decode_step_instructions": {
                      "runs": 100, "total_s": 2.0, "by_instruction": {
                          "fusion.1": [100, 0.3],
                          "latent_attention.3": [600, 0.9],
                          "fusion.9": [100, 0.5]}}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    call = costs_sarvam_mla.attention_call(config, 7_000, 860_000, 64)
    kernel = cell.reader("mla_attn_roofline_pct")(record)
    assert kernel == pytest.approx(100.0 * max(
        call["bytes"] / 819e9, call["flops"] / 197e12) / (0.9 / 600))
    assert 0 < kernel <= 100
    hit = (15 * 94 + 90) / (16 * 100) * 16
    moved = costs_sarvam_mla.decode_step_bytes(config, 7_000, 64, hit)
    step = cell.reader("mla_decode_hbm_roofline_pct")(record)
    assert step == pytest.approx(100.0 * (moved["total"] / 819e9) / 0.02)
    assert 0 < step <= 100
    rowwise = costs_sarvam_mla.decode_step_bytes(config, 13_400, 64, hit)
    assert cell.reader("latent_cache_bytes_pct")(record) == pytest.approx(
        100.0 * rowwise["cache"] / rowwise["total"])
    assert cell.reader("latent_share_factor")(record) \
        == pytest.approx(13_400 / 7_000)
    assert cell.reader("mla_time_pct")(record) == pytest.approx(60.0)
    assert cell.reader("expert_time_pct")(record) == pytest.approx(25.0)
    assert cell.reader("prefill_ctx_device_ms")(record) \
        == pytest.approx(600.0 / (20 * 18_000 / 1e3))


def test_readers_find_nothing_on_a_program_without_the_latent_path(config):
    """Another cell's record, the parent's program: every new reader
    returns None and raises nothing."""
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    del record["report"]["latent_ticks"]
    for edge in ("opened", "closed"):
        record[edge]["stats"] = {}
    record["parity"] = {}
    record["trace"]["ops"] = {}
    for name in NEW_METRICS:
        assert cell.reader(name)(record) is None, name
    record["trace"]["programs"] = {}
    del record["trace"]["decode_step_instructions"]
    for name in NEW_METRICS:
        assert cell.reader(name)(record) is None, name
    del record["trace"]
    for name in NEW_METRICS:
        assert cell.reader(name)(record) is None, name


def test_the_cell_lists_every_metric_it_reports():
    cell = spec.Cell(ROOT, CELL)
    assert cell.chips == 1
    end_to_end = {m["name"] for m in cell.metrics(False)}
    assert end_to_end == {"serve_out_tok_s", "tpot_p90_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.metrics(True)}
    assert set(NEW_METRICS) | {
        "gap_p99_ms", "batch_occupancy_pct", "prefill_tick_pct",
        "ttft_p50_ms.closed", "pool_in_use_pct", "prefix_hit_pct",
        "decode_step_ms", "decode_step_device_ms",
        "prefill_chunk_device_ms", "compiles_in_window.serve",
        "device_idle_pct.serve", "hbm_peak_gib.serve", "expert_time_pct",
        "expert_pairs_per_step", "expert_load_max_over_mean", "tick_p99_ms",
        "tick_stall_pct", "tick_stall_unexplained_pct",
        "tick_stage_offcpu_pct", "lookahead_pct",
        "prefill_finish_ms"} == per_layer
    # the dense kernel is not on this model's path
    assert "paged_attn_roofline_pct" not in per_layer
    for metric in cell.metrics(True):
        cell.reader(metric["name"])    # each has its file
    assert cell.driver() is serve_cell_sarvam_mla.run
    for metric in cell.benchmark["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "serve_out_tok_s"


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_sarvam_mla.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "8", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 45)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""
    line = json.loads([ln for ln in got.stderr.splitlines()
                       if ln.startswith("bench: rehearsal")][-1]
                      .split(": ", 2)[2])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["prefix_hit_pct"]["value"] == 100.0
    assert line["metrics"]["latent_share_factor"]["value"] >= 1.0
    said = [ln for ln in got.stderr.splitlines() if "first asks in" in ln]
    assert said and "4 first asks" in said[0]
