"""The files the Keye-VL-2.0 cell brings: the counts behind its roofline
shares (ISSUE 49's arithmetic from the file's keys), the sessions' request
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

from benchmarks.harness import costs_keye_dsa, serve_cell_keye_dsa, spec
from benchmarks.harness import serve_cell_sarvam_mla as sessions
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-keye-longdoc-closed96"
NEW_METRICS = ("dsa_decode_hbm_roofline_pct", "dsa_index_roofline_pct",
               "dsa_attend_roofline_pct", "dsa_select_time_pct",
               "dsa_time_pct", "dsa_cache_bytes_pct", "dsa_selected_pct")
# the source's config.json, as the catalog of public architectures has it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b-serve.json")) as f:
        return json.load(f)


def test_the_file_keeps_every_published_number(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert changed == {"num_hidden_layers", "num_experts"} \
        == set(config["reduced"])
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128}
    assert (config["num_hidden_layers"], config["num_experts"]) == (6, 16)
    assert config["held_experts"] == [0, 16]
    assert "VISION TOWER IS NOT BUILT" in config["deployment"]
    # every reading the config does not settle names its one place
    for key in ("qk_norm", "indexer_rotary", "index_scale", "ties",
                "chunk_sizes", "rotary_pairs", "mrope", "routing"):
        assert "keye_dsa_ref.py::" in config["assumed"][key], key
    assert config["requires"] == ["ray_tpu.models.keye_dsa",
                                  "ray_tpu.ops.sparse_attention"]


def test_parameter_counts_are_the_issues(config):
    p = costs_keye_dsa.layer_params(config)
    assert p["attention"] == 2048 * 4096 * 2 + 2048 * 512 * 2
    assert p["indexer"] == 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert (p["router"], p["expert"]) == (2048 * 128, 3 * 2048 * 768)
    table = costs_keye_dsa.table(config)
    assert table["weights_params"] == pytest.approx(1.20e9, rel=0.005)
    assert table["weights_bytes"] == pytest.approx(2.41e9, rel=0.005)
    assert table["cache_bytes_per_token"] == 6 * (2048 + 128)
    assert table["resident_bytes_per_token"] == 6 * (2048 + 256) == 13824
    assert table["pool_bytes"] == 13312 * 64 * 13824
    assert table == {k: pytest.approx(v) for k, v in
                     config["memory_analysis"]["table"].items()}
    # the fullest device holds over 60 % of the chip
    step = config["memory_analysis"]["decode_step_batch48"]
    assert step["argument_bytes"] + step["temp_bytes"] > 0.6 * 16.9e9


def test_a_step_moves_what_the_issue_counted(config):
    """48 rows at a mean context of 36k: index keys 1.33 GB, selected K/V
    1.21 GB, held experts 0.91 GB, other layer weights 0.26 GB, head 0.62:
    the sparse path ~59 % of ~4.3 GB."""
    moved = costs_keye_dsa.decode_step_bytes(config, 48 * 36e3, 48 * 2048,
                                             48)
    assert moved["index"] == pytest.approx(1.33e9, rel=0.01)
    assert moved["selected"] == pytest.approx(1.21e9, rel=0.01)
    assert moved["weights"] == pytest.approx(0.91e9 + 0.26e9 + 0.62e9,
                                             rel=0.01)
    assert moved["cache"] / moved["total"] == pytest.approx(0.59, abs=0.01)
    hit = costs_keye_dsa.step_weight_bytes(config, 15.0)
    assert costs_keye_dsa.step_weight_bytes(config) - hit \
        == 6 * 2 * 3 * 2048 * 768
    # the scoring is bound by the keys' bytes (17 FLOP a byte), the
    # gather-and-attend by the rows' (8 FLOP a byte)
    index = costs_keye_dsa.index_call(config, 48 * 36e3, 48)
    assert index["flops"] / index["bytes"] < 20
    attend = costs_keye_dsa.attend_call(config, 48 * 2048)
    assert attend["flops"] / attend["bytes"] == pytest.approx(8.0)


def test_the_sessions_stream(config):
    from benchmarks.harness import traffic
    cell = spec.Cell(ROOT, CELL)
    t = cell.traffic
    assert traffic.longest(t) <= config["engine"]["max_len"] - 2
    assert (t["clients"], t["cycle"], t["schedule_seed"]) == (96, 96, 49)
    assert 2 * config["engine"]["max_batch"] == t["clients"]
    assert t["sharing"]["ask_offsets"] == [0, 16, 32, 48, 64, 80]
    vocab = config["vocab_size"]
    docs = sessions.documents(t, 5, vocab)
    sizes = [len(d) for d in docs]
    assert len(docs) == 16 and sizes == sorted(sizes)
    assert 16384 <= sizes[0] < 17200 and 62000 < sizes[-1] <= 65536
    assert sum(sizes) == pytest.approx(567e3, rel=0.01)
    assert sum(-(-n // 64) for n in sizes) == pytest.approx(8870, abs=16)
    assert sizes == [len(d) for d in
                     sessions.documents(t, 2 ** 31 + 9, vocab)]
    assert 32768 < max(max(d) for d in docs) < vocab

    def cycle(seed):
        docs_ = sessions.documents(t, seed, vocab)
        stream = sessions.later_turns(t, seed, vocab, docs_)
        return [(r.document_tokens, len(r.prompt) - r.document_tokens,
                 r.max_new) for r, _ in zip(stream, range(96))]

    one = cycle(1)
    # ONE order for every seed; six turns of each session a cycle
    assert one == cycle(2 ** 31 + 7)
    assert sorted(d for d, _, _ in one) == sorted(sizes * 6)
    assert all(64 <= q <= 256 and 64 <= a <= 256 for _, q, a in one)


def _record(config):
    """A traced window of 100 decode steps: 48 rows a step scoring 1.7 M
    index keys and selecting 98,304 tokens; 15 of 16 experts hit a layer a
    step; the step's 40 ms split 12 index / 10 select / 8 attend / 6
    experts."""
    ticks = [(10.0 + 0.04 * i, 1_700_000, 26_600, 8_900, 98_304, 1_700_000,
              48, 0, 0) for i in range(100)]
    stats = lambda scale: {  # noqa: E731
        "sparse_rows_selected": 98_304 * 100 * scale,
        "sparse_rows_context": 1_700_000 * 100 * scale,
        "expert_pairs": [[300 * scale] * 16] * 6,
        "expert_steps": [[94 * scale] * 15 + [90 * scale]] * 6,
        "layer_kinds": ["pc"] * 6}
    steps = lambda n: [{"kind": "decode", "steps": n}]  # noqa: E731
    scopes = {"fusion.1": "x/dsa/index/dot", "while.2": "x/dsa/select/w",
              "fusion.3": "x/dsa/select/cmp", "gather.4": "x/dsa/attend/g",
              "fusion.9": "x/moe/experts/dot", "fusion.7": "x/attn/qkv/dot"}
    return {
        "config": config, "t0": 10.0, "t1": 14.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"page_size": 64, "dsa_ticks": ticks},
        "opened": {"stats": stats(1), "steps": steps(100)},
        "closed": {"stats": stats(2), "steps": steps(200)},
        "parity": {"dsa_instructions": scopes,
                   "moe_instructions": {"fusion.9": "x/moe/experts/dot"}},
        "trace": {"window_s": 4.0, "busy_s": 3.9, "host_began": 10.0,
                  "host_ended": 14.0,
                  "programs": {"jit_decode_step": {
                      "calls": 100, "total_s": 4.0, "median_ms": 40.0}},
                  "ops": {},
                  "decode_step_instructions": {
                      "runs": 100, "total_s": 4.0, "by_instruction": {
                          "fusion.1": [600, 1.2], "fusion.3": [19200, 1.0],
                          "gather.4": [1200, 0.8], "fusion.9": [600, 0.6],
                          "fusion.7": [600, 0.2]}}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    hit = (15 * 94 + 90) / (16 * 100) * 16
    moved = costs_keye_dsa.decode_step_bytes(config, 1.7e6, 98_304, 48, hit)
    step = cell.reader("dsa_decode_hbm_roofline_pct")(record)
    assert step == pytest.approx(100.0 * (moved["total"] / 819e9) / 0.04)
    assert 0 < step <= 100
    index = costs_keye_dsa.index_call(config, 1.7e6, 48)
    got = cell.reader("dsa_index_roofline_pct")(record)
    assert got == pytest.approx(
        100.0 * (index["bytes"] / 819e9) / (1.2 / 600))
    assert 0 < got <= 100
    attend = costs_keye_dsa.attend_call(config, 98_304)
    got = cell.reader("dsa_attend_roofline_pct")(record)
    assert got == pytest.approx(
        100.0 * (attend["bytes"] / 819e9) / (0.8 / 600))
    assert 0 < got <= 100
    assert cell.reader("dsa_select_time_pct")(record) == pytest.approx(25.0)
    assert cell.reader("dsa_time_pct")(record) == pytest.approx(75.0)
    assert cell.reader("dsa_cache_bytes_pct")(record) == pytest.approx(
        100.0 * moved["cache"] / moved["total"])
    assert cell.reader("dsa_selected_pct")(record) == pytest.approx(
        100.0 * 98_304 / 1.7e6)
    assert cell.reader("expert_time_pct")(record) == pytest.approx(15.0)


def test_readers_find_nothing_on_a_program_without_the_sparse_path(config):
    """Another cell's record, the parent's program: every new reader
    returns None and raises nothing."""
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    del record["report"]["dsa_ticks"]
    for edge in ("opened", "closed"):
        record[edge]["stats"] = {}
    record["parity"] = {}
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
    for metric in cell.metrics(True):
        cell.reader(metric["name"])    # each has its file
    assert cell.driver() is serve_cell_keye_dsa.run
    for metric in cell.benchmark["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "serve_out_tok_s"
    assert len(cell.benchmark["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in cell.benchmark["workloads"]) == 1


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_keye_dsa.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "8", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 49)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""
    line = json.loads([ln for ln in got.stderr.splitlines()
                       if ln.startswith("bench: rehearsal")][-1]
                      .split(": ", 2)[2])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["prefix_hit_pct"]["value"] == 100.0
    assert 0 < line["metrics"]["dsa_selected_pct"]["value"] < 100.0
    assert 0 < line["metrics"]["dsa_cache_bytes_pct"]["value"] < 100.0
    said = [ln for ln in got.stderr.splitlines() if "first asks in" in ln]
    assert said and "4 first asks" in said[0]
