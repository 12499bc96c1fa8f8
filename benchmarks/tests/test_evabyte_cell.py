"""The files the EvaByte cell brings: the byte counts behind its roofline
shares (ISSUE 42's table from the file's keys), its readers on a synthetic
record and on a record that has nothing for them, the published numbers
the configuration file must keep, the driver shim's refusal of a program
that lacks the model, and the cell end to end on the CPU (--rehearse: toy
widths, a toy window that closes in prefill and in decode, the same
control flow, parity against the float32 reference included)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import costs_evabyte, serve_cell_evabyte, spec
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-evabyte-doc-closed80"
NEW_METRICS = ("eva_decode_hbm_roofline_pct", "eva_cache_bytes_pct",
               "summary_rows_pct", "compress_device_ms", "tick_compress_ms",
               "eva_attn_roofline_pct")
# the source's config.json, as the catalog of public architectures has it
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32,
    "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "evabyte-6.5b-serve.json")) as f:
        return json.load(f)


def test_the_file_keeps_every_published_number(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(config["reduced"])
    assert config["published"]["num_hidden_layers"] == 32
    assert config["num_hidden_layers"] == 8
    for key in ("source", "deployment", "assumed", "engine",
                "memory_analysis", "builder", "parity", "requires"):
        assert config[key], key
    assert config["requires"] == ["ray_tpu.models.evabyte"]
    # the assumed list of the reference file, repeated
    for key in ("pooling_scale", "mu", "rotary_before_pooling",
                "phi_mu_init", "prediction_heads", "weights",
                "rotary_table", "engine"):
        assert key in config["assumed"], key


def test_parameter_counts_are_the_published_layer(config):
    p = costs_evabyte.params(config)
    # ISSUE 42, section 2: attention 67.1 M + phi, mu 8192; MLP 135.3 M
    assert p["attention"] == 4 * 4096 * 4096 == 67_108_864
    assert p["pooling"] == 8192
    assert p["mlp"] == 3 * 4096 * 11008 == 135_266_304
    assert costs_evabyte.layer_params(config) == 202_391_552
    assert p["embedding"] == 1_310_720 and p["head"] == 10_485_760
    assert p["head_sampled"] * 8 == p["head"]
    # 6.49 B published, 1.631 B here: 3.26 GB in bf16
    assert round(costs_evabyte.total_params(config, 32) / 1e9, 2) == 6.49
    assert round(costs_evabyte.total_params(config) / 1e9, 3) == 1.631
    assert round(2 * costs_evabyte.total_params(config) / 1e9, 2) == 3.26
    # a page: 16 rows of K and V, 32 heads x 128: 256 KiB a layer, 2 MiB
    assert costs_evabyte.page_bytes(config, 16, layers=1) == 256 * 1024
    assert costs_evabyte.page_bytes(config, 16) == 2 * 1024 * 1024


def test_a_row_holds_summaries_and_its_open_window(config):
    held = lambda n: costs_evabyte.pages_held(config, n, 16)  # noqa: E731
    assert [held(n) for n in (0, 1, 2047, 2048, 5000, 10239)] \
        == [0, 1, 128, 8, 16 + 57, 32 + 128]
    # as the program's own configuration says
    from ray_tpu.models.evabyte import EvaByteConfig
    model = EvaByteConfig()
    for n in (0, 17, 2047, 2048, 4095, 4096, 9999):
        assert held(n) == model.pages_held(n, 16)


def test_a_step_moves_what_the_issue_counted(config):
    # 40 rows x 78 pages: 3.26 GB of weights + 6.5 GB of pages = 9.8 GB
    moved = costs_evabyte.decode_step_bytes(config, 40 * 78, 16)
    assert round(moved["cache"] / 1e9, 1) == 6.5
    assert round(moved["total"] / 1e9, 1) == 9.8
    assert 0.66 < moved["cache"] / moved["total"] < 0.67
    # the embedding multiplies nothing, and only head 0's columns do
    assert moved["weights"] == 2 * (8 * (67_108_864 + 135_266_304)
                                    + 4096 * 320)
    # a close reads 128 pages and writes 8, in every layer: 0.35 ms
    assert costs_evabyte.compress_bytes(config, 16) == 136 * 2 * 1024 * 1024
    assert round(costs_evabyte.compress_bytes(config, 16) / 819e9 * 1e3,
                 2) == 0.35


def _record(config):
    ticks = [(10.0 + i, 10.5 + i, 100, 40 + i % 2, 1, (40 + i % 2) * 3000)
             for i in range(8)]
    page_ticks = [(10.0 + i, 40 + i % 2, (40 + i % 2) * 78)
                  for i in range(8)]
    stats = {"window_closes_decode": 1, "summary_rows": 1000,
             "window_rows": 9000}
    phases = {"compress": 0.5, "admit": 1.0}
    step = lambda n, scale: [{"kind": "tick", "steps": n,  # noqa: E731
                              "wall_s": 9.0 * scale, "cpu_s": 1.0 * scale,
                              "phases": {k: v * scale
                                         for k, v in phases.items()}}]
    return {
        "config": config, "t0": 10.0, "t1": 18.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"ticks": ticks, "page_ticks": page_ticks,
                   "max_batch": 44, "page_size": 16},
        "opened": {"stats": dict(stats), "steps": step(10, 1.0)},
        "closed": {"stats": dict(stats, window_closes_decode=5,
                                 summary_rows=3000, window_rows=17000),
                   "steps": step(110, 3.0)},
        "trace": {"window_s": 4.0, "busy_s": 3.0, "host_began": 12.0,
                  "host_ended": 16.0,
                  "ops": {"paged_attention": {"calls": 32,
                                              "total_s": 0.048}},
                  "programs": {"jit_decode_step": {"calls": 4,
                                                   "total_s": 0.068},
                               "jit_compress_window": {
                                   "calls": 2, "total_s": 0.0012,
                                   "median_ms": 0.6}}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    moved = costs_evabyte.decode_step_bytes(config, 40.5 * 78, 16)
    roofline = cell.reader("eva_decode_hbm_roofline_pct")(record)
    assert roofline == pytest.approx(100.0 * (moved["total"] / 819e9) / 0.017)
    assert 0 < roofline <= 100
    assert cell.reader("eva_cache_bytes_pct")(record) == pytest.approx(
        100.0 * moved["cache"] / moved["total"])
    assert cell.reader("summary_rows_pct")(record) == pytest.approx(20.0)
    assert cell.reader("compress_device_ms")(record) == 0.6
    assert cell.reader("tick_compress_ms")(record) == pytest.approx(
        1.0 / 100 * 1e3)
    kernel = cell.reader("eva_attn_roofline_pct")(record)
    assert kernel == pytest.approx(
        100.0 * (40.5 * 78 * 256 * 1024 / 819e9) / 0.0015)
    assert 0 < kernel <= 100


def test_readers_find_nothing_on_a_program_without_the_windows(config):
    """Another cell's record, an older program's: every new reader returns
    None and raises nothing."""
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    del record["report"]["page_ticks"]
    for edge in ("opened", "closed"):
        record[edge]["stats"] = {}
    record["trace"]["programs"] = {}
    record["trace"]["ops"] = {}
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
        "ttft_p50_ms.closed", "pool_in_use_pct", "decode_step_ms",
        "decode_step_device_ms", "prefill_chunk_device_ms",
        "compiles_in_window.serve", "device_idle_pct.serve",
        "hbm_peak_gib.serve", "tick_p99_ms", "tick_stall_pct",
        "tick_stall_unexplained_pct", "tick_stage_offcpu_pct",
        "lookahead_pct", "prefill_finish_ms"} == per_layer
    # its reader would count a row's length, four times what it holds
    assert "paged_attn_roofline_pct" not in per_layer
    for metric in cell.metrics(True):
        cell.reader(metric["name"])    # each has its file
    assert cell.driver() is serve_cell_evabyte.run
    # the new metrics are this cell's alone
    for metric in cell.benchmark["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "serve_out_tok_s"


def test_the_traffic_fits_the_engine_and_fixes_its_order(config):
    from benchmarks.harness import traffic
    cell = spec.Cell(ROOT, CELL)
    assert traffic.longest(cell.traffic) <= config["engine"]["max_len"] - 2
    assert cell.traffic["clients"] == 80 and cell.traffic["cycle"] == 80
    sizes = lambda seed: [  # noqa: E731
        (len(r.prompt), r.max_new) for r, _ in zip(
            traffic.requests(cell.traffic, seed, config["vocab_size"]),
            range(80))]
    assert sizes(1) == sizes(2 ** 31 + 7)
    prompts = [p for p, _ in sizes(1)]
    assert min(prompts) >= 1024 and max(prompts) <= 8192
    # ids from all 320 of the vocabulary, drawn by the seed
    first = next(traffic.requests(cell.traffic, 1, 320)).prompt
    other = next(traffic.requests(cell.traffic, 2, 320)).prompt
    assert first != other and max(first) < 320 and max(first) > 256


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_evabyte.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_a_row_cancelled_before_it_was_sent_is_no_request():
    """`Load.stop()` can cancel a caller between making its row and
    opening its connection: `sent` stays None, and the accepted
    ttft_p50_ms.closed (read in traced runs) raises on it. The driver
    leaves such a row out; one that failed before it was sent stays."""
    row = lambda **kw: dict({"due": None, "sent": None,  # noqa: E731
                             "chunks": [], "done": None, "error": None,
                             "expected": 4}, **kw)
    rows = [row(sent=11.0, chunks=[(11.5, 1), (12.0, 3)], done=12.0),
            row(), row(error="ConnectionRefusedError: ...", done=12.5)]
    cell = spec.Cell(ROOT, CELL)
    read = cell.reader("ttft_p50_ms.closed")
    with pytest.raises(TypeError):
        read({"rows": rows, "t0": 10.0, "t1": 18.0})
    kept = serve_cell_evabyte.sent_rows(rows)
    assert kept == [rows[0], rows[2]]
    assert read({"rows": kept[:1], "t0": 10.0, "t1": 18.0}) \
        == pytest.approx(500.0)


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "8", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 42)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""
    line = json.loads([ln for ln in got.stderr.splitlines()
                       if ln.startswith("bench: rehearsal")][-1]
                      .split(": ", 2)[2])
    assert line["correct"] is True and line["failed"] == 0
    # the CPU has no device trace: the three device readers return None
    assert {"eva_cache_bytes_pct", "summary_rows_pct", "tick_compress_ms",
            "decode_step_ms", "prefill_tick_pct", "pool_in_use_pct",
            "lookahead_pct"} <= set(line["metrics"])
    assert not {"eva_decode_hbm_roofline_pct", "compress_device_ms",
                "eva_attn_roofline_pct"} & set(line["metrics"])
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
    # windows closed in prefill and in decode inside 8 s
    assert line["metrics"]["summary_rows_pct"]["value"] > 0
    assert line["metrics"]["tick_compress_ms"]["value"] > 0
    parity = [ln for ln in got.stderr.splitlines()
              if ln.startswith("bench: parity")][-1]
    assert "'closes': [64, 128]" in parity
