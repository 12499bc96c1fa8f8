"""The files the Xing4.0 cell brings: the counts behind its roofline shares
(ISSUE 52's table from the file's keys), its request stream, its readers on
a synthetic record and on a record that has nothing for them (the
parent's), the published numbers the configuration file must keep, the
chain and the router alone with their controls, the driver's refusal of a
program that lacks the model, and the cell end to end on the CPU
(--rehearse: toy widths, fresh prompts prefilled in the window, the same
control flow, parity against the float32 reference included)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import costs_xing_mhc, serve_cell_xing_mhc, spec
from benchmarks.harness.cluster import BenchFailure

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-xing-longin-closed64"
NEW_METRICS = ("xing_decode_hbm_roofline_pct", "xing_chunk_roofline_pct",
               "mhc_time_pct", "mhc_sublayer_us")
# the source's config.json, as the catalog of public architectures has it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b-serve.json")) as f:
        return json.load(f)


def test_the_file_keeps_every_published_number(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace"} \
        == set(config["reduced"])
    assert config["published"] == {"num_hidden_layers": 40,
                                   "first_k_dense_replace": 2}
    assert (config["num_hidden_layers"],
            config["first_k_dense_replace"]) == (6, 1)
    # every expert and the whole vocabulary
    assert config["held_experts"] == [0, config["n_routed_experts"]]
    for key in ("source", "deployment", "assumed", "engine",
                "memory_analysis", "builder", "parity", "requires"):
        assert config[key], key
    for key in ("latent_norms", "rotary_pairs", "hc_eps_and_order",
                "stream_norm", "streams_in_and_out", "hc_init",
                "router_bias", "mtp", "weights", "num_pages"):
        assert key in config["assumed"], key
    # the floors of a cut: a period + 4 expert layers
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    engine = config["engine"]
    assert engine["num_pages"] >= engine["max_batch"] * (
        engine["max_len"] // engine["page_size"])


def test_parameter_counts_are_the_issues(config):
    p = costs_xing_mhc.layer_params(config)
    # W_dq 2.753 M + W_uq 4.719 M + W_kva 2.064 M + W_kvb 4.194 M + W_o 14.680
    assert p["attention"] == 2752512 + 4718592 + 2064384 + 4194304 + 14680064
    assert 2 * p["connection"] == 2 * 14336 * 24
    assert p["dense_mlp"] == 3 * 3584 * 9216 == 99090432
    assert p["expert"] == 11010048
    assert p["moe_outside_experts"] == 229376 + 11010048
    table = costs_xing_mhc.table(config)
    assert table["embedding_and_head_params"] == 939524096
    assert table["dense_layer_params"] == pytest.approx(128.2e6, rel=1e-3)
    assert table["expert_layer_params"] == pytest.approx(745.0e6, rel=1e-3)
    assert table["weights_params"] == 4792669828       # 4.79 B
    assert table["weights_bytes"] == pytest.approx(9.585e9, rel=1e-4)
    assert table["resident_bytes_per_token"] == 7680
    assert table["pool_bytes"] == pytest.approx(4.53e9, rel=1e-3)
    assert {k: int(v) for k, v in table.items()} \
        == config["memory_analysis"]["table"]


def test_a_step_and_a_chunk_move_what_the_issue_counted(config):
    """48 rows at a mean context of 5.5k: all but the embedding of the
    weights (8.65 GB) with every expert hit, 1.8 GB of latent rows, 33 MB
    of streams: 10.5 GB, 12.8 ms at 819 GB/s. A 512-token chunk at 4.5k
    rows: the weights without the head, ~1.06 TFLOP of which the chosen
    pairs are 5 x 2048 x 11.01 M x 2."""
    moved = costs_xing_mhc.decode_step_bytes(config, 48 * 5500 / 64, 64, 48)
    assert moved["weights"] == pytest.approx(9.585e9 - 0.9395e9, rel=2e-3)
    assert moved["cache"] == pytest.approx(48 * 5500 * 6912)
    assert moved["streams"] == 48 * 12 * 2 * 4 * 3584 * 2
    assert moved["total"] / 819e9 == pytest.approx(12.8e-3, rel=0.02)
    fewer = costs_xing_mhc.decode_step_bytes(config, 48 * 5500 / 64, 64, 48,
                                             hit_experts=60.0)
    assert moved["weights"] - fewer["weights"] \
        == pytest.approx(5 * 4 * 11010048 * 2)
    chunk = costs_xing_mhc.chunk(config, 512, 4500)
    assert chunk["weights"] == pytest.approx(9.585e9 - 2 * 0.9395e9, rel=2e-3)
    assert chunk["flops"] == pytest.approx(1.06e12, rel=0.02)
    assert chunk["flops"] > 2 * 512 * 5 * 4 * 11010048
    # bound by its bytes: the weights' read, 9.9 ms against 5.4 of FLOPs
    assert chunk["bytes"] / 819e9 > chunk["flops"] / 197e12
    # the dense form's work is not in the count: 16 x the pairs'
    dense_form = 2 * 512 * 5 * 64 * 11010048
    assert chunk["flops"] < dense_form


def test_the_request_stream(config):
    from benchmarks.harness import traffic
    cell = spec.Cell(ROOT, CELL)
    t = cell.traffic
    assert t["kind"] == "closed" and not t["sharing"]
    assert traffic.longest(t) <= config["engine"]["max_len"] - 2
    assert (t["clients"], t["cycle"]) == (64, 64)
    assert t["clients"] > config["engine"]["max_batch"]

    def cycle(seed):
        stream = traffic.requests(t, seed, config["vocab_size"])
        return [next(stream) for _ in range(64)]

    one, other = cycle(1), cycle(2 ** 31 + 7)
    sizes = [(len(r.prompt), r.max_new) for r in one]
    # ONE order of the sizes for every seed; the seed draws the ids
    assert sizes == [(len(r.prompt), r.max_new) for r in other]
    assert one[0].prompt != other[0].prompt
    assert all(2048 <= p <= 8192 and 256 <= a <= 1024 for p, a in sizes)
    assert np.mean([p for p, _ in sizes]) == pytest.approx(4.4e3, rel=0.02)
    assert np.mean([a for _, a in sizes]) == pytest.approx(554, rel=0.02)
    assert max(max(r.prompt) for r in one) > 32768     # the whole vocabulary
    assert all(r.shared_tokens == 0 for r in one)


def _record(config):
    """A traced window of 100 decode steps: 48 rows a step holding 4,100
    distinct pages, 262k cached tokens attended; 80 ticks carried a chunk
    whose last token attended 4,500 rows; 62 of 64 experts hit a layer a
    step; a step's 16 ms by instruction."""
    ticks = [(10.0 + 0.02 * i, 262_000, 4_100, 4_100, 48,
              4_500 if i % 5 else 0, 1 if i % 5 else 0)
             for i in range(100)]
    stats = lambda scale: {  # noqa: E731
        "latent_pages_rowwise": 4_100 * 100 * scale,
        "latent_pages_distinct": 4_100 * 100 * scale,
        "expert_pairs": [[300 * scale] * 64] * 5,
        "expert_steps": [[97 * scale] * 62 + [95 * scale] * 2] * 5,
        "layer_kinds": ["p"] + ["pc"] * 5}
    steps = lambda n: [{"kind": "decode", "steps": n}]  # noqa: E731
    scopes = {"fusion.1": "x/layer_0/attn_hc/mhc/coeff/dot",
              "fusion.2": "x/layer_0/mhc/post/mul",
              "fusion.3": "x/layer_1/mlp_hc/mhc/coeff/while/body/div",
              "fusion.4": "x/layer_2/mhc/pre/mul"}
    return {
        "config": config, "t0": 10.0, "t1": 12.0,
        "device": {"kind": "TPU v5 lite"},
        "report": {"page_size": 64, "latent_ticks": ticks},
        "opened": {"stats": stats(1), "steps": steps(100)},
        "closed": {"stats": stats(2), "steps": steps(200)},
        "parity": {"decode_instructions": dict(
            scopes, **{"latent_attention.3": "x/layer_0/attn/mla/attend/",
                       "fusion.9": "x/layer_1/moe/routed/moe/experts/dot/"}),
                   "mla_instructions": {"latent_attention.3": "x/mla/attend"},
                   "moe_instructions": {"fusion.9": "x/moe/experts/dot"},
                   "chunk_instructions": {
                       "fusion.7": "x/moe/routed/moe/experts/dot/",
                       "fusion.8": "x/mhc/coeff/dot/"}},
        "trace": {"window_s": 2.0, "busy_s": 1.9, "host_began": 10.0,
                  "host_ended": 12.0,
                  "programs": {"jit_decode_step": {
                      "calls": 100, "total_s": 1.6, "median_ms": 16.0},
                      "jit_chunk_prefill": {
                      "calls": 80, "total_s": 3.2, "median_ms": 40.0}},
                  "ops": {"latent_attention.3": {"calls": 600,
                                                 "total_s": 0.3}},
                  "decode_step_instructions": {
                      "runs": 100, "total_s": 1.6, "by_instruction": {
                          "fusion.1": [100, 0.02], "fusion.2": [100, 0.01],
                          "fusion.3": [2000, 0.06], "fusion.4": [100, 0.03],
                          "latent_attention.3": [600, 0.3],
                          "fusion.9": [100, 0.5]}},
                  "chunk_prefill_instructions": {
                      "runs": 80, "total_s": 3.2, "by_instruction": {
                          "fusion.7": [400, 1.6], "fusion.8": [960, 0.2]}}}}


def test_readers_on_a_synthetic_record(config):
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    hit = (62 * 97 + 2 * 95) / (64 * 100) * 64
    moved = costs_xing_mhc.decode_step_bytes(config, 4_100, 64, 48, hit)
    step = cell.reader("xing_decode_hbm_roofline_pct")(record)
    assert step == pytest.approx(100.0 * (moved["total"] / 819e9) / 0.016)
    assert 0 < step <= 100
    need = costs_xing_mhc.chunk(config, 512, 4_500)
    chunk = cell.reader("xing_chunk_roofline_pct")(record)
    assert chunk == pytest.approx(100.0 * max(
        need["bytes"] / 819e9, need["flops"] / 197e12) / 0.040)
    assert 0 < chunk <= 100
    assert cell.reader("mhc_time_pct")(record) \
        == pytest.approx(100.0 * 0.12 / 1.6)
    # layers 0, 1, 2 spend 0.3, 0.6, 0.3 ms a step: the median, over two
    assert cell.reader("mhc_sublayer_us")(record) == pytest.approx(150.0)
    # the accepted readers this cell is appended to, on the same record
    assert cell.reader("expert_time_pct")(record) \
        == pytest.approx(100.0 * 0.5 / 1.6)
    assert cell.reader("mla_time_pct")(record) \
        == pytest.approx(100.0 * 0.3 / 1.6)
    assert 0 < cell.reader("mla_attn_roofline_pct")(record) <= 100
    assert cell.reader("prefill_ctx_device_ms")(record) \
        == pytest.approx(3200.0 / (80 * 4_500 / 1e3))
    found = serve_cell_xing_mhc.scoped_seconds(
        record, "moe/experts/", program="chunk_prefill")
    assert found[0] == pytest.approx(1.6) and found[1]["runs"] == 80
    # the module named `routed` is not the scope `moe/route`
    assert serve_cell_xing_mhc.scoped_seconds(
        record, "moe/route/", program="chunk_prefill")[0] == 0.0
    assert serve_cell_xing_mhc.scoped_seconds(
        record, "moe/experts/")[0] == pytest.approx(0.5)


def test_readers_find_nothing_on_a_program_without_the_model(config):
    """Another cell's record, the parent's program: every new reader
    returns None and raises nothing."""
    cell = spec.Cell(ROOT, CELL)
    record = _record(config)
    del record["report"]["latent_ticks"]
    for edge in ("opened", "closed"):
        record[edge]["stats"] = {}
    record["parity"] = {}
    for name in NEW_METRICS:
        assert cell.reader(name)(record) is None, name
    record["trace"]["programs"] = {}
    del record["trace"]["decode_step_instructions"]
    del record["trace"]["chunk_prefill_instructions"]
    for name in NEW_METRICS:
        assert cell.reader(name)(record) is None, name
    del record["trace"]
    for name in NEW_METRICS:
        assert cell.reader(name)(record) is None, name


def test_the_cell_lists_every_metric_it_reports():
    cell = spec.Cell(ROOT, CELL)
    assert cell.chips == 1
    assert len(cell.benchmark["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in cell.benchmark["workloads"]) == 1
    end_to_end = {m["name"] for m in cell.metrics(False)}
    assert end_to_end == {"serve_out_tok_s", "tpot_p90_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.metrics(True)}
    assert set(NEW_METRICS) | {
        "gap_p99_ms", "batch_occupancy_pct", "prefill_tick_pct",
        "ttft_p50_ms.closed", "pool_in_use_pct", "decode_step_ms",
        "decode_step_device_ms", "prefill_chunk_device_ms",
        "compiles_in_window.serve", "device_idle_pct.serve",
        "hbm_peak_gib.serve", "expert_time_pct", "expert_pairs_per_step",
        "expert_load_max_over_mean", "mla_time_pct",
        "mla_attn_roofline_pct", "prefill_ctx_device_ms"} == per_layer
    # the tick's tail readers (tickstalls.py) find nothing in a traced run
    # of this cell whose `slow` list (the newest 64) fills after the trace
    # began (PERF.md section 7 (k), PR 52): not this cell's to list
    assert not {"tick_p99_ms", "tick_stall_pct", "lookahead_pct",
                "tick_stall_unexplained_pct", "tick_stage_offcpu_pct",
                "prefill_finish_ms"} & per_layer
    # Sarvam's readers that read its own keys are not this cell's
    assert not {"mla_decode_hbm_roofline_pct", "latent_cache_bytes_pct",
                "prefix_hit_pct"} & per_layer
    for metric in cell.metrics(True):
        cell.reader(metric["name"])    # each has its file
    assert cell.driver() is serve_cell_xing_mhc.run
    for metric in cell.benchmark["per_layer"]:
        if metric["name"] in NEW_METRICS:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "serve_out_tok_s"
            assert metric["source"] == "device_trace"


def test_the_chain_and_the_router_alone_tell_their_controls():
    """`parity_xing_mhc.chains_alone` and `router_alone` on a toy model's
    own float32 outputs: the program's chain agrees with float64 far under
    the limit, a bf16 chain and 19 iterations far over it; the program's
    routings agree with the float64 order."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import builders_xing_mhc, parity_xing_mhc
    from ray_tpu.parallel.mesh import unbox
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b-serve.json")) as f:
        cfg = builders_xing_mhc.xing_mhc_model(json.load(f), rehearse=True)
    module = cfg.module()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 1, 500)
    params = unbox(module.init(jax.random.PRNGKey(0), tokens)["params"])
    _, sown = module.apply({"params": params}, tokens,
                           mutable=["routing", "intermediates"])
    routes, gave = parity_xing_mhc._sown(sown, cfg)
    chain = parity_xing_mhc.chains_alone(
        np.asarray(gave["streams"][0]), np.asarray(gave["coefficients"][0]),
        params, cfg)
    assert chain["program"] < parity_xing_mhc.COEFFICIENTS / 10
    assert chain["bf16_chain"] > 100 * parity_xing_mhc.COEFFICIENTS
    assert chain["sinkhorn_19"] > 3 * parity_xing_mhc.COEFFICIENTS
    assert 1e-5 < chain["doubly_stochastic"] < 5e-3
    router = parity_xing_mhc.router_alone(
        np.asarray(gave["router_inputs"][0]),
        [np.asarray(r[0]) for r in routes], params, cfg)
    assert router["program"] == 1.0 and router["routings"] == 2 * 48
    assert jnp.isfinite(gave["coefficients"]).all()


@pytest.mark.parametrize("median,worst,passed", [
    (0.16, 0.25, {"logits": True, "logit_median": True}),
    (0.30, 0.25, {"logits": True, "logit_median": False}),
    (0.16, 2.0, {"logits": False, "logit_median": True})])
def test_the_logit_limits_lie_between_the_program_and_the_8bit_rows(
        median, worst, passed):
    """`logit_verdict` at the chip's readings (PERF.md section 6, PR 52):
    the program's pass both limits, and each limit alone refuses a part that
    is over it (the 8-bit control is refused by both)."""
    from benchmarks.harness import parity_xing_mhc
    diffs = np.full(64, median)
    diffs[7] = worst
    got = parity_xing_mhc.logit_verdict(
        {"decode": {"median": float(np.median(diffs)),
                    "diff_over_std": diffs.tolist()}},
        {"decode": np.zeros(64, bool)})
    assert got["passed"] == passed
    assert got["beyond"] == (0 if passed["logits"] else 1)
    # an ill-conditioned position is set aside, not held to the limit
    aside = np.zeros(64, bool)
    aside[7] = True
    assert parity_xing_mhc.logit_verdict(
        {"decode": {"median": median, "diff_over_std": diffs.tolist()}},
        {"decode": aside})["passed"]["logits"]


def test_stream_errors_by_connection():
    """`stream_errors`: 0 where the program read what the reference read,
    the relative distance of the sums and of the streams apart where not,
    and each sublayer's output beside the streams it was added to."""
    from benchmarks.harness import parity_xing_mhc
    rng = np.random.default_rng(0)
    reference = [rng.normal(size=(5, 4, 16)) for _ in range(3)]
    reference[1] = reference[0] + 0.5 * reference[0].sum(1, keepdims=True) / 4
    have = np.stack(reference, 1)
    have[:, 2] *= 1.01
    got = parity_xing_mhc.stream_errors(have, reference)
    assert got["of_sum"][:2] == [0.0, 0.0] == got["of_streams"][:2]
    assert got["of_sum"][2] == pytest.approx(0.01)
    assert got["of_streams"][2] == pytest.approx(0.01)
    assert got["sublayer_over_stream"][0] == pytest.approx(0.5)
    assert len(got["sublayer_over_stream"]) == 2


def test_a_program_without_the_model_is_refused_before_any_cluster():
    cell = spec.Cell(ROOT, CELL)
    cell.config = dict(cell.config, requires=["ray_tpu.models.no_such_model"])
    with pytest.raises(BenchFailure, match="no_such_model"):
        serve_cell_xing_mhc.run(cell, 0, 1.0, False, True, 0.0)
    import ray_tpu
    assert not ray_tpu.is_initialized()


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "8", "--workload", CELL, "--trace", "1",
         "--seed", str(2 ** 31 + 52)],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""
    line = json.loads([ln for ln in got.stderr.splitlines()
                       if ln.startswith("bench: rehearsal")][-1]
                      .split(": ", 2)[2])
    assert line["correct"] is True and line["failed"] == 0
    assert "prefill_tick_pct" in line["metrics"]
    assert line["metrics"]["expert_pairs_per_step"]["value"] > 0
    # the check's verdict: every control that is judged must fail, the
    # 8-bit latent rows by the logits' limits; the timed programs are held
    # between their readings and a mismatched program's
    import ast
    verdict = ast.literal_eval(
        [ln for ln in got.stderr.splitlines()
         if ln.startswith("bench: parity ")][-1][len("bench: parity "):])
    assert verdict["ok"] and not verdict["failed"]
    assert verdict["controls"]["latent_rows_8bit"]["ok"] is False
    assert verdict["timed"]["decode_agree"] >= 0.9 \
        > verdict["timed"]["mismatched_decode_agree"]
    assert verdict["timed"]["chunk_median"] <= 0.05 \
        < verdict["timed"]["mismatched_chunk_median"]
    assert len(verdict["stream_error"]["of_sum"]) == 6
    spans = ast.literal_eval(
        [ln for ln in got.stderr.splitlines()
         if ln.startswith("bench: the window's ticks ")][-1]
        [len("bench: the window's ticks "):])
    assert sum(spans["ticks"]) > 0 and spans["longest_tick_s"] > 0
    assert len(spans["ticks"]) == len(spans["rows_decoding"]) <= 2
    said = [ln for ln in got.stderr.splitlines() if "preemptions" in ln]
    assert said and "preemptions 0" in said[0] \
        and "0 came from the radix" in said[0]
