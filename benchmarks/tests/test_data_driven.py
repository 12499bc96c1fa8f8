"""A later PR adds a configuration of an architecture the harness has never
seen, a traffic mix and a metric reader as NEW FILES ONLY. Shown here by
adding all three from a temporary directory and running them end to end
(CPU, toy sizes, --rehearse) without touching a file of the benchmark."""

import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


def rehearse(args, env=None, timeout=600):
    merged = dict(os.environ, JAX_PLATFORMS="cpu")
    merged.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "--seconds", "4"] + args,
        capture_output=True, text=True, timeout=timeout, env=merged,
        cwd=ROOT)


def rehearsal_line(result):
    lines = [ln for ln in result.stderr.splitlines()
             if ln.startswith("bench: rehearsal")]
    assert lines, result.stderr[-3000:]
    return json.loads(lines[-1].split(": ", 2)[2])


def test_new_config_traffic_and_metric_are_files_only(tmp_path):
    extra = tmp_path / "extra"
    write(str(extra / "toy_arch.py"), """
        def engine(config, seed, rehearse=False):
            # an "architecture" the harness has no builder for: its own
            # key names, built in a file this PR brings
            import jax.numpy as jnp
            from ray_tpu.llm.paged import PagedEngineConfig
            from ray_tpu.models.llama import LlamaConfig
            t = config["toy"]
            model = LlamaConfig(
                vocab_size=t["words"], hidden_size=t["width"],
                intermediate_size=3 * t["width"], num_layers=t["depth"],
                num_heads=2, num_kv_heads=1, max_seq_len=256,
                dtype=jnp.float32, param_dtype=jnp.float32,
                use_flash=False, attention_impl="reference")
            return PagedEngineConfig(
                model=model, max_batch=3, max_len=160, page_size=8,
                num_pages=128, prefill_buckets=(16, 32), seed=seed % 1000)
        """)
    write(str(extra / "configs" / "toy.json"), json.dumps({
        "source": "none: a toy for the harness's own test",
        "builder": "toy_arch:engine", "vocab_size": 300,
        "toy": {"words": 300, "width": 32, "depth": 1},
        "engine": {"max_batch": 3, "max_len": 160, "page_size": 8,
                   "num_pages": 128, "prefill_buckets": [16, 32]},
        "reduced": [], "assumed": {}}))
    toy_traffic = {
        "kind": "closed", "clients": 3, "client_start_gap_s": 0.05,
        "settle_s": 0.5, "cycle": 6,
        "prompt_tokens": {"dist": "loguniform", "low": 5, "high": 40},
        "output_tokens": {"dist": "loguniform", "low": 6, "high": 6}}
    toy_traffic["rehearse"] = {"cycle": 6}
    write(str(extra / "traffic" / "toy-closed.json"),
          json.dumps(toy_traffic))
    write(str(extra / "metrics" / "toy_requests.py"), """
        def read(record):
            return float(sum(1 for r in record["rows"] if r["done"]))
        """)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"].append("extra")
    bench["configs"].append({"name": "toy", "source": "none",
                             "file": "extra/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.closed", "config": "toy",
                               "traffic": "toy-closed", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "toy_requests", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "load generator (benchmark)",
        "moves": "serve_out_tok_s", "workloads": ["toy.closed"]})
    for metric in bench["end_to_end"]:
        if metric["name"] == "serve_out_tok_s":
            metric["workloads"].append("toy.closed")
    # the benchmark's own metric and traffic files are found by name from
    # the new root as well: link them, change none
    os.symlink(os.path.join(ROOT, "benchmarks"),
               str(tmp_path / "benchmarks"))
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    env = {"PYTHONPATH": str(extra)}
    got = rehearse(["--workload", "toy.closed", "--root", str(tmp_path),
                    "--trace", "1"], env)
    line = rehearsal_line(got)
    assert got.returncode == 3, got.stderr[-3000:]
    assert line["metrics"]["toy_requests"]["value"] > 0
    assert line["correct"] is True and line["failed"] == 0
    end_to_end = rehearsal_line(rehearse(
        ["--workload", "toy.closed", "--root", str(tmp_path),
         "--trace", "0"], env))
    assert set(end_to_end["metrics"]) == {"serve_out_tok_s", "setup_s"}


def test_rehearsal_runs_a_train_cell_end_to_end_and_prints_no_result():
    got = rehearse(["--workload", "train-yi-1chip", "--trace", "0",
                    "--seed", str(2 ** 31 + 99)])
    assert got.returncode == 3, got.stderr[-3000:]
    assert got.stdout.strip() == ""          # a rehearsal is never a result
    line = rehearsal_line(got)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "train-yi-1chip", "--seconds", "2"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert got.returncode not in (0, 3)
    assert got.stdout.strip() == ""
