"""chip_smoke.py: the quickest proof that ray_tpu still starts on the chip.

Drives the system's two main paths once, through the entry points a user
calls, at the published widths of Llama-3-8B (depth cut, random weights
from --seed, made inside the process that owns the chip):

  serve  ray_tpu.init -> serve.run(build_llm_deployment(...)) -> streamed
         HTTP requests through the real proxy (chunked prefill, batched
         decode, a radix prefix hit, greedy)
  train  the Pallas kernels against the jnp references on a small input,
         then JaxTrainer(...).fit() -> five steps on one fixed token batch

    python chip_smoke.py             # one chip: serve, then train
    python chip_smoke.py --chips 4   # four chips: ONLY the cross-chip
                                     # paths and what each is compared with
    python chip_smoke.py --rehearse  # CPU, tiny widths, same control flow;
                                     # never prints "ok": true, never exits 0

This process never opens a JAX backend: a parent that has touched JAX holds
the chip and its workers then cannot. Platform, device kind and count come
from what the chip-owning worker reports. Every phase fails loudly; none
catches an exception and continues. The last stdout line is the result:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import functools
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

# stdout carries this script's own lines only: whatever else prints in this
# process (the relayed worker logs) goes to stderr, so the result stays last.
OUT = sys.stdout
sys.stdout = sys.stderr

LLAMA3_8B_LAYERS = 32
SERVE_LAYERS = 16       # of 32: ~4.5 B params, 9.1 GB bf16 (AOT: PERF.md)
SERVE_LAYERS_4CHIP = 8  # tensor=1 arm must fit one chip; keeps compiles short
TRAIN_LAYERS = 4        # of 32: 1.9 B params at batch 2 x 2048 (AOT: PERF.md)
REPLICA_WAIT_S = 900.0
HTTP_TIMEOUT_S = 900.0


def say(line: str) -> None:
    print(line, file=OUT, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# the plan: real widths on the chip, a toy on the CPU rehearsal
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Plan:
    chips: int
    rehearse: bool
    seed: int

    @property
    def platform(self) -> str:
        return "cpu" if self.rehearse else "tpu"

    def model(self, layers: int):
        import jax.numpy as jnp  # dtype names only; opens no backend
        from ray_tpu.models.llama import LlamaConfig
        if self.rehearse:
            return LlamaConfig(
                vocab_size=512, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=512,
                dtype=jnp.float32, param_dtype=jnp.float32)
        # published widths; depth is the only cut
        return dataclasses.replace(
            LlamaConfig.llama3_8b(), num_layers=layers,
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)

    def engine(self, layers: int):
        from ray_tpu.llm.paged import PagedEngineConfig
        if self.rehearse:
            return PagedEngineConfig(
                model=self.model(layers), max_batch=4, max_len=256,
                page_size=8, num_pages=256, prefill_buckets=(16, 32),
                seed=self.seed)
        return PagedEngineConfig(
            model=self.model(layers), max_batch=8, max_len=2048,
            page_size=16, num_pages=2048,
            prefill_buckets=(32, 64, 128, 256), seed=self.seed)

    def actor_options(self) -> Dict[str, Any]:
        """The replica's lease: its chips on the chip; on the CPU rehearsal
        no chips, and (four-chip paths) four virtual CPU devices."""
        if not self.rehearse:
            return {"num_tpus": self.chips}
        env = {"JAX_PLATFORMS": "cpu"}
        if self.chips > 1:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={self.chips}")
        return {"runtime_env": {"env_vars": env}}

    def scaling(self, mesh_axes: Optional[Dict[str, int]]):
        from ray_tpu.train import ScalingConfig
        if self.rehearse:
            return ScalingConfig(
                num_workers=1, mesh_axes=mesh_axes,
                virtual_devices=self.chips if self.chips > 1 else None)
        return ScalingConfig(
            num_workers=1, use_tpu=True, mesh_axes=mesh_axes,
            resources_per_worker={"TPU": self.chips})

    def train_shape(self):
        """(batch, sequence) of the one fixed training batch."""
        return (2, 128) if self.rehearse else (2, 2048)


# ---------------------------------------------------------------------------
# cluster bring-up / tear-down
# ---------------------------------------------------------------------------

def start_cluster(plan: Plan) -> None:
    import ray_tpu
    ray_tpu.init()
    have = ray_tpu.cluster_resources().get("TPU", 0)
    if not plan.rehearse:
        check(have >= plan.chips,
              f"this node advertises TPU: {have}, the smoke needs "
              f"{plan.chips} (no /dev/accel* or /dev/vfio/N chip found)")


def wait_pid_gone(pid: int, what: str, timeout_s: float = 300.0) -> None:
    """A chip belongs to one process: the next phase may start only once
    the last chip-owning worker has really exited. A zombie has exited —
    it holds no chip, only a row its parent has yet to read."""
    def state() -> Optional[str]:
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0]
        except (FileNotFoundError, ProcessLookupError):
            return None

    start = time.monotonic()
    while time.monotonic() - start < timeout_s:
        if state() in (None, "Z"):
            say(f"smoke observation: {what} (pid {pid}) gone "
                f"{time.monotonic() - start:.1f}s after shutdown")
            return
        time.sleep(0.2)
    raise SmokeFailure(
        f"{what} (pid {pid}) still alive (state {state()}) "
        f"{timeout_s:.0f}s after shutdown: it would keep the chip")


def check_device(plan: Plan, device: Dict[str, Any], who: str) -> None:
    check(device["platform"] == plan.platform,
          f"{who} runs on platform {device['platform']!r}, "
          f"expected {plan.platform!r}")
    check(device["count"] >= plan.chips,
          f"{who} sees {device['count']} devices, needs {plan.chips}")


def say_memory(who: str, memory: List[Optional[Dict[str, Any]]]) -> None:
    for index, stats in enumerate(memory):
        if stats:
            say(f"smoke observation: {who} device {index} bytes_in_use="
                f"{stats.get('bytes_in_use')} peak_bytes_in_use="
                f"{stats.get('peak_bytes_in_use')}")


def check_spread(plan: Plan, who: str,
                 memory: List[Optional[Dict[str, Any]]]) -> None:
    """Sharded state lies on every chip, not all on device 0 (the CPU
    backend of the rehearsal reports no memory_stats)."""
    in_use = [m["bytes_in_use"] for m in memory if m]
    if in_use:
        check(len(in_use) == plan.chips and min(in_use) > 0
              and max(in_use) < 2 * min(in_use),
              f"{who}: state is not spread over the chips: "
              f"bytes_in_use {in_use}")


# ---------------------------------------------------------------------------
# serve: HTTP through the real proxy
# ---------------------------------------------------------------------------

def stream_request(address: str, prompt: List[int],
                   max_new: int) -> Dict[str, Any]:
    """One greedy streamed generation through the proxy over a raw socket
    (the saturation bench's client); returns the token ids, the wall time
    to the first token and the whole wall time."""
    from ray_tpu.perf_workloads import _sat_stream_once
    host, port = address.replace("http://", "").rsplit(":", 1)
    start = time.monotonic()
    got = _sat_stream_once(
        host, int(port),
        {"prompt_tokens": prompt, "max_new_tokens": max_new,
         "stream": True, "temperature": 0.0}, timeout_s=HTTP_TIMEOUT_S)
    # LLMServer._drive fails requests loudly (fail_all): any error on a
    # stream fails the smoke
    check(not got["error"], f"stream failed in the engine: {got['error']}")
    check(len(got["tokens"]) == max_new,
          f"stream returned {len(got['tokens'])} tokens, asked {max_new}")
    return {"tokens": got["tokens"], "first_token_s": got["ttft_s"],
            "wall_s": time.monotonic() - start}


def concurrently(calls) -> List[Any]:
    """Run the thunks at once; the first failure is re-raised."""
    with concurrent.futures.ThreadPoolExecutor(len(calls)) as pool:
        return list(pool.map(lambda call: call(), calls))


def seeded_prompts(plan: Plan, engine_cfg) -> Dict[str, List[int]]:
    import numpy as np
    rng = np.random.RandomState(plan.seed)
    vocab = engine_cfg.model.vocab_size
    largest = engine_cfg.prefill_buckets[-1]
    page = engine_cfg.page_size
    # longer than the largest prefill bucket: runs as several chunks
    long_prompt = rng.randint(1, vocab, size=largest + 44).tolist()
    shared_pages = (largest + 32) // page
    return {
        "long": long_prompt,
        "a": rng.randint(1, vocab, size=40).tolist(),
        "b": rng.randint(1, vocab, size=90).tolist(),
        # repeats whole pages of `long`, then diverges: a radix hit
        "repeat": long_prompt[:shared_pages * page]
        + rng.randint(1, vocab, size=20).tolist(),
    }


def serve_session(plan: Plan, layers: int, mesh_config, label: str,
                  drive) -> Dict[str, Any]:
    """One cluster, one LLMServer replica behind the proxy;
    `drive(ask, prompts)` sends the traffic. Returns the replica's reports
    once its process has exited."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serving import build_llm_deployment

    engine_cfg = plan.engine(layers)
    model = engine_cfg.model
    say(f"smoke: serve[{label}] llama3-8b widths hidden={model.hidden_size} "
        f"mlp={model.intermediate_size} heads={model.num_heads}/"
        f"{model.num_kv_heads}x{model.head_dim_} vocab={model.vocab_size} "
        f"depth={model.num_layers} of {LLAMA3_8B_LAYERS} "
        f"params={model.num_params()} max_batch={engine_cfg.max_batch} "
        f"max_len={engine_cfg.max_len} pages={engine_cfg.num_pages}"
        f"x{engine_cfg.page_size}")
    start_cluster(plan)
    try:
        t0 = time.monotonic()
        app = build_llm_deployment(
            engine_cfg, name="smoke", mesh_config=mesh_config,
            ray_actor_options=plan.actor_options())
        handle = serve.run(app, name="smoke", route_prefix="/llm",
                           wait_for_ready_timeout_s=REPLICA_WAIT_S)
        say(f"smoke observation: serve[{label}] replica ready (weights "
            f"made on device) in {time.monotonic() - t0:.1f}s")
        result = drive(
            functools.partial(stream_request, serve.get_http_address()),
            seeded_prompts(plan, engine_cfg))
        report = handle.device_report.remote().result(
            timeout_s=REPLICA_WAIT_S)
        stats = handle.engine_stats.remote().result(timeout_s=60.0)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()

    device = {k: report[k] for k in ("platform", "kind", "count")}
    kernels = report["decode_kernels"]
    pool_copies = report["decode_pool_copies"]
    say(f"smoke: serve[{label}] replica pid {report['pid']} on "
        f"{json.dumps(device)}; decode kernels {json.dumps(kernels)}; "
        f"pool-shaped copies in the decode step {pool_copies}; "
        f"stats steps={stats['steps']} tokens={stats['tokens_generated']} "
        f"prefix_hits={stats['prefix_hits']} "
        f"leaked_pages={stats['leaked_pages']} tp={stats['tp']} "
        f"paged_kernel={stats['paged_kernel']} "
        f"lookahead_ticks={stats['lookahead_ticks']} "
        f"drained_by={json.dumps(stats['drained_by'])} "
        f"discarded_tokens={stats['discarded_tokens']}")
    wait_pid_gone(report["pid"], f"serve[{label}] replica")
    check_device(plan, device, f"serve[{label}] replica")
    if not plan.rehearse:
        # a shape the kernel refuses is an error here, not a gather run
        check(stats["paged_kernel"] == "pallas"
              and kernels.get("paged_attention") == model.num_layers,
              f"compiled decode step holds {kernels} and the engine took "
              f"the {stats['paged_kernel']} path — expected the "
              f"paged-attention tpu_custom_call once per layer "
              f"({model.num_layers})")
        # the token's K/V write and the kernel share the pool's layout:
        # a copy shaped like a whole pool is the relayout that cost 38 %
        # of the decode step (PERF.md, PR 29)
        check(pool_copies == 0,
              f"compiled decode step copies a whole page pool "
              f"{pool_copies} times: the K/V write and the paged kernel "
              f"no longer agree on the pool's layout")
    check(stats["leaked_pages"] == 0,
          f"{stats['leaked_pages']} leaked KV pages")
    # the tick runs one decode step ahead of the host (PERF.md, PR 33):
    # a run that decodes at all dispatches most steps before it reads
    # the one before, and reads without a step behind only when a batch
    # empties. A serial tick reads 0 ahead and every step behind. The
    # four-chip phase's 24 one-token requests end at their prefill, run
    # no decode step and empty the batch once a wave, as many waves as
    # their arrivals happen to form: 22 ahead on both sides, 5 behind on
    # the parent and 6 on the change in one call (PR 37), so a factor of
    # 4 passed by one wave or failed by one
    check(stats["lookahead_ticks"] > stats["drained_ticks"],
          f"the step ahead was there for {stats['lookahead_ticks']} decode "
          f"steps and missing for {stats['drained_ticks']} "
          f"({stats['drained_by']}): the tick is serial again")
    compiles = report["compile"]
    say(f"smoke observation: serve[{label}] one cold run: "
        f"{compiles['compiles']} compiles, "
        f"{compiles['compile_seconds']:.1f}s compiling; decode ticks "
        + json.dumps([{k: round(v, 4) if isinstance(v, float) else v
                       for k, v in row.items()
                       if k in ("kind", "steps", "mean_step_s", "compile_s")}
                      for row in report["steps"]]))
    say_memory(f"serve[{label}]", report["memory"])
    return {"device": device, "stats": stats, "result": result,
            "memory": report["memory"]}


def phase_serve(plan: Plan) -> Dict[str, Any]:
    def drive(ask, prompts):
        first = ask(prompts["long"], 16)       # chunked prefill
        pair = concurrently([                  # batched decode
            lambda: ask(prompts["a"], 24),
            lambda: ask(prompts["b"], 24)])
        repeat = ask(prompts["repeat"], 8)     # radix prefix hit
        for name, r in (("long", first), ("a", pair[0]), ("b", pair[1]),
                        ("repeat", repeat)):
            say(f"smoke observation: stream {name} prompt="
                f"{len(prompts[name])} tokens={len(r['tokens'])} "
                f"first_token={r['first_token_s']:.2f}s "
                f"wall={r['wall_s']:.2f}s (first of each shape compiles)")

    out = serve_session(plan, SERVE_LAYERS, None, "1 chip", drive)
    check(out["stats"]["prefix_hits"] >= 1,
          "the repeated prefix did not hit the radix cache")
    return out["device"]


def phase_serve_tensor_parallel(plan: Plan) -> Dict[str, Any]:
    """tensor=4 against tensor=1 at the same depth and seed. Free-running
    greedy ids are printed; the gate is teacher-forced: for every position
    of the tensor=1 answer, the tensor=4 replica is asked for the one next
    token after the same prefix. bf16 partial sums are reduced in another
    order across four chips, and random weights put the top two of 128,256
    logits a few hundredths apart, so a few per cent of positions may
    legitimately flip; a wrong sharding agrees nowhere. (On the chip the
    random model decodes every prompt to one token, so this says less
    than it looks: the kernels' arithmetic is held to the references by
    the one-chip run's parity phase.)"""
    from ray_tpu.parallel import MeshConfig
    n_new = 12

    def reference(ask, prompts):
        return {k: ask(prompts[k], n_new)["tokens"] for k in ("a", "b")}

    base = serve_session(plan, SERVE_LAYERS_4CHIP, None, "tensor=1",
                         reference)
    expected = base["result"]

    def compare(ask, prompts):
        free = {k: ask(prompts[k], n_new)["tokens"] for k in ("a", "b")}
        forced = concurrently([
            (lambda k=k, i=i: ask(prompts[k] + expected[k][:i], 1)
             ["tokens"][0])
            for k in ("a", "b") for i in range(n_new)])
        want = [t for k in ("a", "b") for t in expected[k]]
        return {"free": free,
                "agree": sum(int(x == y) for x, y in zip(forced, want)),
                "of": len(want)}

    sharded = serve_session(
        plan, SERVE_LAYERS_4CHIP, MeshConfig(data=1, tensor=plan.chips),
        f"tensor={plan.chips}", compare)
    check(sharded["stats"]["tp"] == plan.chips,
          f"replica reports tp={sharded['stats']['tp']}")
    check_spread(plan, f"serve tensor={plan.chips}", sharded["memory"])
    got = sharded["result"]
    say(f"smoke: token ids tensor=1      {json.dumps(expected)}")
    say(f"smoke: token ids tensor={plan.chips}      {json.dumps(got['free'])}")
    say(f"smoke: free-running ids identical: {got['free'] == expected}; "
        f"teacher-forced agreement {got['agree']}/{got['of']}")
    check(got["agree"] >= math.ceil(0.8 * got["of"]),
          f"tensor={plan.chips} agrees with tensor=1 on only "
          f"{got['agree']}/{got['of']} next tokens")
    return sharded["device"]


# ---------------------------------------------------------------------------
# train: JaxTrainer.fit
# ---------------------------------------------------------------------------

def worker_device(expect_platform: str) -> Dict[str, Any]:
    """In a worker: the device as JAX reports it. Asked before any weights
    are made — a lease that did not become a chip stops here."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != expect_platform:
        raise RuntimeError(f"worker is on {device}, expected platform "
                           f"{expect_platform!r}")
    return device


def train_loop(config: Dict[str, Any]) -> Dict[str, Any]:
    """train_loop_per_worker: runs in the worker that holds the chip(s)."""
    device = worker_device(config["platform"])

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import LlamaModel, cross_entropy_loss
    from ray_tpu.ops.attention import pallas_kernels
    from ray_tpu.parallel import (MeshConfig, create_train_state,
                                  make_train_step)
    from ray_tpu.parallel.mesh import collective_counts

    devices = jax.devices()
    ctx = train.get_context()
    mesh_config = ctx.mesh_config() or MeshConfig(data=1)
    mesh = ctx.get_mesh() if ctx.mesh_config() is not None \
        else mesh_config.build(devices[:1])
    rules = mesh_config.rules_dict()
    model_cfg = config["model"]
    batch, seq = config["shape"]
    # bench.py's recipe: bf16 params + adafactor's factored fp32 moments
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adafactor(learning_rate=1e-3))
    # ONE fixed batch from the seed, so the loss must fall
    data = {"tokens": jax.random.randint(
        jax.random.PRNGKey(config["seed"] + 1), (batch, seq), 0,
        model_cfg.vocab_size)}

    def placed_and_compiled(cfg):
        model = LlamaModel(cfg)
        state = create_train_state(
            jax.random.PRNGKey(config["seed"]), model,
            jnp.zeros((batch, seq), jnp.int32), mesh, tx, rules)
        placed = [d.memory_stats() for d in devices]

        def loss_fn(params, data):
            logits = model.apply({"params": params}, data["tokens"])
            return cross_entropy_loss(logits[:, :-1], data["tokens"][:, 1:])

        step = make_train_step(loss_fn, mesh, rules, state=state)
        with mesh:
            t0 = time.perf_counter()
            compiled = step.lower(state, data).compile()
            return state, placed, compiled, time.perf_counter() - t0

    state, placed, compiled, compile_s = placed_and_compiled(model_cfg)
    text = compiled.as_text()
    kernels = pallas_kernels(text)
    collectives = {model_cfg.num_layers: collective_counts(text)}
    with mesh:
        losses, step_s = [], []
        for i in range(config["steps"]):
            t0 = time.perf_counter()
            state, metrics = compiled(state, data)
            loss = float(jax.device_get(metrics["loss"]))
            step_s.append(time.perf_counter() - t0)
            losses.append(loss)
            train.report({"step": i, "loss": loss, "losses": list(losses),
                          "step_time_s": step_s[-1]})
    memory = [d.memory_stats() for d in devices]
    if mesh.size > 1:
        # the same step one layer shallower, compiled and never run: a
        # layout the partitioner is left to guess shows as all-to-alls
        # that grow with depth
        del state, compiled
        shallow = dataclasses.replace(
            model_cfg, num_layers=model_cfg.num_layers - 1)
        collectives[shallow.num_layers] = collective_counts(
            placed_and_compiled(shallow)[2].as_text())
    return {"pid": os.getpid(), "device": device, "kernels": kernels,
            "collectives": collectives, "losses": losses,
            "compile_s": compile_s, "step_s": step_s, "placed": placed,
            "memory": memory}


class KernelParity:
    """Actor in a chip-owning worker: the Pallas kernels of both paths
    against the repo's plain jnp implementations of the same semantics,
    on a small input at the model's attention widths. Greedy ids cannot
    show this: with random weights every prompt decodes to one token."""

    def run(self, config: Dict[str, Any]) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.llama import Attention
        from ray_tpu.ops.attention import (attention_reference,
                                           flash_attention)
        from ray_tpu.parallel.mesh import unbox

        cfg = config["model"]
        out: Dict[str, Any] = {
            "pid": os.getpid(),
            "device": worker_device(config["platform"])}
        heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        keys = jax.random.split(jax.random.PRNGKey(config["seed"]), 8)

        def err(got, want):
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            return float(np.abs(got - want).max() / np.abs(want).max())

        # flash forward + backward against the O(S^2) float32 reference
        seq = config["flash_seq"]
        q = jax.random.normal(keys[0], (1, heads, seq, hd), cfg.dtype)
        k = jax.random.normal(keys[1], (1, kvh, seq, hd), cfg.dtype)
        v = jax.random.normal(keys[2], (1, kvh, seq, hd), cfg.dtype)
        w = jax.random.normal(keys[3], (1, heads, seq, hd), jnp.float32)

        def scored(attend):
            def f(q_, k_, v_):
                y = attend(q_, k_, v_)
                return (y.astype(jnp.float32) * w).sum(), y
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))
        (_, y), grads = scored(
            lambda *a: flash_attention(*a, force_pallas=True))(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, y_ref), grads_ref = scored(lambda q_, k_, v_:
                                           attention_reference(
                q_.astype(jnp.float32), k_.astype(jnp.float32),
                v_.astype(jnp.float32)))(q, k, v)
        out["flash_fwd"] = err(y, y_ref)
        out["flash_bwd"] = max(err(g, r) for g, r in zip(grads, grads_ref))

        # one Attention layer's decode step: paged branch (page scatter +
        # paged kernel) against its dense-cache branch, same cache content;
        # at the model's grouping and at the hybrid and expert cells' (5
        # and 16 query heads a kv head), with one dead row (length 0, a
        # table of null pages: what the engine stages for a free slot)
        batch, page, per_seq = 5, config["page_size"], 16
        span = page * per_seq
        x = jax.random.normal(keys[4], (batch, 1, cfg.hidden_size),
                              cfg.dtype)
        lengths = jnp.asarray([0, 1, page + 3, span // 2, span - 2],
                              jnp.int32)
        # row b's token t lives in page 1 + b*per_seq + t//page (0 = null)
        tables = 1 + jnp.arange(batch * per_seq, dtype=jnp.int32).reshape(
            batch, per_seq)
        tables = tables.at[0].set(0)
        for label, q_heads, kv_heads in (
                ("paged_decode", heads, kvh),
                ("paged_decode_5to1", 20, 4), ("paged_decode_16to1", 32, 2)):
            layer = Attention(dataclasses.replace(
                cfg, num_heads=q_heads, num_kv_heads=kv_heads, head_dim=hd))
            params = jax.jit(lambda r: unbox(layer.init(
                r, x, lengths[:, None])["params"]))(keys[5])
            ck = jax.random.normal(keys[6], (batch, kv_heads, span, hd),
                                   cfg.dtype)
            cv = jax.random.normal(keys[7], (batch, kv_heads, span, hd),
                                   cfg.dtype)

            def pooled(c):
                body = c.transpose(1, 0, 2, 3).reshape(
                    c.shape[1], batch * per_seq, page, hd)
                return jnp.concatenate(
                    [jnp.zeros_like(body[:, :1]), body], 1)
            apply = jax.jit(lambda cache, index: layer.apply(
                {"params": params}, x, lengths[:, None], cache, index)[0])
            paged = apply({"k": pooled(ck), "v": pooled(cv),
                           "block_tables": tables, "lengths": lengths}, None)
            out[label] = err(paged, apply((ck, cv), lengths))
        return out


def phase_kernel_parity(plan: Plan) -> None:
    """In the running cluster, before the trainer takes the chip."""
    import ray_tpu
    model = plan.model(1)
    options = {} if plan.rehearse else {"num_tpus": plan.chips}
    probe = ray_tpu.remote(KernelParity).options(**options).remote()
    got = ray_tpu.get(probe.run.remote({
        "model": model, "seed": plan.seed, "platform": plan.platform,
        "flash_seq": 128 if plan.rehearse else 1024,
        "page_size": plan.engine(1).page_size}), timeout=REPLICA_WAIT_S)
    ray_tpu.kill(probe)
    errors = {k: v for k, v in got.items()
              if k.startswith(("flash_", "paged_decode"))}
    say(f"smoke: kernels against the jnp references on "
        f"{json.dumps(got['device'])}, max error over max value: "
        f"{json.dumps({k: round(v, 5) for k, v in errors.items()})}")
    wait_pid_gone(got["pid"], "kernel parity worker")
    check_device(plan, got["device"], "kernel parity worker")
    # bf16 keeps 8 bits: a few ulps of 2**-8 on outputs of size one
    check(all(math.isfinite(v) and v <= 2 ** -5 for v in errors.values()),
          f"a kernel disagrees with its reference beyond bf16: {errors}")


def fit_once(plan: Plan, mesh_axes: Optional[Dict[str, int]], steps: int,
             label: str) -> Dict[str, Any]:
    """One JaxTrainer.fit() in a running cluster; returns the worker's
    account once its process has exited."""
    from ray_tpu.train import JaxTrainer
    model = plan.model(TRAIN_LAYERS)
    batch, seq = plan.train_shape()
    say(f"smoke: train[{label}] llama3-8b widths hidden={model.hidden_size} "
        f"vocab={model.vocab_size} depth={model.num_layers} of "
        f"{LLAMA3_8B_LAYERS} params={model.num_params()} "
        f"batch={batch}x{seq} steps={steps} mesh={mesh_axes}")
    result = JaxTrainer(
        train_loop,
        train_loop_config={"model": model, "shape": (batch, seq),
                           "steps": steps, "seed": plan.seed,
                           "platform": plan.platform},
        scaling_config=plan.scaling(mesh_axes)).fit()
    if result.error is not None:
        raise result.error
    worker = result.worker_returns[0]
    wait_pid_gone(worker["pid"], f"train[{label}] worker")
    check_device(plan, worker["device"], f"train[{label}] worker")
    reported = result.metrics["losses"]  # what came through report()
    check(len(reported) == steps and reported == worker["losses"],
          f"report() carried {reported}, the loop saw {worker['losses']}")
    check(all(math.isfinite(x) for x in reported),
          f"non-finite loss in {reported}")
    kernels = worker["kernels"]
    say(f"smoke: train[{label}] worker pid {worker['pid']} on "
        f"{json.dumps(worker['device'])}; losses "
        f"{json.dumps([round(x, 4) for x in reported])}; step kernels "
        f"{json.dumps(kernels)}; step collectives by depth "
        f"{json.dumps(worker['collectives'])}")
    if not plan.rehearse:
        check(all(kernels.get(k) for k in
                  ("flash_fwd", "flash_bwd_kv", "flash_bwd_q")),
              f"compiled train step holds {kernels} — expected the flash "
              "forward and backward tpu_custom_calls")
    say(f"smoke observation: train[{label}] one cold run: step program "
        f"compiled in {worker['compile_s']:.1f}s; step wall "
        + json.dumps([round(s, 3) for s in worker["step_s"]]))
    say_memory(f"train[{label}] after placement", worker["placed"])
    say_memory(f"train[{label}] after the steps", worker["memory"])
    return worker


def phase_train(plan: Plan) -> Dict[str, Any]:
    import ray_tpu
    start_cluster(plan)
    try:
        phase_kernel_parity(plan)
        worker = fit_once(plan, None, 5, "1 chip")
    finally:
        ray_tpu.shutdown()
    losses = worker["losses"]
    check(losses[-1] < losses[0],
          f"loss did not fall on the fixed batch: {losses}")
    return worker["device"]


def phase_train_mesh(plan: Plan) -> Dict[str, Any]:
    """One worker holding four chips on an fsdp=2 x tensor=2 mesh against
    the same seed and batch on one device: the loss of step 1 (before any
    update) must agree to bf16 tolerance."""
    import ray_tpu
    start_cluster(plan)
    try:
        single = fit_once(plan, None, 1, "one device")
        meshed = fit_once(plan, {"data": 1, "fsdp": 2, "tensor": 2}, 3,
                          "fsdp=2 x tensor=2")
    finally:
        ray_tpu.shutdown()
    a, b = single["losses"][0], meshed["losses"][0]
    say(f"smoke: step-1 loss one device {a:.5f}, 2x2 mesh {b:.5f}, "
        f"difference {abs(a - b):.5f}")
    # bf16 has 8 bits of mantissa: 2**-7 relative on a loss of ~ln(vocab)
    check(abs(a - b) <= 2 ** -7 * abs(a),
          f"step-1 losses differ beyond bf16 tolerance: {a} vs {b}")
    check(meshed["losses"][-1] < meshed["losses"][0],
          f"loss did not fall on the mesh: {meshed['losses']}")
    check_spread(plan, "train mesh after placement", meshed["placed"])
    (_, shallow), (_, deep) = sorted(meshed["collectives"].items())
    check(deep.get("all-to-all", 0) <= shallow.get("all-to-all", 0),
          f"the mesh step's all-to-alls grow with depth ({shallow} -> "
          f"{deep}): the model's activation layout did not engage")
    return meshed["device"]


# ---------------------------------------------------------------------------

def run(plan: Plan) -> Dict[str, Any]:
    from ray_tpu._internal import accel, rpc
    from ray_tpu.accelerators.tpu import compile_cache_dir

    if not plan.rehearse:
        platforms = os.environ.get("JAX_PLATFORMS", "")
        check(not platforms or "tpu" in platforms.split(","),
              f"JAX_PLATFORMS={platforms!r} keeps JAX off the TPU")
    cache_dir = compile_cache_dir()  # workers inherit it through os.environ

    def cache_entries() -> int:
        if not os.path.isdir(cache_dir):
            return 0
        # jax keeps an access-time file beside each entry for its LRU
        return sum(not name.endswith("-atime")
                   for name in os.listdir(cache_dir))

    before = cache_entries()
    say(f"smoke: compile cache {cache_dir} holds {before} entries")
    say("smoke: rpc transport "
        + ("native (src/fastrpc.cpp built)" if rpc._native_io() is not None
           else "python asyncio (no native build)"))
    if plan.chips == 1:
        device = phase_serve(plan)
        trained_on = phase_train(plan)
    else:
        device = phase_serve_tensor_parallel(plan)
        trained_on = phase_train_mesh(plan)
    check(trained_on == device,
          f"serve ran on {device}, train on {trained_on}")
    say(f"smoke: compile cache {cache_dir} holds {cache_entries()} entries "
        f"({cache_entries() - before} added by this run)")
    check(not accel.backend_initialized(),
          "the driver process opened a JAX backend")
    return device


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU, tiny widths, same control flow; never ok")
    args = parser.parse_args()
    plan = Plan(chips=args.chips, rehearse=args.rehearse, seed=args.seed)
    device = run(plan)
    if plan.rehearse:
        say("smoke: rehearsal finished on " + json.dumps(device)
            + " — a rehearsal is never a pass")
        return 3
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException as e:  # noqa: BLE001 — report, exit non-zero
        import traceback
        traceback.print_exc()
        say(f"smoke: FAILED: {type(e).__name__}: {e}")
        code = 1
    OUT.flush()
    sys.stderr.flush()
    # every cluster was shut down above; leave without waiting on
    # whatever daemon threads the runtime still holds
    os._exit(code)
